"""graftlint core: findings, source units, pragmas, baseline, pass manager.

Design notes
------------
A *finding* is identified for baselining purposes by
``(check, path, context, message)`` — deliberately **not** by line number,
so unrelated edits above a grandfathered finding do not churn the baseline
diff. ``context`` is the qualified name of the enclosing function (dots
join nesting levels; ``<module>`` at file scope). Identical findings in
one context are matched count-aware: the baseline absorbs as many
occurrences as it recorded and any extra is new.

Pragmas: ``# graftlint: allow=<check>(<reason>)``.
On a comment-only line the allowance covers the whole file; trailing a
code line it covers that line only. The reason is mandatory — an empty
one (and an unknown check name) is itself reported under the ``pragma``
check, so suppressions stay auditable.
"""

from __future__ import annotations

import ast
import io
import json
import os
import re
import tokenize
from collections import Counter
from dataclasses import dataclass, field


# --------------------------------------------------------------------------
# findings
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class Finding:
    check: str
    path: str           # repo-relative, posix separators
    line: int
    message: str
    context: str = "<module>"

    def key(self):
        """Line-number-free identity used for baseline matching."""
        return f"{self.check}|{self.path}|{self.context}|{self.message}"

    def render(self):
        return f"{self.path}:{self.line}: [{self.check}] {self.message}" + (
            f" (in {self.context})" if self.context != "<module>" else "")

    def as_dict(self):
        return {"check": self.check, "path": self.path,
                "context": self.context, "message": self.message}


@dataclass
class LintResult:
    findings: list = field(default_factory=list)     # new (not baselined)
    baselined: list = field(default_factory=list)
    suppressed: list = field(default_factory=list)   # pragma-allowed
    stale_baseline: list = field(default_factory=list)  # keys no longer hit

    @property
    def all_findings(self):
        return self.findings + self.baselined


# --------------------------------------------------------------------------
# source units + pragmas
# --------------------------------------------------------------------------

_PRAGMA_RE = re.compile(r"graftlint:\s*allow=([A-Za-z0-9_-]+)\(([^)]*)\)")
_PRAGMA_MARK = re.compile(r"graftlint:\s*(allow|hotpath)\b")


class SourceUnit:
    """One parsed file: AST + raw lines + the pragmas found in it."""

    def __init__(self, path, source):
        self.path = path                     # repo-relative posix
        self.source = source
        self.lines = source.splitlines()
        self.tree = None
        self.parse_error = None
        try:
            self.tree = ast.parse(source, filename=path)
        except SyntaxError as e:
            self.parse_error = e
        # check name -> reason (whole file) / line -> {check: reason}
        self.file_allows = {}
        self.line_allows = {}
        self.hotpath_lines = set()
        self.pragma_findings = []
        self._scan_pragmas()

    def _comments(self):
        """(line, comment_text, code_before) for every real COMMENT token
        — tokenizing (not string-scanning) so pragma syntax quoted in a
        docstring is never mistaken for a pragma."""
        try:
            tokens = tokenize.generate_tokens(
                io.StringIO(self.source).readline)
            for tok in tokens:
                if tok.type == tokenize.COMMENT:
                    line = tok.start[0]
                    before = self.lines[line - 1][:tok.start[1]].strip()
                    yield line, tok.string, before
        except (tokenize.TokenError, IndentationError, SyntaxError):
            return

    def _scan_pragmas(self):
        for i, comment, before in self._comments():
            if "graftlint" not in comment:
                continue
            if "hotpath" in comment and _PRAGMA_MARK.search(comment) \
                    and "allow" not in comment:
                self.hotpath_lines.add(i)
                continue
            matches = list(_PRAGMA_RE.finditer(comment))
            if not matches:
                if _PRAGMA_MARK.search(comment):
                    self.pragma_findings.append(Finding(
                        "pragma", self.path, i,
                        "malformed graftlint pragma (expected "
                        "allow=<check>(<reason>))"))
                continue
            for m in matches:
                check, reason = m.group(1), m.group(2).strip()
                if not reason:
                    self.pragma_findings.append(Finding(
                        "pragma", self.path, i,
                        f"pragma allow={check} has no reason — every "
                        "suppression must say why"))
                    continue
                if check not in checker_names() and check != "pragma":
                    self.pragma_findings.append(Finding(
                        "pragma", self.path, i,
                        f"pragma allows unknown check {check!r}"))
                    continue
                if before:
                    self.line_allows.setdefault(i, {})[check] = reason
                else:
                    self.file_allows.setdefault(check, reason)

    def allows(self, finding):
        if finding.check in self.file_allows:
            return True
        return finding.check in self.line_allows.get(finding.line, {})


# --------------------------------------------------------------------------
# AST helpers shared by checkers
# --------------------------------------------------------------------------

def dotted(node):
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def root_name(node):
    """The base Name of an Attribute/Subscript/Call chain, else None."""
    while isinstance(node, (ast.Attribute, ast.Subscript, ast.Call)):
        node = node.func if isinstance(node, ast.Call) else node.value
    return node.id if isinstance(node, ast.Name) else None


def str_const(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def iter_defs(tree):
    """Yield ``(qualname, class_name, node)`` for every function in the
    module; qualname joins nesting with dots (no ``<locals>`` noise)."""
    out = []

    def walk(node, prefix, cls):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{prefix}.{child.name}" if prefix else child.name
                out.append((q, cls, child))
                walk(child, q, cls)
            elif isinstance(child, ast.ClassDef):
                q = f"{prefix}.{child.name}" if prefix else child.name
                walk(child, q, child.name)
            else:
                walk(child, prefix, cls)

    walk(tree, "", None)
    return out


def local_names(fn):
    """Names bound in ``fn``'s own scope (params, assignments, for/with/
    comprehension targets, inner defs) — everything else is free."""
    names = set()
    a = fn.args
    for arg in (a.posonlyargs + a.args + a.kwonlyargs
                + ([a.vararg] if a.vararg else [])
                + ([a.kwarg] if a.kwarg else [])):
        names.add(arg.arg)

    class V(ast.NodeVisitor):
        def visit_FunctionDef(self, node):
            names.add(node.name)  # inner def binds its name; skip its body

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_Lambda(self, node):
            pass

        def visit_ClassDef(self, node):
            names.add(node.name)

        def visit_Name(self, node):
            if isinstance(node.ctx, (ast.Store, ast.Del)):
                names.add(node.id)

        def visit_Global(self, node):
            names.difference_update(node.names)

        def visit_Nonlocal(self, node):
            names.difference_update(node.names)

    v = V()
    for stmt in fn.body:
        v.visit(stmt)
    return names


def enclosing_context(tree):
    """line -> qualname of the innermost enclosing function (for finding
    contexts). Built once per unit, consumed by checkers via ctx_of."""
    spans = []  # (start, end, qualname), innermost wins by later start
    for qual, _cls, node in iter_defs(tree):
        end = getattr(node, "end_lineno", node.lineno)
        spans.append((node.lineno, end, qual))
    spans.sort()
    return spans


def ctx_of(spans, line):
    best = "<module>"
    for start, end, qual in spans:
        if start <= line <= end:
            best = qual
        elif start > line:
            break
    return best


# --------------------------------------------------------------------------
# checker registry
# --------------------------------------------------------------------------

def all_checkers():
    from .checkers import ALL_CHECKERS

    return list(ALL_CHECKERS)


def checker_names():
    return [c.name for c in all_checkers()]


class TreeContext:
    """What cross-file checkers need: the repo root, every unit, and lazy
    access to the docs the catalogues must stay in sync with."""

    def __init__(self, root, units):
        self.root = root
        self.units = units
        self._docs = {}
        self._callgraph = None

    def callgraph(self):
        """The whole-program :class:`~analysis.callgraph.CallGraph` over
        this tree, built once and shared by every checker that asks."""
        if self._callgraph is None:
            from . import callgraph
            self._callgraph = callgraph.CallGraph.build(self)
        return self._callgraph

    def unit(self, path):
        for u in self.units:
            if u.path == path:
                return u
        return None

    def doc_text(self, relpath):
        """Contents of a docs file, or None when absent (fixture trees)."""
        if relpath not in self._docs:
            full = os.path.join(self.root, relpath)
            try:
                with open(full, encoding="utf-8") as f:
                    self._docs[relpath] = f.read()
            except OSError:
                self._docs[relpath] = None
        return self._docs[relpath]


# --------------------------------------------------------------------------
# file collection + suite driver
# --------------------------------------------------------------------------

#: tree scope: the framework package. Tools and tests stay out — they are
#: allowed to sync, read environs and poke locks.
_SCOPE_DIRS = ("mxnet_tpu",)


def default_files(root):
    files = []
    for d in _SCOPE_DIRS:
        for dirpath, dirnames, filenames in os.walk(os.path.join(root, d)):
            dirnames.sort()
            if "__pycache__" in dirnames:
                dirnames.remove("__pycache__")
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    files.append(os.path.join(dirpath, fn))
    return files


def _load_units(root, files):
    units = []
    for full in files:
        rel = os.path.relpath(full, root).replace(os.sep, "/")
        try:
            with open(full, encoding="utf-8") as f:
                src = f.read()
        except OSError as e:
            units.append(SourceUnit(rel, ""))
            units[-1].parse_error = e
            continue
        units.append(SourceUnit(rel, src))
    return units


def build_context(root, files=None):
    """A :class:`TreeContext` over ``files`` (default: the framework
    scope) without running any checker — the CLI's ``--callgraph`` debug
    mode and ad-hoc analysis scripts start here."""
    root = os.path.abspath(root)
    units = _load_units(root, files if files is not None
                        else default_files(root))
    return TreeContext(root, units)


def run_suite(root, files=None, checks=None, baseline=None):
    """Lint ``files`` (default: the framework scope under ``root``).

    ``checks``: iterable of checker names to run (default all).
    ``baseline``: a baseline Counter from :func:`load_baseline`, or None.
    Returns a :class:`LintResult`.
    """
    root = os.path.abspath(root)
    units = _load_units(root, files if files is not None
                        else default_files(root))
    ctx = TreeContext(root, units)
    selected = [c for c in all_checkers()
                if checks is None or c.name in set(checks)]

    raw = []
    for u in units:
        if u.parse_error is not None:
            raw.append(Finding(
                "parse", u.path,
                getattr(u.parse_error, "lineno", 0) or 0,
                f"file does not parse: {u.parse_error}"))
        raw.extend(u.pragma_findings)
    for checker in selected:
        raw.extend(checker().run(ctx))

    result = LintResult()
    by_path = {u.path: u for u in units}
    kept = []
    for f in sorted(raw, key=lambda f: (f.path, f.line, f.check, f.message)):
        unit = by_path.get(f.path)
        if unit is not None and f.check != "pragma" and unit.allows(f):
            result.suppressed.append(f)
        else:
            kept.append(f)

    remaining = Counter(baseline or {})
    for f in kept:
        if remaining.get(f.key(), 0) > 0:
            remaining[f.key()] -= 1
            result.baselined.append(f)
        else:
            result.findings.append(f)
    result.stale_baseline = sorted(
        k for k, n in remaining.items() if n > 0)
    return result


# --------------------------------------------------------------------------
# baseline IO
# --------------------------------------------------------------------------

def load_baseline(path):
    """Baseline file -> Counter of finding keys (missing file = empty)."""
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except OSError:
        return Counter()
    keys = Counter()
    for entry in data.get("findings", []):
        keys[
            f"{entry['check']}|{entry['path']}|{entry['context']}|"
            f"{entry['message']}"
        ] += 1
    return keys


def write_baseline(findings, path):
    """Write ``findings`` as the new baseline, deterministically: entries
    are path-relative, sorted, line-number free — diffs stay reviewable."""
    entries = sorted(
        (f.as_dict() for f in findings),
        key=lambda e: (e["check"], e["path"], e["context"], e["message"]))
    payload = {
        "_comment": (
            "graftlint grandfathered findings. Regenerate with "
            "`python tools/lint.py --write-baseline`; shrink it by fixing "
            "findings, never grow it by hand."),
        "version": 1,
        "findings": entries,
    }
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path
