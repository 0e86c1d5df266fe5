"""Profiler: execution tracing, and the per-operator reading of a trace.

Reference: ``python/mxnet/profiler.py:10-38`` + the in-engine profiler
(``src/engine/profiler.{h,cc}``), which reports time per operator. TPU
mapping (SURVEY.md §5): the jax/XLA profiler captures the device trace
(``profiler_set_state('run')`` ... ``dump_profile()``), and
:func:`device_table` reads it back by the graph's own names:

* **Scopes.** The executor lowers every Symbol node under
  ``Operator[node]`` (:func:`node_scope`; ``Operator[node]xN`` for N
  ``FullyConnected`` nodes that run as one matmul) and its own phases under
  ``executor.<phase>`` (:func:`phase_scope`: ``update`` with one
  ``param[<name>]`` inside it a parameter, ``guard``, ``unpack``,
  ``repack``, ``accumulate``, ``window_data``). jax wraps a scope in
  ``jvp(...)`` / ``transpose(...)`` as it differentiates, and marks the
  forward that runs again under ``MXNET_BACKWARD_DO_MIRROR=1`` with
  ``rematted_computation``; :func:`parse_scope` recovers ``(operator, node,
  pass)`` from the string the trace carries for a device operation
  (``tf_op``), pass one of ``forward``, ``backward``, ``recompute``,
  ``update``, ``other``. A scope holds no ``:`` and no ``/``.
* **One reader.** :func:`device_table` sums the device's operations by
  operator and pass, by node, by XLA program, and names every idle gap by
  the ``telemetry.span`` the host was in. :func:`reduce_trace` is its
  arithmetic, pure over plain tuples; :func:`load_xplane` is the thin part
  that opens the file. ``tools/trace_table.py`` prints the tables.
* **Memory.** :func:`memory_table` is the same question asked of memory:
  every resolved program's bytes beside the device's own statistics.

``dump_profile`` also honours the reference's file contract by extracting
the chrome-trace JSON out of the captured run and writing it to
``filename``, loadable in chrome://tracing / Perfetto.
``MXNET_PROFILER_AUTOSTART`` starts tracing at import (reference
env_var.md:69-78).

Every tracing entry point degrades gracefully when jax profiling is
unavailable (stripped builds, backends without a profiler plugin): the
operation becomes a warn-once no-op instead of raising at import or
construction time, for profiling must never be able to take a training
job down. The host half of the timeline lives in
:mod:`mxnet_tpu.telemetry`: while a trace runs, every ``telemetry.span``
is on its host plane already.
"""

from __future__ import annotations

import glob
import gzip
import logging
import os
import re
import shutil
import struct

_state = {"mode": "symbolic", "filename": "profile.json", "running": False}

_warned = set()


def _warn_once(key, msg):
    if key not in _warned:
        _warned.add(key)
        logging.warning(msg)


def _jax_profiler():
    """The jax profiler module, or None (warn once) when unavailable."""
    try:
        import jax

        return jax.profiler
    except Exception as e:  # ImportError, stripped builds, plugin errors
        _warn_once("import", f"jax profiler unavailable ({e}); "
                             "device profiling is a no-op")
        return None


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """Set up the profiler (reference profiler_set_config)."""
    _state["mode"] = mode
    _state["filename"] = filename


def profiler_set_state(state="stop"):
    """'run' starts a jax profiler trace; 'stop' ends it. A backend whose
    profiler cannot start/stop logs one warning and leaves the state
    unchanged instead of raising."""
    prof = _jax_profiler()
    if prof is None:
        return
    if state == "run" and not _state["running"]:
        logdir = os.path.splitext(_state["filename"])[0] + "_trace"
        try:
            prof.start_trace(logdir)
        except Exception as e:
            _warn_once("start", f"profiler start_trace failed ({e}); "
                                "device profiling is a no-op")
            return
        _state["running"] = True
        _state["logdir"] = logdir
    elif state == "stop" and _state["running"]:
        try:
            prof.stop_trace()
        except Exception as e:
            _warn_once("stop", f"profiler stop_trace failed ({e})")
        _state["running"] = False


def dump_profile():
    """Write the chrome-trace JSON to the configured filename.

    Returns the filename (reference contract: the file the user set via
    profiler_set_config exists and holds trace-event JSON after this
    call). The raw xplane/TensorBoard artifacts stay in the side logdir
    for deeper analysis.
    """
    if _state["running"]:
        profiler_set_state("stop")
    logdir = _state.get("logdir")
    if not logdir:
        return None
    fname = _state["filename"]
    traces = sorted(glob.glob(
        os.path.join(logdir, "**", "*.trace.json.gz"), recursive=True
    ))
    if traces:
        with gzip.open(traces[-1], "rb") as src, open(fname, "wb") as dst:
            shutil.copyfileobj(src, dst)
        return fname
    return None


class trace_annotation:
    """Context manager naming a region in the device trace
    (maps to jax.profiler.TraceAnnotation). A no-op (warn once) when jax
    profiling is unavailable, so instrumented user code keeps running."""

    def __init__(self, name):
        self.name = name
        self._ann = None
        prof = _jax_profiler()
        ann_cls = getattr(prof, "TraceAnnotation", None) if prof else None
        if ann_cls is None:
            if prof is not None:
                _warn_once("annotation", "jax profiler has no "
                                         "TraceAnnotation; annotations are "
                                         "no-ops")
            return
        try:
            self._ann = ann_cls(name)
        except Exception as e:
            _warn_once("annotation", f"TraceAnnotation failed ({e}); "
                                     "annotations are no-ops")

    def __enter__(self):
        if self._ann is None:
            return self
        return self._ann.__enter__()

    def __exit__(self, *a):
        if self._ann is None:
            return False
        return self._ann.__exit__(*a)


# --- scopes: what the executor writes and the reader parses ----------------

PASSES = ("forward", "backward", "recompute", "update", "other")
_PHASE_PREFIX = "executor."
_UNSAFE = re.compile(r"[^\w.+\-]")          # in a name: ':' '/' '[' ']' ...
_WRAPPED = re.compile(r"^((?:\w+\()*)(.*?)\)*$")  # jvp( transpose( ... )
_NODE = re.compile(r"^([A-Za-z_]\w*)\[([^\[\]]*)\](?:x(\d+))?$")
REMAT_MARK = "rematted_computation"


def node_scope(op, node, group=0):
    """``Operator[node]``: the scope a Symbol node lowers under;
    ``Operator[node]xN`` for ``group`` = N nodes that run as one."""
    scope = f"{op}[{_UNSAFE.sub('_', node)}]"
    return f"{scope}x{group}" if group else scope


def phase_scope(phase):
    """``executor.<phase>``: the scope of one of the executor's own
    phases of a program."""
    return _PHASE_PREFIX + phase


def param_scope(name):
    """``param[<name>]``: one parameter's update, inside
    ``executor.update``."""
    return f"param[{_UNSAFE.sub('_', name)}]"


def parse_scope(tf_op):
    """``(operator, node, pass)`` of a device operation from the name stack
    its trace event carries (``tf_op``; HLO ``metadata.op_name``), or None
    where the stack names no node and no executor phase.

    The outermost scope decides: ``Operator[node]`` bare or in ``jvp(`` is
    ``forward``; in ``transpose(`` it is ``backward``, or ``recompute``
    where a later part is jax's ``rematted_computation`` (under
    ``jax.checkpoint`` the first forward carries no mark, the one that
    runs again inside the backward does). ``executor.update/param[w]`` is
    ``("update", "w", "update")``; every other ``executor.<phase>`` is
    ``(phase, None, "other")``."""
    parts = str(tf_op).split(":", 1)[0].split("/")
    if len(parts) < 2:  # a program's argument, named ``upd_vals[0]``
        return None
    for i, part in enumerate(parts):
        wrappers, core = _WRAPPED.match(part).groups()
        if core.startswith(_PHASE_PREFIX):
            phase = core[len(_PHASE_PREFIX):]
            if phase != "update":
                return phase, None, "other"
            for later in parts[i + 1:]:
                m = _NODE.match(_WRAPPED.match(later).group(2))
                if m and m.group(1) == "param":
                    return "update", m.group(2), "update"
            return "update", None, "update"
        m = _NODE.match(core)
        if not m:
            continue
        operator, node, group = m.groups()
        if group:
            node = f"{node} (x{group})"
        if "transpose(" not in wrappers:
            return operator, node, "forward"
        remat = any(REMAT_MARK in later for later in parts[i + 1:])
        return operator, node, "recompute" if remat else "backward"
    return None


def inner_scope(tf_op):
    """What follows the node in a name stack that :func:`parse_scope`
    reads as a node's: ``jit(take_along_axis)/gather`` of
    ``jit(_step)/jvp(MoE[l1_moe])/jit(take_along_axis)/gather:``, the
    operator's own ``jax.named_scope``s, the ``jit`` functions it calls
    and the primitive. After the LAST part that names a node (backward
    reads ``transpose(jvp(MoE[m]))/jvp(MoE[m])/...``), less jax's own
    ``checkpoint`` and ``rematted_computation`` parts, which the pass
    already says. ``""`` where the stack names no node."""
    parts = str(tf_op).split(":", 1)[0].split("/")
    for i in range(len(parts) - 1, -1, -1):
        if _NODE.match(_WRAPPED.match(parts[i]).group(2)):
            return "/".join(
                part for part in parts[i + 1:]
                if part != "checkpoint" and REMAT_MARK not in part)
    return ""


# --- the reader: a trace by operator and pass, program, host span ----------

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HLO_PLANE = "/host:metadata"
STEP_SPANS = ("fit.step", "fit.dispatch")
# a telemetry.span's name: dotted lower case (jax's own host events are not)
_SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
UNSCOPED = "unscoped"
STALE_CACHE_HINT = (
    "most device time carries no scope: these executables were probably "
    "read from a compilation cache that a tree without scopes filled "
    "(jax's cache key leaves debug info out, so an old entry answers for "
    "the new program, names and all); clear the cache directory "
    "(JAX_COMPILATION_CACHE_DIR, or <checkout>/.jax_cache) and trace again")


def _short(text):
    """``%fusion.8 = bf16[...] fusion(...)`` -> ``fusion.8``: the trace
    names a device operation by its whole HLO line."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def _kind(text):
    """``fusion``: an operation's XLA name without its serial number."""
    return re.sub(r"\.\d+$", "", _short(text))


_OPERAND = re.compile(r"%([\w.\-]+)")


def _scopes(ops, graph=None):
    """[(scope, inherited)] of ``ops``: :func:`parse_scope` of each one's
    own ``tf_op``, and for an operation the compiler put in with no name
    stack (a layout ``copy``, an asynchronous slice, the copy of an
    updated weight into its donated buffer) the scope of the nearest
    operation of its program that has one: up its operands first (whose
    result it moves), then down its readers. ``graph``, ``{(program,
    instruction): (op_name, [operand names])}`` from the trace's HLO,
    adds the instructions that are no device event (a
    ``get-tuple-element`` between a fusion and the copy of its result);
    without it the operands are read from each event's HLO text."""
    own, operands, readers = {}, {}, {}
    for (program, name), (op_name, names) in (graph or {}).items():
        own[program, name] = parse_scope(op_name)
        operands[program, name] = [(program, n) for n in names]
    keys, seen = [], set()
    for op in ops:  # an instruction runs once a step: one parse each
        text, tf_op, program = op[0], op[1], op[6]
        key = (program, _short(text))
        keys.append(key)
        if key in seen or own.get(key) is not None:
            continue
        seen.add(key)
        own[key] = parse_scope(tf_op)
        if key not in operands:
            names = _OPERAND.findall(text.split(" = ", 1)[-1])
            operands[key] = [(program, n) for n in names]
    for key, names in operands.items():
        for n in names:
            if n in own:
                readers.setdefault(n, []).append(key)

    def nearest(key, edges, depth=4):
        for n in edges.get(key, ()):
            if n in own and own[n] is not None:
                return own[n]
        if depth:
            for n in edges.get(key, ()):
                if n in own:
                    found = nearest(n, edges, depth - 1)
                    if found is not None:
                        return found
        return None

    found = {}
    for key, scope in own.items():
        if scope is None:
            scope = nearest(key, operands) or nearest(key, readers)
            found[key] = (scope, scope is not None)
        else:
            found[key] = (scope, False)
    return [found[key] for key in keys]


def _self_times(ops):
    """[(self_ns, is_leaf)] of ``ops``: an operation nested in another of
    its timeline (the body of a ``while``) is taken out of the one that
    holds it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][2], -ops[i][3]))
    own = [[op[3], True] for op in ops]
    stacks = {}  # timeline -> [(end, index)]
    for i in order:
        start, dur = ops[i][2], ops[i][3]
        stack = stacks.setdefault(ops[i][7] if len(ops[i]) > 7 else 0, [])
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            holder = own[stack[-1][1]]
            holder[0] -= dur
            holder[1] = False
        stack.append((start + dur, i))
    return own


def _rows(groups, busy_ns, steps, top):
    rows = []
    for key, g in sorted(groups.items(), key=lambda kv: -kv[1]["ns"]):
        row = dict(key)
        row["ms"] = g["ns"] * 1e-6
        if steps:
            row["ms_per_step"] = row["ms"] / steps
        row["calls"] = g["calls"]
        row["share"] = g["ns"] / busy_ns if busy_ns else 0.0
        for stat in ("flops", "bytes"):
            if g[stat]:
                row[stat] = g[stat]
        row["xla"] = [[k, ns * 1e-6, n] for k, (ns, n) in sorted(
            g["xla"].items(), key=lambda kv: -kv[1][0])[:3]]
        rows.append(row)
    return rows[:top] if top else rows


def reduce_trace(ops, modules=(), spans=(), window=None, top=None,
                 graph=None, inner=None):
    """The tables of :func:`device_table` from plain tuples, all times in
    nanoseconds on one clock.

    ``ops``: ``[(HLO text, tf_op, start, duration, flops, bytes, program
    id)]``, the operations of one device timeline (events either nest or
    do not overlap; an eighth field tells timelines apart where there are
    several, as XLA:CPU's threads); ``modules``: ``[(program, start, duration)]`` of the
    same device; ``spans``: ``[(name, start, duration)]`` of the host.
    ``window`` names the host span that bounds what is counted (the
    longest of that name); None counts everything. ``graph``: see
    :func:`_scopes`. ``inner`` names one operator whose time is also
    split by :func:`inner_scope` (``by_inner``)."""
    ops = list(ops)
    lo, hi = float("-inf"), float("inf")
    if window is not None:
        marks = [(d, s) for name, s, d in spans if name == window]
        if not marks:
            raise ValueError(f"the trace holds no span {window!r}")
        dur, lo = max(marks)
        hi = lo + dur
    ops = [op for op in ops if lo <= op[2] < hi]
    own = _self_times(ops)
    # a trace stopped from a batch-end callback ends inside the last
    # fit.step root, which is then not in the file; its dispatch is
    steps = max(sum(1 for name, s, _ in spans if name == root
                    and lo <= s < hi) for root in STEP_SPANS)
    by_operator, by_node, by_inner, loose = {}, {}, {}, {}
    busy_ns = unscoped_ns = inherited_ns = 0.0
    for op, (self_ns, leaf), (scope, inherited) in zip(ops, own,
                                                       _scopes(ops, graph)):
        flops, nbytes = op[4], op[5]
        busy_ns += self_ns
        kind = _kind(op[0])
        targets = ()
        if scope is None:
            unscoped_ns += self_ns
            scope = (UNSCOPED, None, "other")
            targets = ((loose, (("name", kind),), kind),)
        elif inherited:
            inherited_ns += self_ns
        operator, node, pass_ = scope
        targets += (
            (by_operator, (("operator", operator), ("pass", pass_)), kind),
            (by_node, (("operator", operator), ("node", node),
                       ("pass", pass_)), kind))
        if operator == inner:  # its rows name the instruction, serial and all
            name = _short(op[0])
            stack = op[1] or (graph or {}).get((op[6], name), ("",))[0]
            targets += ((by_inner, (
                ("inner", "" if inherited else inner_scope(stack)),
                ("pass", pass_)), name),)
        for table, key, xla in targets:
            g = table.setdefault(key, {"ns": 0.0, "calls": 0, "flops": 0.0,
                                       "bytes": 0.0, "xla": {}})
            g["ns"] += self_ns
            g["calls"] += 1
            if leaf:  # a holder's stats count what it holds again
                g["flops"] += flops or 0.0
                g["bytes"] += nbytes or 0.0
            x = g["xla"].setdefault(xla, [0.0, 0])
            x[0] += self_ns
            x[1] += 1
    programs = {}
    for name, s, d in modules:
        if lo <= s < hi:
            g = programs.setdefault(name, [0.0, 0])
            g[0] += d
            g[1] += 1
    out = {
        "window": window, "steps": steps, "busy_ms": busy_ns * 1e-6,
        "by_operator": _rows(by_operator, busy_ns, steps, top),
        "by_node": _rows(by_node, busy_ns, steps, top),
        "by_program": [
            {"program": name, "ms": ns * 1e-6, "calls": n,
             **({"ms_per_step": ns * 1e-6 / steps} if steps else {})}
            for name, (ns, n) in sorted(programs.items(),
                                        key=lambda kv: -kv[1][0])],
        "unscoped": _rows(loose, busy_ns, steps, top or 10),
        "unscoped_share": unscoped_ns / busy_ns if busy_ns else 0.0,
        "inherited_share": inherited_ns / busy_ns if busy_ns else 0.0,
        "idle": _idle(ops, spans, lo, hi, window),
    }
    if inner is not None:
        out["by_inner"] = _rows(by_inner, busy_ns, steps, top)
    if out["unscoped_share"] > 0.10:
        out["hint"] = STALE_CACHE_HINT
    return out


def _idle(ops, spans, lo, hi, window):
    """The device's gaps inside [lo, hi] (the first operation's start to
    the last one's end where the window is open), each named by the
    innermost program span that covers its midpoint. A span of the
    window's own family (``bench.`` for ``bench.traced_slice``) is the
    caller's annotation around the program and names no gap."""
    if not ops:
        return {"total_ms": 0.0, "by_span": {}, "longest": []}
    if lo == float("-inf"):
        lo = min(op[2] for op in ops)
        hi = max(op[2] + op[3] for op in ops)
    family = window.split(".", 1)[0] + "." if window else None
    named = [(d, s, name) for name, s, d in spans
             if _SPAN_NAME.match(name)
             and not (family and name.startswith(family))]
    gaps, at = [], lo
    for s, e in sorted((op[2], op[2] + op[3]) for op in ops):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    by_span, longest = {}, []
    for s, e in gaps:
        mid = (s + e) / 2.0
        cover = [sp for sp in named if sp[1] <= mid <= sp[1] + sp[0]]
        name = min(cover)[2] if cover else "unattributed"
        by_span[name] = by_span.get(name, 0.0) + (e - s) * 1e-6
        longest.append([name, (e - s) * 1e-6, (s - lo) * 1e-6])
    longest.sort(key=lambda g: -g[1])
    return {"total_ms": sum(by_span.values()),
            "by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
            "longest": longest[:10]}


def find_xplane(trace):
    """The newest ``.xplane.pb`` under a profile directory, or ``trace``
    itself where it is a file."""
    if os.path.isfile(trace):
        return trace
    found = sorted(glob.glob(os.path.join(trace, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def _varint(buf, i):
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: a varint as an
    int, everything else as a slice of ``buf``."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            elif kind == 1:
                size = 8
            elif kind == 5:
                size = 4
            else:
                raise ValueError(f"protobuf wire type {kind}")
            value = buf[i:i + size]
            i += size
        yield key >> 3, value


def _text(value):
    return bytes(value).decode("utf-8", "replace")


def _stats(message, field, stat_names):
    """{stat name: value} of the ``XStat`` entries at ``field`` of an
    event or of an event's metadata (numbers and strings; a reference is
    resolved to the string it names)."""
    out = {}
    for f, v in _fields(message):
        if f != field:
            continue
        name = value = None
        for g, w in _fields(v):
            if g == 1:
                name = stat_names.get(w)
            elif g == 2:
                value = struct.unpack("<d", w)[0]
            elif g == 3:
                value = w
            elif g == 4:
                value = w - (1 << 64) if w >> 63 else w
            elif g == 5:
                value = _text(w)
            elif g == 6:
                value = w
            elif g == 7:
                value = stat_names.get(w, "")
        out[name] = value
    return out


def _planes(path):
    """``(name, [line], {id: event metadata}, {id: stat name})`` of every
    plane of an ``XSpace`` file, messages still encoded."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for f, plane in _fields(space):
        if f != 1:
            continue
        name, lines, metas, stat_names = "", [], {}, {}
        for g, v in _fields(plane):
            if g == 2:
                name = _text(v)
            elif g == 3:
                lines.append(v)
            elif g in (4, 5):  # map entries: key = 1, value = 2
                entry = dict(_fields(v))
                if g == 4:
                    metas[entry[1]] = entry[2]
                else:
                    stat_names[entry[1]] = _text(
                        dict(_fields(entry[2])).get(2, b""))
        yield name, lines, metas, stat_names


def _line(line):
    """``(name, [(metadata id, start ns, duration ns, event)])``."""
    name, t0, events = "", 0, []
    for f, v in _fields(line):
        if f == 2:
            name = _text(v)
        elif f == 3:
            t0 = v
        elif f == 4:
            events.append(v)
    out = []
    for ev in events:
        got = dict(_fields(ev))  # a repeated field (stats) keeps its last
        out.append((got.get(1, 0), t0 + got.get(2, 0) * 1e-3,
                    got.get(3, 0) * 1e-3, ev))
    return name, out


def _hlo_graph(proto, program, into):
    """``into[program, instruction] = (op_name, [operand names])`` for
    every instruction of an ``HloProto`` (its entry computation and every
    computation that one calls)."""
    module = dict(_fields(proto)).get(1, b"")
    by_id, rows = {}, []
    for f, computation in _fields(module):
        if f != 3:
            continue
        for g, instruction in _fields(computation):
            if g != 2:
                continue
            name, op_name, ident, operand_ids = "", "", None, []
            for h, v in _fields(instruction):
                if h == 1:
                    name = _text(v)
                elif h == 7:
                    op_name = _text(dict(_fields(v)).get(2, b""))
                elif h == 35:
                    ident = v
                elif h == 36:
                    if isinstance(v, int):
                        operand_ids.append(v)
                    else:  # packed
                        i = 0
                        while i < len(v):
                            one, i = _varint(v, i)
                            operand_ids.append(one)
            by_id[ident] = name
            rows.append((name, op_name, operand_ids))
    for name, op_name, operand_ids in rows:
        into[program, name] = (
            op_name, [by_id[i] for i in operand_ids if i in by_id])


def load_xplane(path):
    """``(ops, modules, spans, graph)`` as :func:`reduce_trace` takes them, from
    an ``.xplane.pb``: the "XLA Ops" and "XLA Modules" lines of the
    lowest-numbered ``/device:TPU:<n>`` plane, and every host event named
    like a ``telemetry.span``.

    The file is read as protobuf wire format, with no schema beyond the
    field numbers of ``XSpace`` / ``XPlane`` / ``XLine`` / ``XEvent`` /
    ``XStat``: on the v5e the name stack (``tf_op``), ``flops`` and
    ``bytes_accessed`` of an operation are statistics of the event's
    METADATA, which ``jax.profiler.ProfileData`` does not hand out. A
    trace with no TPU plane (XLA:CPU) gives the host events that carry an
    ``hlo_op``, a timeline a thread, which carry no name stack: their
    scopes come from
    ``graph``, the instructions of every program's HLO as the trace's
    ``/host:metadata`` plane holds it (:func:`_scopes`)."""
    def num(stats, key):
        try:
            return float(stats.get(key) or 0.0)
        except (TypeError, ValueError):
            return 0.0

    planes = list(_planes(path))
    tpu = sorted((p for p in planes if p[0].startswith(DEVICE_PLANE)),
                 key=lambda p: int(p[0][len(DEVICE_PLANE):].split()[0]))
    ops, modules, spans, graph, runs = [], [], [], {}, {}
    if tpu:
        _, lines, metas, stat_names = tpu[0]
        known = {}

        def meta(mid):
            if mid not in known:
                fields = dict(_fields(metas[mid]))
                stats = _stats(metas[mid], 5, stat_names)
                known[mid] = (
                    _text(fields.get(2, b"")),
                    str(stats.get("tf_op") or ""), num(stats, "flops"),
                    num(stats, "bytes_accessed"),
                    str(stats.get("program_id") or ""))
            return known[mid]

        for line in lines:
            name, events = _line(line)
            if name == OPS_LINE:
                for mid, start, dur, _ev in events:
                    text, tf_op, flops, nbytes, program = meta(mid)
                    ops.append((text, tf_op, start, dur, flops, nbytes,
                                program))
            elif name == MODULES_LINE:
                modules.extend((meta(mid)[0], start, dur)
                               for mid, start, dur, _ev in events)
    for pname, lines, metas, stat_names in planes:
        if pname.startswith("/device:"):
            continue
        if pname == HLO_PLANE:
            # one event metadata a program, its id the program's
            for mid, m in metas.items():
                proto = _stats(m, 5, stat_names).get("Hlo Proto")
                if proto is not None:
                    _hlo_graph(proto, str(mid), graph)
            continue
        names = {mid: _text(dict(_fields(m)).get(2, b""))
                 for mid, m in metas.items()}
        for thread, line in enumerate(lines):
            for mid, start, dur, ev in _line(line)[1]:
                name = names.get(mid, "")
                st = {} if tpu else _stats(ev, 4, stat_names)
                if "hlo_op" in st:
                    program = str(st.get("program_id", ""))
                    ops.append((str(st["hlo_op"]), "", start, dur, 0.0,
                                num(st, "bytes_accessed"), program, thread))
                    run = runs.setdefault(
                        (st.get("hlo_module"), program, st.get("run_id")),
                        [start, start])
                    run[1] = max(run[1], start + dur)
                elif _SPAN_NAME.match(name):
                    spans.append((name, start, dur))
    modules.extend((f"{module}({program})", s, e - s)
                   for (module, program, _run), (s, e) in runs.items())
    return ops, modules, spans, graph


def device_table(trace=None, window=None, top=None, inner=None):
    """A trace read back by the graph's own names.

    ``trace`` is an ``.xplane.pb`` or a profile directory (default: the
    log directory of this process's last ``profiler_set_state('run')``);
    ``window`` names the host span that bounds what is counted, e.g.
    ``"fit.step"`` or a caller's own ``jax.profiler.TraceAnnotation``
    (default: the whole trace); ``top`` cuts every table to its longest
    rows. Returns a dict:

    ``by_operator``
        one row per ``(operator, pass)``: ``ms``, the summed SELF time of
        its operations on device 0 (an operation nested in a ``while`` is
        taken out of the ``while``); ``ms_per_step`` where the window
        holds ``fit.step`` step roots (``steps``); ``calls``; ``share`` of
        the device's busy time; ``flops`` and ``bytes`` as the trace's own
        statistics give them (absent where it gives none); ``xla``, the
        three XLA operation kinds that hold most of the row. A fusion that
        spans scopes is booked where XLA booked it: by the one ``tf_op``
        its event carries.
    ``by_node``
        the same, one row a node, and a parameter inside ``update``.
    ``by_program``
        device time by XLA program ("XLA Modules"): each bucket's train
        program, a metric's eager programs, a caller's own.
    ``idle``
        device 0's gaps inside the window: ``total_ms``, ``by_span`` (each
        gap named by the innermost ``telemetry.span`` that covers its
        midpoint, or ``unattributed``) and the ten ``longest`` as
        ``[span, ms, ms after the window's start]``.
    ``unscoped_share``, ``unscoped``
        the share of busy time whose ``tf_op`` names no node and no
        executor phase, and those operations by XLA kind. Above 10 %
        ``hint`` says what most likely happened: the executables came
        from a compilation cache that a tree without scopes filled.
    ``by_inner`` (only with ``inner="<Operator>"``)
        that operator's rows split by what follows ``Operator[node]`` in
        the name stack (:func:`inner_scope`) and pass, all its nodes
        together; ``xla`` there names the three instructions (serial
        numbers and all) that hold most of a row.
    """
    if trace is None:
        trace = _state.get("logdir")
        if not trace:
            raise ValueError("no trace given and none taken in this "
                             "process (profiler_set_state('run'))")
    path = find_xplane(trace)
    if path is None:
        raise ValueError(f"no .xplane.pb under {trace!r}")
    ops, modules, spans, graph = load_xplane(path)
    return reduce_trace(ops, modules, spans, window=window, top=top,
                        graph=graph, inner=inner)


def memory_table():
    """What holds the devices' memory: one row for every program resolved
    in this process (arguments, kept outputs, temporaries, code,
    footprint, launches) beside each device's own ``memory_stats()``;
    ``print(memory_table()["text"])``. No trace and no device call:
    :func:`mxnet_tpu.aot.memory_table` keeps it, where the executables
    are; docs/observability.md, "Where the memory goes"."""
    from . import aot as _aot

    return _aot.memory_table()


def _maybe_autostart():
    from . import env as _env

    try:
        if _env.get("MXNET_PROFILER_AUTOSTART"):
            profiler_set_config(mode=_env.get("MXNET_PROFILER_MODE"))
            profiler_set_state("run")
    except Exception as e:
        # autostart is a convenience; a broken profiler must not turn
        # `import mxnet_tpu` into a crash
        _warn_once("autostart", f"profiler autostart failed ({e})")


_maybe_autostart()
