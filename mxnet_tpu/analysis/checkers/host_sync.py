"""host-sync: no blocking device→host syncs *reachable* from hot roots.

PR 1 made ``Module.fit``/``score`` run with zero per-batch host syncs and
PR 5/7 extended the contract to the serving request path; the runtime
counter tests verify it on the paths they drive. The PR-8 version of this
checker enforced it lexically inside a table of declared hot functions —
which meant a sync one call below a listed function shipped unseen, and
the table rotted as the call tree grew.

This version is whole-program: :data:`ROOTS` declares only the *entry
points* of the hot planes (the fit/score epoch loops, the prefetch
staging thread, the decode-pool consumer/worker loops, the serving
submit/dispatch chain, bench's timed loop), and the call graph
(:mod:`analysis.callgraph`) closes over everything they can reach. Any
``asnumpy`` / ``wait_to_read`` / ``block_until_ready`` / ``.item()`` /
``np.asarray`` in any transitively reached function is a finding, and the
message carries the full root→function call chain so the reader sees WHY
the function is hot.

Declaring hotness:

- :data:`ROOTS` below — path -> set of root function qualnames;
- a ``# graftlint: hotpath`` marker comment on (or directly above) any
  ``def`` — how new thread bodies/entry points opt in without touching
  this file.

Cutting reachability (the triage workflow): a *deliberate* cold boundary
— an epoch-end checkpoint, a metric drain — is declared by putting a
``# graftlint: allow=host-sync(<reason>)`` pragma on the **call site**
that crosses into cold code; edges leaving a pragma-carrying line are not
followed, so one annotation covers the whole cold subtree. A deliberate
sync *on* the hot path itself carries the same pragma on its own line,
exactly as before.
"""

from __future__ import annotations

import ast

from ..core import Finding, dotted, iter_defs

#: repo-relative path -> hot ROOT function qualnames in that file. Keep
#: this list to entry points only (thread bodies, public loop drivers) —
#: everything they call is covered by reachability, so helpers never
#: need to be listed (that rot is what killed the old HOT_PATHS table).
ROOTS = {
    "mxnet_tpu/module/base_module.py": {
        "BaseModule.fit", "BaseModule.score",
    },
    "mxnet_tpu/io.py": {
        "DevicePrefetchIter._worker",
    },
    "mxnet_tpu/io_plane.py": {
        "DecodePool.next_result", "_worker_loop",
    },
    "mxnet_tpu/serving/batcher.py": {
        "DynamicBatcher.submit", "DynamicBatcher._run",
    },
    "mxnet_tpu/serving/replica.py": {
        "ReplicaPool.run_batch",
    },
    "mxnet_tpu/serving/server.py": {
        "ModelServer.submit",
    },
}

_SYNC_ATTRS = {"asnumpy", "wait_to_read", "block_until_ready", "item"}
_ASARRAY = ("np.asarray", "numpy.asarray", "np.array", "numpy.array")


class HostSyncChecker:
    name = "host-sync"
    doc = ("blocking device→host syncs (`asnumpy`/`wait_to_read`/"
           "`block_until_ready`/`.item()`/`np.asarray`) anywhere "
           "reachable from the declared hot roots — findings carry the "
           "root→function call chain")

    def run(self, ctx):
        graph = ctx.callgraph()
        by_path = {u.path: u for u in ctx.units}

        roots = []
        for path in sorted(ROOTS):
            for qual in sorted(ROOTS[path]):
                node = graph.node_for(path, qual)
                if node is not None:
                    roots.append(node.node_id)
        roots.extend(self._marked_roots(ctx, graph))

        def follow(caller, site):
            # a host-sync pragma on a call-site line declares a deliberate
            # cold boundary: edges leaving that line are not followed
            unit = by_path.get(caller.path)
            if unit is None:
                return True
            return "host-sync" not in unit.line_allows.get(site.line, {})

        chains = graph.reachable(roots, edge_filter=follow)
        for node_id in sorted(chains):
            node = graph.nodes[node_id]
            unit = by_path.get(node.path)
            if unit is None:
                continue
            yield from self._check_fn(unit, graph, node, chains[node_id])

    @staticmethod
    def _marked_roots(ctx, graph):
        """Functions opted in via ``# graftlint: hotpath`` markers."""
        for unit in ctx.units:
            if unit.tree is None or not unit.hotpath_lines:
                continue
            for qual, _cls, fn in iter_defs(unit.tree):
                deco_top = min([fn.lineno]
                               + [d.lineno for d in fn.decorator_list])
                if fn.lineno in unit.hotpath_lines \
                        or deco_top - 1 in unit.hotpath_lines:
                    node = graph.node_for(unit.path, qual)
                    if node is not None:
                        yield node.node_id

    @staticmethod
    def _chain_text(graph, chain):
        names = [graph.nodes[n].dotted.replace("mxnet_tpu.", "", 1)
                 for n in chain]
        if len(names) == 1:
            return f"hot root `{names[0]}`"
        return (f"reachable from hot root `{names[0]}` via "
                + " -> ".join(f"`{n}`" for n in names[1:]))

    def _check_fn(self, unit, graph, node, chain):
        from ..callgraph import iter_own_scope

        where = self._chain_text(graph, chain)
        for sub in iter_own_scope(node.fn):
            if not isinstance(sub, ast.Call):
                continue
            callee = dotted(sub.func)
            if isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr in _SYNC_ATTRS:
                yield Finding(
                    self.name, unit.path, sub.lineno,
                    f"blocking host sync `.{sub.func.attr}()` on a hot "
                    f"path ({where}) — keep device work async, cut the "
                    "chain at a deliberate cold boundary, or pragma the "
                    "deliberate fence",
                    context=node.qual)
            elif callee in _ASARRAY:
                yield Finding(
                    self.name, unit.path, sub.lineno,
                    f"`{callee}(...)` on a hot path ({where}) is a "
                    "device→host copy when handed an NDArray — stage on "
                    "device or pragma the deliberate fetch",
                    context=node.qual)
