"""Pipeline parallelism as a Module-API feature.

``SequentialModule`` lowers to the GPipe schedule here when a mesh with a
``pp`` axis is installed at bind time — the same promotion the Symbol-level
``__shard__`` attribute gave tensor parallelism. The reference's nearest
"usable from user code" analogue is its model-parallel LSTM
(``example/model-parallel-lstm/lstm.py``), which places layers on devices
with ``group2ctx`` but has no microbatch schedule; SURVEY.md §2.5 marks
scheduled pipelining absent upstream, so the schedule itself is TPU-native
surface: one jitted SPMD program, a ``lax.scan`` over pipeline ticks with
``lax.ppermute`` hops, differentiated end-to-end by ``jax.grad`` (GPipe
fill/drain bubbles included; grads/loss match the serial execution
exactly, which the tests assert).

Two lowerings, picked automatically:

* **stacked** — every stage is structurally identical (a homogeneous
  label-free block stack): per-stage parameters are stacked on a leading
  axis and sharded ``P('pp')``, so each pipeline rank holds only its
  slice.
* **composed** — heterogeneous stages (the common case: distinct layers,
  loss head on the last stage): each tick dispatches this rank's stage
  with ``lax.switch`` over per-stage branch closures. Parameters and aux
  are PACKED per stage: stage ``i``'s tensors ride row ``i`` of one
  ``(S, Lmax)`` flat buffer per dtype, sharded ``P('pp')`` — each rank
  holds ~1/S of the parameter bytes (padding to the longest stage), the
  same memory scaling the stacked mode gets, without requiring
  homogeneity. Gradients come back sharded the same way (only ``dp``
  contributions are summed).

Composed meshes (``dp×pp``, ``dp×tp×pp``): a ``dp`` axis places each GPipe
stage on a pp rank *set* — the batch shards over ``dp`` inside every
microbatch, and in composed mode the packed rows additionally shard their
flat dim over the stage's (dp, tp) sub-mesh, so each device holds
~``total/(S·dp·tp)`` packed parameter bytes (ZeRO-style: rows are
``all_gather``-ed over the rank set at program entry, and the gather's AD
transpose is exactly the gradient ``psum_scatter`` over the ``dp``
sub-axis *within* each stage's rank set — the reduce-scatter form of the
per-stage data-parallel gradient sum). BatchNorm-style aux updates are
``pmean``-ed over ``dp`` (mean of per-shard batch statistics = full-batch
means, the serial semantics). A ``tp`` axis nests inside stages: tp ranks
hold distinct packed-row shards; stage compute replicates over tp on
runtimes whose SPMD partitioner cannot nest GSPMD-auto regions inside
manual collectives (jax 0.4.x hard-aborts there), while ``__shard__``
Megatron shardings ride the pure-jit executor path (dp×tp) unchanged.

Scope (enforced with clear errors): every child is a plain bound
``Module`` with one data input, interior boundaries are single tensors of
one shared shape/dtype, and only the last child takes labels. More
children than pipeline ranks group contiguously into balanced stages
(each rank chains its children over the activation); fewer children than
ranks is an error. BatchNorm-style aux states follow SERIAL semantics:
each stage runs its M microbatch ticks against the step-start aux and
the masked per-tick updates are averaged, which for the BN EMA equals
one serial update with full-batch mean statistics (variances keep
per-microbatch granularity — the reference's own non-sync multi-device
BN behavior); fill/drain ticks contribute nothing.
"""

from __future__ import annotations

import math

from ..base import MXNetError
from .. import telemetry as _tm
from .compat import shard_map as _shard_map
from .mesh import as_graft


def _graph_signature(graph, data_names, label_names, shape_of):
    """Structural signature for homogeneity detection: op types, attrs,
    wiring and bound variable shapes/dtypes, with names erased; data/label
    inputs marked by role. Shapes matter — structurally identical stages
    with different bound widths cannot stack."""
    index = {}
    sig = []
    for i, node in enumerate(graph.topo):
        index[id(node)] = i
        if node.is_variable:
            role = ("data" if node.name in data_names
                    else "label" if node.name in label_names
                    else "aux" if node.is_aux else "param")
            sig.append(("var", role) + shape_of(node.name, node.is_aux))
        else:
            params = tuple(sorted((k, str(v)) for k, v in
                           (node.params() or {}).items()))
            wiring = tuple((index[id(n)], ix) for (n, ix) in node.inputs)
            sig.append((node.op.name, params, wiring))
    heads = tuple((index[id(n)], ix) for (n, ix) in graph.heads)
    return (tuple(sig), heads)


class _StageUnit:
    """One child Module inside a pipeline stage (stages may group several
    consecutive children when the child count exceeds the pp degree)."""

    def __init__(self, module, takes_labels):
        self.module = module
        exe = module._exec_group._exec
        self.exec_ = exe
        self.graph = exe.graph
        self.data_name = module._data_names[0]
        self.label_names = list(module._label_names) if takes_labels else []
        self.param_names = [n for n in self.graph.arg_names
                            if n != self.data_name
                            and n not in self.label_names]
        self.aux_names = list(self.graph.aux_names)


class _StageInfo:
    def __init__(self, group):
        self.units = [_StageUnit(st.module, st.takes_labels)
                      for st in group]
        self.module = group[-1].module  # stage boundary (output shapes)
        self.label_names = self.units[-1].label_names
        # per-stage flat orders (the engine's value tuples follow these)
        self.param_entries = [(u, n) for u, unit in enumerate(self.units)
                              for n in unit.param_names]
        self.aux_entries = [(u, n) for u, unit in enumerate(self.units)
                            for n in unit.aux_names]
        self.param_index = {e: j for j, e in enumerate(self.param_entries)}
        self.aux_index = {e: j for j, e in enumerate(self.aux_entries)}

    @property
    def graph(self):
        return self.units[-1].graph  # heads/loss flags live on the tail


def _build_stages(stages, num_stages):
    for i, st in enumerate(stages):
        mod = st.module
        if getattr(mod, "_exec_group", None) is None:
            raise MXNetError(
                f"pipeline child {i} is not a bound plain Module; pipelined "
                "SequentialModule supports Module children only"
            )
        if len(mod._data_names) != 1:
            raise MXNetError(
                f"pipeline child {i} has {len(mod._data_names)} data "
                "inputs; the GPipe boundary carries exactly one activation"
            )
        if st.takes_labels and i != len(stages) - 1:
            raise MXNetError(
                "only the last pipeline child may take labels (the loss "
                f"head); child {i} sets take_labels"
            )
        req = mod._grad_req
        reqs = set(req.values()) if isinstance(req, dict) else \
            set(req) if isinstance(req, (list, tuple)) else {req}
        if "add" in reqs:
            raise MXNetError(
                "grad_req='add' accumulation is not supported by the "
                "pipelined SequentialModule (each step writes fresh "
                f"gradients); child {i} requests it"
            )
    # contiguous balanced grouping: N children over S stages (the manual
    # alternative the old error message demanded). The extra children go
    # to the EARLIEST stages so the loss-head child stays alone last when
    # the split allows.
    n, s = len(stages), num_stages
    base, extra = divmod(n, s)
    groups = []
    start = 0
    for i in range(s):
        size = base + (1 if i < extra else 0)
        groups.append(list(stages[start:start + size]))
        start += size
    return [_StageInfo(g) for g in groups]


class PipelineEngine:
    """Owns the jitted GPipe program(s) for one bound SequentialModule."""

    def __init__(self, stages, mesh, num_microbatches, batch_size, logger):
        from ..env import get as env_get

        self.gmesh = as_graft(mesh)
        self.mesh = self.gmesh.mesh
        self.S = self.gmesh.pp
        if self.S < 2:
            raise MXNetError("a pp mesh axis of size 1 pipelines nothing; "
                             "drop the pp axis or grow it")
        if len(stages) < self.S:
            raise MXNetError(
                f"{len(stages)} pipeline children for a pp axis of size "
                f"{self.S}; need at least one child per stage"
            )
        # composed-mesh degrees: each GPipe stage is placed on a pp rank
        # SET spanning the dp×tp sub-mesh; packed rows shard over it
        self.dp_size = self.gmesh.dp
        self.tp_size = self.gmesh.tp
        self._row_axes = tuple(a for a in ("dp", "tp")
                               if self.gmesh.has(a))
        self._row_shard = self.dp_size * self.tp_size
        self.infos = _build_stages(stages, self.S)
        self.M = int(num_microbatches or env_get("MXNET_PP_MICROBATCHES")
                     or self.S)
        if batch_size % self.M != 0:
            raise MXNetError(
                f"batch {batch_size} not divisible into {self.M} "
                "microbatches"
            )
        if (batch_size // self.M) % self.dp_size != 0:
            raise MXNetError(
                f"microbatch {batch_size // self.M} not divisible by the "
                f"data-parallel degree {self.dp_size} (mesh "
                f"{self.gmesh.spec})"
            )
        self.logger = logger
        shapes = set()
        for info in self.infos[:-1]:
            outs = info.module.output_shapes
            if len(outs) != 1:
                raise MXNetError(
                    f"interior pipeline stage {info.module} has "
                    f"{len(outs)} outputs; exactly one activation crosses "
                    "a GPipe boundary"
                )
            shapes.add((outs[0][1][0] // self.M,) + tuple(outs[0][1][1:]))
        if len(shapes) > 1:
            raise MXNetError(
                f"interior boundary shapes differ across stages: "
                f"{sorted(shapes)}; the pipeline ring buffer needs one "
                "shape (pad or restructure stages)"
            )
        def shape_of(unit):
            def f(name, is_aux):
                d = unit.exec_.aux_dict if is_aux else unit.exec_.arg_dict
                arr = d.get(name)
                return (tuple(arr.shape), str(arr.dtype)) if arr is not None \
                    else ((), "?")
            return f

        sigs = [
            tuple(_graph_signature(u.graph, {u.data_name},
                                   set(u.label_names), shape_of(u))
                  for u in info.units)
            for info in self.infos
        ]
        self.homogeneous = self.S > 1 and all(s == sigs[0] for s in sigs[1:])
        from ..executor import _head_loss_flags

        self.has_loss = any(_head_loss_flags(self.infos[-1].graph))
        self._programs = {}
        self._last_outputs = None
        self._rng_dev = None
        if not self.homogeneous:
            # composed-mode parameter packing: stage i's params/aux ride
            # row i of one (S, Lmax) buffer per dtype, sharded P('pp') —
            # heterogeneous pipelines get the same 1/S per-device
            # parameter memory the stacked (homogeneous) mode has, instead
            # of full replication
            self._param_layout = self._make_pack_layout(is_aux=False)
            self._aux_layout = self._make_pack_layout(is_aux=True)
        # packed buffers are rebuilt from the child executors every run()
        # (they remain the single source of truth for checkpoint/update);
        # the repack is O(param tensors) of eager device ops per step — an
        # accepted cost on the capability path. retain_packed=True keeps
        # the last packed params alive for sharding introspection (tests);
        # off by default so steady state holds no second parameter copy.
        self.retain_packed = False
        self._packed_params = None
        # inference param caching (the serving path): packing/stacking the
        # stage params is O(param tensors) of eager device ops per run —
        # irrelevant against a train step, but on the request path it IS
        # the per-batch host cost. With cache_inference_params=True, eval
        # runs reuse the packed/stacked values until invalidate_params()
        # (weight hot-swaps must call it; training runs never read the
        # cache, and a train step invalidates it as a side effect of
        # writing the executors).
        self.cache_inference_params = False
        self._cached_vals = None

    def _make_pack_layout(self, is_aux):
        """Static flat layout: per dtype, per stage, the (entry_index,
        offset, size, shape) slices of that stage's packed row."""
        per_stage = []
        dtypes = set()
        for info in self.infos:
            entries = info.aux_entries if is_aux else info.param_entries
            rows = {}
            for j, (u, n) in enumerate(entries):
                unit = info.units[u]
                d = unit.exec_.aux_dict if is_aux else unit.exec_.arg_dict
                arr = d[n]
                dt = str(arr.dtype)
                dtypes.add(dt)
                off = rows.setdefault(dt, [0, []])
                size = 1
                for s in arr.shape:
                    size *= int(s)
                off[1].append((j, off[0], size, tuple(arr.shape)))
                off[0] += size
            per_stage.append(rows)
        dtypes = sorted(dtypes)
        lmax = {}
        # lane-align AND keep the flat dim divisible by the stage rank
        # set's shard degree (rows shard over the dp×tp sub-mesh)
        align = 128 * self._row_shard // math.gcd(128, self._row_shard)
        for dt in dtypes:
            longest = max((st[dt][0] for st in per_stage if dt in st),
                          default=0)
            lmax[dt] = max(align, -(-longest // align) * align)
        return {"dtypes": dtypes, "per_stage": per_stage, "lmax": lmax,
                "n_entries": [len(info.aux_entries if is_aux
                                  else info.param_entries)
                              for info in self.infos]}

    def stage_slices(self):
        """Packed-row placement per parameter, for checkpoint manifests:
        ``{name: {stage, aux, dtype, offset, size, shape, lmax}}`` (None
        when this pipeline doesn't pack, i.e. homogeneous mode).

        Purely descriptive — the elastic loader restores into the child
        executors and rows repack from them on the next run(), so resume
        onto a DIFFERENT pipeline layout never reads these offsets. They
        let tools/ckpt.py display/audit the packed geometry a commit was
        trained under, and pin the round-trip contract in tests."""
        if getattr(self, "_param_layout", None) is None:
            return None
        out = {}
        for is_aux, layout in ((False, self._param_layout),
                               (True, self._aux_layout)):
            for i, info in enumerate(self.infos):
                entries = info.aux_entries if is_aux else info.param_entries
                for dt, (_used, sl) in layout["per_stage"][i].items():
                    for j, off, size, shape in sl:
                        name = entries[j][1]
                        out[name] = {
                            "stage": i,
                            "aux": is_aux,
                            "dtype": dt,
                            "offset": int(off),
                            "size": int(size),
                            "shape": [int(s) for s in shape],
                            "lmax": int(layout["lmax"][dt]),
                        }
        return out

    def _row_spec_entry(self):
        """The PartitionSpec entry sharding a packed row's flat dim over
        the stage rank set's dp×tp sub-mesh (None on a pure-pp mesh)."""
        if not self._row_axes:
            return None
        return self._row_axes if len(self._row_axes) > 1 \
            else self._row_axes[0]

    def _pack_rows(self, vals_per_stage, layout):
        """Eager: stack per-stage flat rows into {dtype: (S, Lmax)} arrays
        placed P('pp', <dp×tp>) — each pipeline rank set holds only its
        stage's row, and within the rank set each device holds a 1/(dp·tp)
        slice of it (~total/(S·dp·tp) packed bytes per device)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        out = {}
        nbytes = 0
        for dt in layout["dtypes"]:
            rows = []
            for i in range(self.S):
                sl = layout["per_stage"][i].get(dt)
                parts = []
                if sl is not None:
                    vals = vals_per_stage[i]
                    parts = [jnp.ravel(vals[j]) for j, _, _, _ in sl[1]]
                used = sl[0] if sl is not None else 0
                pad = layout["lmax"][dt] - used
                if pad:
                    parts.append(jnp.zeros((pad,), jnp.dtype(dt)))
                rows.append(jnp.concatenate(parts) if len(parts) > 1
                            else parts[0])
            buf = jnp.stack(rows)
            out[dt] = jax.device_put(
                buf, NamedSharding(self.mesh, P("pp", self._row_spec_entry())))
            nbytes += buf.size * buf.dtype.itemsize
        if layout is self._param_layout:
            _tm.gauge("parallel.packed_bytes_per_device").set(
                nbytes // (self.S * self._row_shard))
        return out

    @staticmethod
    def _unpack_row(stage_layout, packed_local, n_entries):
        """Rebuild stage tensors from this rank's (1, Lmax) rows; offsets
        are static (the stage index is static inside its switch branch)."""
        vals = [None] * n_entries
        for dt, (_used, sl) in stage_layout.items():
            row = packed_local[dt][0]
            for j, off, size, shape in sl:
                vals[j] = row[off:off + size].reshape(shape)
        return tuple(vals)

    @staticmethod
    def _repack_row(stage_layout, packed_local, new_vals, out_dtype=None):
        """Inverse of _unpack_row: write updated stage tensors back into
        fresh (1, Lmax) rows (untouched dtypes keep their rows).
        ``out_dtype`` overrides the storage dtype — accumulator rows must
        receive UNQUANTIZED values (a cast through a bf16 storage dtype
        would add M per-tick rounding errors to the average)."""
        import jax.numpy as jnp

        out = dict(packed_local)
        for dt, (used, sl) in stage_layout.items():
            cast = jnp.dtype(out_dtype) if out_dtype else jnp.dtype(dt)
            parts = [jnp.ravel(new_vals[j]).astype(cast)
                     for j, _, _, _ in sl]
            lmax = packed_local[dt].shape[1]
            if lmax > used:
                parts.append(jnp.zeros((lmax - used,), cast))
            out[dt] = (jnp.concatenate(parts) if len(parts) > 1
                       else parts[0])[None]
        return out

    # -- value plumbing ---------------------------------------------------
    def _stage_vals(self):
        """Current (param_vals, aux_vals) per stage from the child execs."""
        pvals, avals = [], []
        for info in self.infos:
            pvals.append(tuple(
                info.units[u].exec_.arg_dict[n]._data
                for u, n in info.param_entries))
            avals.append(tuple(
                info.units[u].exec_.aux_dict[n]._data
                for u, n in info.aux_entries))
        return tuple(pvals), tuple(avals)

    # -- program construction --------------------------------------------
    def _program(self, is_train, with_grads):
        import jax

        key = (bool(is_train), bool(with_grads))
        if key not in self._programs:
            self._programs[key] = jax.jit(self._make_step(*key))
        return self._programs[key]

    def _make_step(self, is_train, with_grads):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from ..executor import _head_loss_flags

        mesh, S, M = self.mesh, self.S, self.M
        infos = self.infos
        homogeneous = self.homogeneous
        gm = self.gmesh
        dp = "dp" if gm.has("dp") else None
        dp_size = self.dp_size
        row_axes = self._row_axes
        row_shard = self._row_shard
        gather_axes = row_axes if len(row_axes) > 1 else \
            (row_axes[0] if row_axes else None)
        loss_flags = _head_loss_flags(infos[-1].graph)
        num_heads = len(infos[-1].graph.heads)

        def gather_rows(packed):
            """ZeRO-style: reassemble this rank set's full packed rows
            from the (dp, tp)-sharded slices. Differentiable — the AD
            transpose is psum_scatter over the rank set, i.e. the
            per-stage gradient reduce-scatter over the dp sub-axis."""
            if gather_axes is None or homogeneous:
                return packed
            return {
                dt: jax.lax.all_gather(packed[dt], gather_axes, axis=1,
                                       tiled=True)
                for dt in packed
            }

        if not homogeneous:
            p_layout, a_layout = self._param_layout, self._aux_layout
            unpack, repack = self._unpack_row, self._repack_row

            def stage_params(i, packed):
                return unpack(p_layout["per_stage"][i], packed,
                              p_layout["n_entries"][i])

            def stage_aux(i, packed):
                return unpack(a_layout["per_stage"][i], packed,
                              a_layout["n_entries"][i])

        def run_stage(i, a_in, labels_mb, pvals_i, avals_i, stage_key):
            """Chain the stage's grouped children over the activation.

            ``stage_key`` is already stage-distinct (the homogeneous path
            folds the traced pipeline rank — a static index there would
            hand every rank the same dropout key per tick)."""
            info = infos[i]
            pidx, aidx = info.param_index, info.aux_index
            act = a_in
            new_aux = list(avals_i)
            outs = None
            for u, unit in enumerate(info.units):
                full = []
                for n in unit.graph.arg_names:
                    if n == unit.data_name:
                        full.append(act)
                    elif n in unit.label_names:
                        full.append(labels_mb[unit.label_names.index(n)])
                    else:
                        full.append(pvals_i[pidx[(u, n)]])
                unit_aux = [new_aux[aidx[(u, n)]] for n in unit.aux_names]
                outs, aux_upd = unit.graph.evaluate(
                    full, unit_aux, jax.random.fold_in(stage_key, u),
                    is_train,
                )
                for n, v in zip(unit.aux_names, aux_upd):
                    new_aux[aidx[(u, n)]] = v
                act = outs[0]
            return outs, tuple(new_aux)

        def sched(pvals, avals, rng, xs, ls):
            s = jax.lax.axis_index("pp")
            key0 = jax.random.PRNGKey(0)
            # composed rank sets: the body receives (dp, tp)-sharded row
            # slices; compute needs the full rows of THIS pp rank's stage
            avals_in = avals
            pvals = gather_rows(pvals)
            avals = gather_rows(avals)

            def first_stage_out(a):
                pv = (jax.tree_util.tree_map(lambda v: v[0], pvals)
                      if homogeneous else stage_params(0, pvals))
                av = (jax.tree_util.tree_map(lambda v: v[0], avals)
                      if homogeneous else stage_aux(0, avals))
                return run_stage(0, a, (), pv, av, key0)[0][0]

            ring_aval = jax.eval_shape(first_stage_out, xs[0])

            def last_stage_outs(a, lm):
                pv = (jax.tree_util.tree_map(lambda v: v[0], pvals)
                      if homogeneous else stage_params(S - 1, pvals))
                av = (jax.tree_util.tree_map(lambda v: v[0], avals)
                      if homogeneous else stage_aux(S - 1, avals))
                return run_stage(S - 1, a, lm, pv, av, key0)[0]

            head_avals = jax.eval_shape(
                last_stage_outs,
                jax.ShapeDtypeStruct(ring_aval.shape, ring_aval.dtype),
                tuple(l[0] for l in ls),
            )
            zero_ring = jnp.zeros(ring_aval.shape, ring_aval.dtype)
            outs0 = tuple(jnp.zeros((M,) + tuple(h.shape), h.dtype)
                          for h in head_avals)
            # Aux (BN moving stats) under GPipe: every tick runs its stage
            # against the STEP-START aux and the per-tick updates are
            # masked to the stage's M valid microbatch ticks and AVERAGED.
            # For the EMA form upd_t = m*mv0 + (1-m)*stats_t this yields
            # m*mv0 + (1-m)*avg_t(stats_t) — the serial update with
            # full-batch statistics (exact for means; variances keep
            # per-microbatch granularity, the reference's own multi-device
            # non-sync BN semantics). Fill/drain ticks, which process ring
            # garbage or replayed microbatches, contribute nothing.
            if homogeneous:
                av_base = jax.tree_util.tree_map(lambda v: v[0], avals)
                aux_acc0 = (jax.tree_util.tree_map(
                    lambda v: jnp.zeros(v.shape, jnp.float32), avals),)
            else:
                av_base = None  # per-branch stage_aux(i, avals)
                aux_acc0 = {
                    dt: jnp.zeros(avals[dt].shape, jnp.float32)
                    for dt in avals
                }

            def tick(carry, t):
                buf, outs, aux_all, key = carry
                feed = xs[jnp.clip(t, 0, M - 1)]
                out_idx = t - (S - 1)
                lab_idx = jnp.clip(out_idx, 0, M - 1)
                labels_mb = tuple(l[lab_idx] for l in ls)
                tick_key = jax.random.fold_in(key, t)

                if homogeneous:
                    # identical graphs chain, so data microbatches share the
                    # boundary shape and stage 0 can blend in via the ring.
                    # rng: fold the TRACED rank — the static stage index is
                    # 0 on every rank here and would replicate dropout
                    # masks across the pipeline
                    a_in = jnp.where(s == 0, feed.astype(zero_ring.dtype),
                                     buf)
                    local_p = jax.tree_util.tree_map(lambda v: v[0], pvals)
                    outs_i, aux_upd = run_stage(
                        0, a_in, labels_mb, local_p, av_base,
                        jax.random.fold_in(tick_key, s))
                    ring = outs_i[0]
                    heads = tuple(outs_i[:num_heads])
                    if is_train:
                        mb = t - s  # this rank's microbatch index at tick t
                        aux_valid = (mb >= 0) & (mb < M)
                        new_aux_all = (jax.tree_util.tree_map(
                            lambda acc, u: acc + jnp.where(
                                aux_valid, u[None].astype(jnp.float32),
                                jnp.zeros((), jnp.float32)),
                            aux_all[0], tuple(aux_upd),
                        ),)
                    else:  # eval: aux passes through bit-exact
                        new_aux_all = aux_all
                else:
                    # the data microbatch generally has a different shape
                    # from the ring activation, so stage 0 reads `feed`
                    # from its closure and ignores the ring buffer
                    def branch(i):
                        st_layout = a_layout["per_stage"][i]

                        def f(buf, feed, labels_mb, aux_all):
                            a_in = feed if i == 0 else buf
                            p_i = stage_params(i, pvals)
                            aux_i = stage_aux(i, avals)  # step-start aux
                            if i == S - 1:
                                # fill ticks feed the last stage garbage
                                # whose OUTPUT is masked — but loss heads
                                # inject their gradient unconditionally
                                # (SoftmaxOutput ignores its cotangent by
                                # reference contract), so the stage must
                                # not execute at all on invalid ticks
                                def taken(op):
                                    a, lm, ax = op
                                    outs_i, aux_upd = run_stage(
                                        i, a, lm, p_i, ax,
                                        jax.random.fold_in(tick_key, i))
                                    return tuple(outs_i), aux_upd

                                def skipped(op):
                                    _, _, ax = op
                                    return tuple(
                                        jnp.zeros(h.shape, h.dtype)
                                        for h in head_avals
                                    ), ax

                                heads, aux_upd = jax.lax.cond(
                                    out_idx >= 0, taken, skipped,
                                    (a_in, labels_mb, aux_i))
                                ring = zero_ring
                            else:
                                outs_i, aux_upd = run_stage(
                                    i, a_in, labels_mb, p_i, aux_i,
                                    jax.random.fold_in(tick_key, i))
                                ring = outs_i[0].astype(zero_ring.dtype)
                                heads = tuple(
                                    jnp.zeros(h.shape, h.dtype)
                                    for h in head_avals
                                )
                            if not is_train:
                                # eval BN passes aux through unchanged —
                                # keep the carry constant so writeback is
                                # bit-exact (no sum/divide perturbation)
                                return ring, heads, aux_all
                            # accumulate this tick's masked update into the
                            # rank's f32 accumulator rows (averaged after
                            # the scan — serial EMA semantics, see above)
                            mb = t - i
                            aux_valid = (mb >= 0) & (mb < M)
                            zero_rows = {
                                dt: jnp.zeros(aux_all[dt].shape,
                                              jnp.float32)
                                for dt in aux_all
                            }
                            contrib = repack(st_layout, zero_rows, aux_upd,
                                             out_dtype=jnp.float32)
                            new_aux = {
                                dt: aux_all[dt] + jnp.where(
                                    aux_valid, contrib[dt],
                                    jnp.zeros((), jnp.float32))
                                for dt in aux_all
                            }
                            return ring, heads, new_aux
                        return f

                    ring, heads, new_aux_all = jax.lax.switch(
                        s, [branch(i) for i in range(S)],
                        buf, feed, labels_mb, aux_all,
                    )

                valid = (s == S - 1) & (out_idx >= 0)
                new_outs = tuple(
                    jnp.where(valid,
                              ob.at[jnp.clip(out_idx, 0, M - 1)].set(h), ob)
                    for ob, h in zip(outs, heads)
                )
                nxt = jax.lax.ppermute(ring, "pp",
                                       [(i, (i + 1) % S) for i in range(S)])
                return (nxt, new_outs, new_aux_all, key), None

            (_, outs, aux_acc, _), _ = jax.lax.scan(
                tick, (zero_ring, outs0, aux_acc0, rng),
                jnp.arange(M + S - 1),
            )
            outs = tuple(jax.lax.psum(o, "pp") for o in outs)
            # average the M masked per-tick updates back into storage
            # dtypes; no cross-pp exchange needed — rank i's rows ARE
            # stage i's aux and the P('pp') out spec reassembles them.
            # Under a dp sub-axis the per-rank estimates additionally
            # average over dp (mean of per-shard BN batch statistics =
            # the full-batch means, the serial semantics); tp ranks
            # contribute bit-identical updates, so the same reduction
            # divided by the rank-set size is exact there too. Eval
            # returns the INPUT aux bit-exact (BN aux is inert there).
            inv_m = jnp.float32(1.0 / M)
            if not is_train:
                aux_all = (avals_in,) if homogeneous else avals_in
            elif homogeneous:
                acc = aux_acc[0]
                if dp:
                    acc = jax.tree_util.tree_map(
                        lambda a: jax.lax.psum(a, "dp"), acc)
                inv = jnp.float32(1.0 / (M * (dp_size if dp else 1)))
                aux_all = (jax.tree_util.tree_map(
                    lambda a, ref: (a * inv).astype(ref.dtype),
                    acc, avals),)
            elif gather_axes is not None:
                # reduce over the stage's rank set and scatter straight
                # back to this device's row slice (matches the sharded
                # out spec); /(M·dp·tp) folds the microbatch average,
                # the dp mean and the identical-tp-contribution sum
                inv = jnp.float32(1.0 / (M * row_shard))
                aux_all = {
                    dt: (jax.lax.psum_scatter(
                        aux_acc[dt], gather_axes, scatter_dimension=1,
                        tiled=True) * inv).astype(avals_in[dt].dtype)
                    for dt in aux_acc
                }
            else:
                aux_all = {
                    dt: (aux_acc[dt] * inv_m).astype(avals_in[dt].dtype)
                    for dt in aux_acc
                }
            return outs, aux_all

        def sched_train(pvals, avals, rng, xs, ls):
            """sched + loss + per-rank vjp with explicit psums: gradient
            reduction across the mesh is spelled out here rather than left
            to the transpose of replicated shard_map inputs (which is not
            performed under check_vma=False)."""

            def local_loss(pv):
                outs, aux_all = sched(pv, avals, rng, xs, ls)
                total = None
                for j, o in enumerate(outs):
                    if not jnp.issubdtype(o.dtype, jnp.floating):
                        continue
                    if loss_flags and loss_flags[j]:
                        t = jnp.sum(o.astype(jnp.float32))
                        total = t if total is None else total + t
                if total is None:
                    raise MXNetError(
                        "pipelined training requires a loss head "
                        "(SoftmaxOutput/MakeLoss/...) on the last stage"
                    )
                return total, (outs, aux_all)

            grads, (outs, aux_all) = jax.grad(
                local_loss, has_aux=True)(pvals)
            # params are pp-sharded in BOTH modes (stacked leading axis or
            # packed per-stage rows): each rank's grad IS its slice, so
            # only the dp sub-axis within the stage's rank set sums.
            # Composed sharded rows get that reduction from AD itself —
            # the transpose of the in-graph all_gather is psum_scatter
            # over (dp, tp) — leaving only the identical-tp-contribution
            # scale to divide out. Stacked (homogeneous) rows replicate
            # over dp, whose implicit transpose-psum shard_map does not
            # perform under check_vma=False, so it is spelled out.
            if homogeneous:
                if dp:
                    grads = jax.tree_util.tree_map(
                        lambda g: jax.lax.psum(g, ("dp",)), grads)
            elif gather_axes is not None and self.tp_size > 1:
                inv_tp = jnp.float32(1.0 / self.tp_size)
                grads = {
                    dt: (grads[dt].astype(jnp.float32) * inv_tp
                         ).astype(grads[dt].dtype)
                    for dt in grads
                }
            return outs, aux_all, grads

        def make_step():
            def step(pvals, avals, rng, data, labels):
                B = data.shape[0]
                xs = data.reshape((M, B // M) + tuple(data.shape[1:]))
                ls = tuple(l.reshape((M, B // M) + tuple(l.shape[1:]))
                           for l in labels)
                if homogeneous:
                    # stacked EAGERLY by run() (leading axis S, P('pp')):
                    # producing a multi-axis-mesh shard_map operand inside
                    # the enclosing jit silently miscompiles on jax-0.4.x
                    # SPMD (verified against the serial oracle), so the
                    # program takes the stacked pytrees as real arguments
                    pv_in, av_in = pvals, avals
                    p_spec = jax.tree_util.tree_map(lambda _: P("pp"),
                                                    pv_in)
                    a_spec = jax.tree_util.tree_map(lambda _: P("pp"),
                                                    av_in)
                    aux_out_spec = (a_spec,)
                else:
                    # packed composed: {dtype: (S, Lmax)} buffers, one row
                    # per stage sharded over pp, the flat dim sharded over
                    # the stage rank set's dp×tp sub-mesh (ZeRO-style)
                    row = self._row_spec_entry()
                    pv_in, av_in = pvals, avals
                    p_spec = jax.tree_util.tree_map(lambda _: P("pp", row),
                                                    pv_in)
                    a_spec = jax.tree_util.tree_map(lambda _: P("pp", row),
                                                    av_in)
                    aux_out_spec = a_spec
                x_spec = P(None, dp)
                out_specs = (tuple(P(None, dp) for _ in range(num_heads)),
                             aux_out_spec)
                if with_grads:
                    # param grads keep the parameter sharding in both modes
                    out_specs = out_specs + (p_spec,)
                mapped = _shard_map(
                    sched_train if with_grads else sched, mesh=mesh,
                    in_specs=(p_spec, a_spec, P(), x_spec,
                              jax.tree_util.tree_map(lambda _: x_spec, ls)),
                    out_specs=out_specs,
                    check_vma=False,
                )
                res = mapped(pv_in, av_in, rng, xs, ls)
                outs, aux_all = res[0], res[1]
                outs_flat = tuple(
                    o.reshape((o.shape[0] * o.shape[1],)
                              + tuple(o.shape[2:]))
                    for o in outs
                )
                # homogeneous aux/grads return STACKED (run() unstacks
                # host-side — slicing shard_map results inside this jit
                # risks the same multi-axis SPMD miscompile as stacking)
                next_rng = jax.random.fold_in(rng, 0x9E3779B9)
                if not with_grads:
                    return outs_flat, aux_all, next_rng
                return outs_flat, aux_all, res[2], next_rng
            return step

        return make_step()

    def _stack_stage_vals(self, vals_per_stage):
        """Eager homogeneous-mode input prep: stack per-stage value tuples
        on a leading S axis and place P('pp') (stage i's slice on pipeline
        rank set i)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        stacked = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *vals_per_stage)
        sh = NamedSharding(self.mesh, P("pp"))
        return jax.tree_util.tree_map(
            lambda v: jax.device_put(v, sh), stacked)

    def _unstack_stages(self, tree):
        """Host-side inverse of :meth:`_stack_stage_vals`: per-stage value
        tuples from stacked leaves (reads slice per stage — eager, off the
        traced program)."""
        return tuple(
            tuple(leaf[i] for leaf in tree)
            for i in range(self.S)
        )

    # -- Module-facing API ------------------------------------------------
    def run(self, data_batch, is_train):
        """Execute the pipeline; writes grads into the child executors'
        grad arrays when training (so per-child ``update()`` just works)."""
        import jax

        from ..ndarray import NDArray, array as nd_array

        _tm.counter("parallel.pp_run").inc()
        use_cache = self.cache_inference_params and not is_train
        if is_train:
            self._cached_vals = None  # train writes the executors
        if use_cache and self._cached_vals is not None:
            pvals, avals = self._cached_vals
            _tm.counter("parallel.pp_param_cache_hit").inc()
        else:
            pvals, avals = self._stage_vals()
            if not self.homogeneous:
                # per-stage placement: stage i's params/aux ride row i of
                # the packed P('pp', dp×tp) buffers, so each device
                # materializes ~1/(S·dp·tp) of the parameter bytes inside
                # the program
                pvals = self._pack_rows(pvals, self._param_layout)
                avals = self._pack_rows(avals, self._aux_layout)
                self._packed_params = pvals if self.retain_packed else None
            else:
                # homogeneous: stacked eagerly here (NOT inside the
                # program — see the step() comment on the multi-axis SPMD
                # miscompile)
                pvals = self._stack_stage_vals(pvals)
                avals = self._stack_stage_vals(avals)
            if use_cache:
                self._cached_vals = (pvals, avals)

        def as_val(a):
            return a._data if isinstance(a, NDArray) else nd_array(a)._data

        data_v = as_val(data_batch.data[0])
        labels = []
        if self.infos[-1].label_names:
            if getattr(data_batch, "label", None):
                labels = [as_val(l) for l in data_batch.label]
            elif is_train:
                raise MXNetError("pipelined training batch carries no label")
            else:
                # label-less inference on a loss-headed pipeline: reuse the
                # bound label arrays, as the serial executor group does
                exe = self.infos[-1].units[-1].exec_
                labels = [exe.arg_dict[n]._data
                          for n in self.infos[-1].label_names]
        # the rng key stays device-resident across steps (each program
        # returns its successor) — a fresh host-built key per execute
        # would stall the dispatch pipeline on a host->device upload, the
        # failure mode executor.py's _next_step exists to avoid
        if self._rng_dev is None:
            self._rng_dev = jax.random.PRNGKey(0)
        with_grads = bool(is_train) and self.has_loss
        if with_grads and self.dp_size > 1:
            # the dispatched program reduces gradients over the dp
            # sub-axis within each stage's rank set (explicit psum for
            # stacked rows, the all_gather transpose's psum_scatter for
            # packed rows) — counted so tests can assert composed runs
            # really carried the reduction
            _tm.counter("parallel.dp_reduce").inc()
        if with_grads:
            outs, aux_back, grads, self._rng_dev = \
                self._program(is_train, True)(
                    pvals, avals, self._rng_dev, data_v, tuple(labels))
            if self.homogeneous:
                grads = self._unstack_stages(grads)
            self._write_grads(grads)
        else:
            outs, aux_back, self._rng_dev = self._program(is_train, False)(
                pvals, avals, self._rng_dev, data_v, tuple(labels))
        if self.homogeneous:
            # program returns the 1-tuple of stacked aux leaves
            aux_back = self._unstack_stages(aux_back[0])
        self._write_aux(aux_back)
        for info in self.infos:
            # the children's param/aux snapshots are stale once the engine
            # writes into their executor arrays; get_params must re-sync
            for unit in info.units:
                unit.module._params_dirty = True
        self._last_outputs = [NDArray(o) for o in outs]
        return self._last_outputs

    def _write_grads(self, grads):
        if isinstance(grads, dict):  # packed composed {dtype: (S, Lmax)}
            grads = self._unpack_all(grads, self._param_layout)
        for info, g in zip(self.infos, grads):
            for (u, n), gv in zip(info.param_entries, g):
                arr = info.units[u].exec_.grad_dict.get(n)
                if arr is not None:
                    arr._data = gv.astype(arr._data.dtype)

    def _write_aux(self, aux_back):
        if isinstance(aux_back, dict):  # packed composed
            aux_back = self._unpack_all(aux_back, self._aux_layout)
        for info, av in zip(self.infos, aux_back):
            for (u, n), v in zip(info.aux_entries, av):
                info.units[u].exec_.aux_dict[n]._data = v

    def _unpack_all(self, packed, layout):
        """Host-side inverse of _pack_rows: per-stage value tuples."""
        out = []
        for i in range(self.S):
            local = {dt: packed[dt][i][None] for dt in packed}
            out.append(self._unpack_row(layout["per_stage"][i], local,
                                        layout["n_entries"][i]))
        return tuple(out)

    def invalidate_params(self):
        """Drop the inference param cache: the next eval run re-reads the
        child executors (hot weight swaps call this after writing them)."""
        self._cached_vals = None

    @property
    def outputs(self):
        if self._last_outputs is None:
            raise MXNetError("run a forward before get_outputs()")
        return self._last_outputs
