"""Executor — binds a Symbol to devices and arrays and runs it.

Reference: ``include/mxnet/executor.h`` + ``src/executor/graph_executor.cc``
(2307 LoC). The reference pipeline — ``nnvm::pass::Gradient`` →
``PlaceDevice`` → ``InferShape`` → ``PlanMemory`` → ``DetectInplaceAddTo`` →
``AttachOpExecs`` → per-node cached engine ops with bulk segments — exists
because CUDA kernels launch individually. Here the entire bound graph is
traced into **one jitted XLA computation**:

* gradient construction = ``jax.grad`` over the traced graph (honouring
  ``grad_req`` write/add/null, reference ``AggregateGradient``/``_grad_add``
  semantics via in-jit accumulation);
* memory planning / inplace / bulk segmentation = XLA buffer assignment and
  fusion;
* loss-layer backward conventions (SoftmaxOutput & co ignoring head grads)
  are honoured because those ops carry ``jax.custom_vjp`` rules.

``forward`` is *lazy*: it records the request and materialises outputs on
first access. ``backward`` runs a single fused forward+backward program, so a
``forward → backward → read outputs`` training iteration costs exactly one
XLA execution — the TPU analogue of the reference's bulk-exec fast path
(``MXNET_EXEC_BULK_EXEC_TRAIN``, graph_executor.cc:1247-1325).

Monitor/PartialForward-style introspection uses an un-jitted interpret mode
(SURVEY.md §2.2), matching ``MXExecutorSetMonitorCallback`` behaviour where
bulk execution disables itself when a monitor is installed
(graph_executor.cc:1252).
"""

from __future__ import annotations

import threading
from collections import Counter
from typing import Any, NamedTuple

import numpy as np

from .base import MXNetError, np_dtype
from .context import Context, current_context
from .ndarray import NDArray, ones as nd_ones, zeros as nd_zeros
from .ops.registry import KeptResiduals, OpMode
from . import aot as _aot
from . import telemetry as _tm

_GRAD_REQ = ("write", "add", "null")

# Loss heads (backward ignores out_grad) are detected from the op
# definition's ``is_loss`` flag, set where the loss layers register
# (ops/defs_nn.py) — not from a name list, so new/custom loss ops that
# set the flag participate in implicit head gradients.


def _fold_rng(rng):
    """Fold a (base_key, step) pair into a per-step PRNG key, inside jit."""
    import jax

    base, step = rng
    return jax.random.fold_in(base, step)


def _lazy_placeholder(shape, dtype, ctx=None):
    """An NDArray that reports shape/dtype but allocates device zeros (on
    ``ctx``; the default device without one) only if read before being
    written: what ``simple_bind`` hands out for arguments and gradients,
    which ``init_params`` and the first backward overwrite, and the
    bucketing reshape placeholders. ``copyto`` reads the thunk's
    ``placement`` and writes such a target without making its zeros."""
    if ctx is not None and not isinstance(ctx, Context):
        ctx = Context(ctx)
    nd = NDArray(None, ctx)

    def make():
        import jax.numpy as jnp

        nd._data = (jnp.zeros(shape, np_dtype(dtype)) if ctx is None
                    else nd_zeros(shape, ctx=ctx, dtype=dtype)._data)

    make.shape = tuple(shape)
    make.dtype = np_dtype(dtype)
    make.placement = None if ctx is None else ctx.jax_device()
    nd._set_lazy(make)
    return nd


def _fill_packed(vals, flat, fill):
    """Replace None entries of ``vals`` with static slices of ``flat``.

    ``fill`` is a static tuple of (index, offset, size, shape); under jit
    the slices are free (fused into their consumers)."""
    if not fill or flat is None:
        return list(vals)
    out = list(vals)
    for i, off, size, shape in fill:
        out[i] = flat[off:off + size].reshape(shape)
    return out


def _split_out(vals, fill):
    """Inverse of _fill_packed for program OUTPUTS: gather the packed
    positions into one flat f32 buffer, leaving None in their slots."""
    import jax.numpy as jnp

    if not fill:
        return list(vals), None
    out = list(vals)
    segs = []
    for i, off, size, shape in fill:
        segs.append(out[i].astype(jnp.float32).ravel())
        out[i] = None
    return out, jnp.concatenate(segs)


def _phase(name):
    """The scope one of the executor's own phases lowers under
    (``profiler.phase_scope``: the trace reader books its device time to
    the phase, beside the graph's operators)."""
    import jax

    from . import profiler as _prof

    return jax.named_scope(_prof.phase_scope(name))


def _share_bytes(handles):
    """Bytes one device holds of these NDArrays, from their shapes and
    types: an array sharded over a mesh counts its shard. Host arithmetic;
    a handle whose array is not there yet counts whole."""
    total = 0
    for h in handles:
        shape = h.shape
        sharding = getattr(h._d, "sharding", None)
        if sharding is not None:
            shape = sharding.shard_shape(shape)
        total += int(np.prod(shape)) * np_dtype(h.dtype).itemsize
    return total


# held while Executor._note_train_memory sets its gauges, so that all of
# them stay ONE program's when two threads launch train programs at once
_HEAVIEST_LOCK = threading.Lock()


class _Packs(NamedTuple):
    """The small-parameter packs as an executor's programs see them: the
    static ``(index, offset, size, shape)`` fills of its argument and
    auxiliary lists, and the names whose gradients share the argument
    flat's layout."""

    arg_fill: tuple
    aux_fill: tuple
    grad_names: tuple


def _unpack(packs, arg_vals, arg_flat, aux_vals, aux_flat):
    """Every program's prologue: the full argument and auxiliary lists
    from what crossed the program boundary."""
    with _phase("unpack"):
        return (_fill_packed(arg_vals, arg_flat, packs.arg_fill),
                _fill_packed(aux_vals, aux_flat, packs.aux_fill))


def _repack(packs, aux_upd, grad_map=None):
    """Every program's epilogue, ``(grad_map, grad_flat, aux_big,
    aux_flat)``: the gradients of the packed arguments leave the map for
    one flat f32 buffer, the packed auxiliary states for another."""
    import jax.numpy as jnp

    grad_flat = None
    with _phase("repack"):
        if grad_map is not None and packs.grad_names:
            grad_map = dict(grad_map)
            grad_flat = jnp.concatenate([
                grad_map.pop(n).astype(jnp.float32).ravel()
                for n in packs.grad_names
            ])
        aux_big, aux_flat = _split_out(aux_upd, packs.aux_fill)
    return grad_map, grad_flat, aux_big, aux_flat


def _head_loss_flags(graph):
    """Which graph heads are loss outputs (drive an implicit backward).

    Variable heads count as non-loss: they too contribute zero gradient
    without an explicit head grad. Single source of truth for backward()'s
    misuse warning and _make_grad_core's gradient construction.
    """
    return [
        not node.is_variable and getattr(node.op, "is_loss", False)
        for (node, _ix) in graph.heads
    ]


def _next_step(rng):
    """Next step counter, computed inside the same program that consumes the
    rng — a separate increment dispatch (or a fresh numpy scalar per call)
    costs a full per-execute host overhead."""
    return rng[1] + np.uint32(1)


def _parse_xla_flag(v):
    """Coerce an MXNET_XLA_FLAGS value string to bool/int/float when it
    looks like one (XLA's debug-option overrides are typed)."""
    low = v.lower()
    if low in ("true", "false"):
        return low == "true"
    for cast in (int, float):
        try:
            return cast(v)
        except ValueError:
            pass
    return v


def _compiler_options():
    """XLA compiler options for every executor program, from
    ``MXNET_XLA_FLAGS``: the stand-in for the reference's per-device kernel
    tuning knobs (cuDNN autotune registry / Convolution ``workspace``).
    Values are coerced to bool/int/float when they look like one (XLA's
    debug-option overrides are typed); a key the backend does not know is
    the user's error, and XLA says so. The flags feed the AOT digests and
    the cache env fingerprint, so a persisted executable never serves a
    program compiled under different flags.
    """
    from . import env

    opts = {}
    for item in env.get("MXNET_XLA_FLAGS").split(","):
        k, _, v = item.strip().partition("=")
        if k:
            opts[k] = _parse_xla_flag(v.strip())
    return opts or None


# Most recent fused-window lowering/executable, kept as live objects and
# rendered to text on demand (tools/hlo_audit.py): holding the Lowered and
# the executable costs nothing beyond the jit cache already keeping them.
_FUSED_HLO = {}
_FUSED_DONATE = (0, 1, 3, 4, 8, 9, 10, 11)


def _record_fused_hlo(lowered, exe, call_args):
    """Stash the fused train-update program for the donation/upcast audit
    (``AOTProgram``'s ``on_compile`` hook of every fused program)."""
    try:
        import jax

        donated, pos = [], 0
        param_shapes = []
        for i, a in enumerate(call_args):
            leaves = jax.tree_util.tree_leaves(a)
            if i in _FUSED_DONATE:
                donated.extend(range(pos, pos + len(leaves)))
            if i == 0:  # updated parameters
                param_shapes = [tuple(v.shape) for v in leaves]
            pos += len(leaves)
        _FUSED_HLO.update(
            lowered=lowered, compiled=exe, donated_args=donated,
            n_args=pos, param_shapes=param_shapes,
        )
    except Exception:  # noqa: BLE001 — observability must not break training
        pass


def fused_window_hlo():
    """HLO record of the most recent fused train-window compile, or None.

    Returns a dict with ``lowered`` (StableHLO MLIR text — donated args
    carry ``tf.aliasing_output`` when jax matched them to an output),
    ``compiled`` (post-optimization HLO text — the ``input_output_alias``
    header is the executable's aliasing table), ``donated_args`` (flat
    indices the executor donated), ``n_args`` and ``param_shapes`` (shapes
    of the updated parameters). ``tools/hlo_audit.py`` consumes this to
    fail on un-aliased donations and stray parameter-sized f32 upcasts.
    """
    if not _FUSED_HLO:
        return None
    rec = dict(_FUSED_HLO)
    rec["lowered"] = rec["lowered"].as_text()
    rec["compiled"] = rec["compiled"].as_text()
    return rec


class _StepOut(NamedTuple):
    """What one fused train step returns, in the order the program has
    always returned it. ``grads`` and ``grad_flat`` are None where the
    program does not publish gradients (a pytree without leaves: XLA then
    dead-codes their f32 casts and the concatenation)."""

    outs: Any
    aux: Any
    aux_flat: Any
    grads: Any
    grad_flat: Any
    params: Any
    arg_flat: Any
    states: Any
    st_flat: Any
    hyper: Any
    guard: Any
    step: Any


class _TrainKey(NamedTuple):
    """What one executor's fused train program is traced from beyond the
    executor's own signature (``_jit_signature``, one an executor, which
    ``_aot_digest`` adds). Keys ``Executor._fused_plan`` and, rendered, the
    program's persistent-cache digest."""

    update_names: tuple
    cache_token: Any     # hashable identity of the optimizer's config
    with_head_grads: bool
    state_td: Any        # PyTreeDef of the optimizer states
    mesh: Any            # ambient mesh when backward() was scheduled
    n_steps: int
    stack_names: tuple
    guard_on: bool
    publish: bool


class _TrainPlan(NamedTuple):
    """One fused train program and what staging its arguments and
    installing its results need."""

    key: _TrainKey
    program: Any         # aot.AOTProgram over the donating jit
    upd_idx: list        # positions of the updated arguments
    other_idx: list      # positions of the rest
    st_pack: Any         # pack of the small optimizer-state leaves, or None
    grad_bytes: int      # the gradients it publishes (0: none), a device's
    state_bytes: int     # updated parameters + optimizer + aux states


def _unpublished(out):
    return out._replace(grads=None, grad_flat=None)


def _window_of(step, n_steps, stack_pos, publish):
    """``n_steps`` consecutive train steps as one program: ``fori_loop``
    over ``n_steps - 1`` STATE-ONLY iterations (params / opt-state / aux /
    rng / hyper thread through the carry; per-iteration outputs and f32
    gradient publication are dropped so XLA dead-codes them), then one
    final step unrolled OUTSIDE the loop that returns the full single-step
    output contract. ``stack_pos`` are the positions in ``other_vals`` that
    iteration ``i`` takes from slice ``i`` of ``stacks``."""
    import jax.numpy as jnp
    from jax import lax

    def _step_k(upd_vals, arg_flat, other_vals, aux_vals, aux_flat, rng,
                heads, prev_grads, st_leaves, st_flat, hyper, guard, stacks):
        def sub_data(i, ov):
            ov = list(ov)
            with _phase("window_data"):
                for p, s in zip(stack_pos, stacks):
                    ov[p] = lax.dynamic_index_in_dim(s, i, 0, keepdims=False)
            return ov

        def body(i, carry):
            (upd_c, argf_c, aux_c, auxf_c, rng_c, st_c, stf_c, hyper_c,
             guard_c) = carry
            o = step(upd_c, argf_c, sub_data(i, other_vals), aux_c, auxf_c,
                     rng_c, heads, prev_grads, st_c, stf_c, hyper_c, guard_c)
            return (o.params, o.arg_flat, o.aux, o.aux_flat,
                    (rng_c[0], o.step), o.states, o.st_flat, o.hyper,
                    o.guard)

        init = (upd_vals, arg_flat, aux_vals, aux_flat, rng, st_leaves,
                st_flat, hyper, guard)
        (upd_f, argf_f, aux_f, auxf_f, rng_f, st_f, stf_f, hyper_f,
         guard_f) = lax.fori_loop(0, n_steps - 1, body, init)
        final = step(
            upd_f, argf_f,
            sub_data(jnp.asarray(n_steps - 1, jnp.int32), other_vals),
            aux_f, auxf_f, rng_f, heads, prev_grads, st_f, stf_f, hyper_f,
            guard_f)
        # lazy boundary publication: the whole per-window publish cost a
        # pipelined fit never reads
        return final if publish else _unpublished(final)

    return _step_k


class _FCBatches:
    """What the grad core asks of ``_CompiledGraph.evaluate`` for the
    ``FullyConnected`` nodes that share a weight (``_shared_fc_plan``).

    ``batched`` maps the first node of a group whose inputs do not depend
    on each other to all of its nodes: they run as ONE matmul over their
    stacked rows, in ``order`` (the graph's topological order with those
    nodes pulled together). ``probe`` is the set of nodes whose (data rows,
    output) pair the shape probe wants left in ``seen``.
    """

    __slots__ = ("batched", "order", "skip", "probe", "seen")

    def __init__(self, batched=(), order=None, probe=()):
        self.batched = {id(nodes[0]): nodes for nodes in batched}
        self.order = order
        self.skip = {id(n) for nodes in batched for n in nodes[1:]}
        self.probe = probe
        self.seen = {}


def _fc_rows(data, params):
    """A ``FullyConnected`` node's data as the matmul sees it."""
    return data.reshape((data.shape[0], -1)) if params["flatten"] else data


def _order_with_groups(topo, groups):
    """``topo`` reordered so that the nodes of each group are consecutive,
    after the producers of every input of every one of them. The groups
    must be independent (``_independent``), or no such order exists."""
    group_of = {id(n): nodes for nodes in groups for n in nodes}
    done, order = set(), []
    for root in topo:
        stack = [root]
        while stack:
            node = stack[-1]
            if id(node) in done:
                stack.pop()
                continue
            nodes = group_of.get(id(node), (node,))
            pending = [i for n in nodes for (i, _ix) in n.inputs
                       if id(i) not in done]
            if pending:
                stack.extend(pending)
                continue
            stack.pop()
            for n in nodes:
                done.add(id(n))
                order.append(n)
    return order


def _chunks(nodes, cap):
    """``nodes`` cut into runs of at most ``cap`` (at least 2), as equal
    as they come."""
    n = -(-len(nodes) // max(cap, 2))
    size, extra = divmod(len(nodes), n)
    out, at = [], 0
    for i in range(n):
        step = size + (i < extra)
        out.append(nodes[at:at + step])
        at += step
    return out


def _independent(order, accepted, cand):
    """Whether no node of ``cand`` reads, through any path, the output of
    another: then all of them can run at once. Each group of ``accepted``
    already runs as one node (``order`` has them consecutive), which makes
    every one of its nodes depend on the inputs of all of them."""
    cand_ids = {id(n) for n in cand}
    group_of = {id(n): nodes for nodes in accepted for n in nodes}
    reach = {}  # id(node) -> whether it depends on a node of cand
    for node in order:
        if id(node) in reach:
            continue
        nodes = group_of.get(id(node), (node,))
        r = any(reach[id(i)] for n in nodes for (i, _ix) in n.inputs)
        if r and id(node) in cand_ids:
            return False
        for n in nodes:
            reach[id(n)] = r or id(n) in cand_ids
    return True


class _CompiledGraph:
    """The symbol lowered to a pure function over ordered value lists.

    ``node2dev`` (optional) maps ``id(node)`` → jax device for ctx-group
    model parallelism: values crossing into a placed node are moved with
    ``jax.device_put`` — the analogue of the reference's ``_CrossDeviceCopy``
    nodes inserted by the PlaceDevice pass (graph_executor.cc:286-385).
    """

    def __init__(self, symbol, node2dev=None, remat=False, layout="NCHW",
                 platform=None):
        self.symbol = symbol
        self.platform = platform  # of the executor's context: OpMode's
        self.node2dev = node2dev or {}
        # remat (reference MXNET_BACKWARD_DO_MIRROR): wrap each op in
        # jax.checkpoint so backward recomputes op-internal values from op
        # inputs instead of storing them — FLOPs for activation memory —
        # but for the residuals the op itself names (ops/registry.keep)
        self.remat = remat
        # device layout for the conv stack (ops/layout.py): "NHWC" re-lowers
        # Convolution/Pooling/BatchNorm channels-last at interpretation time
        # while the logical graph, shapes and weights stay NCHW
        self.layout = layout
        self.topo = symbol._topo()
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self._arg_index = {n: i for i, n in enumerate(self.arg_names)}
        self._aux_index = {n: i for i, n in enumerate(self.aux_names)}
        self.heads = symbol._outputs
        # serial numbers for rng folding — stable across traces
        self._rng_serial = {}
        serial = 0
        for node in self.topo:
            if not node.is_variable and node.op.need_rng:
                self._rng_serial[id(node)] = serial
                serial += 1
        self.num_rng_ops = serial
        # op nodes the last ``evaluate`` lowered under a scope of their own
        self.scoped_nodes = 0
        # those of them whose checkpoint kept a residual the op named
        self.kept_residual_nodes = 0
        # {instrument: n}: what those nodes' ops declared a launch counts
        # (``OpDef.launch_counts``), summed; None until an ``evaluate``
        self.launch_counts = None

    def shared_fc_groups(self):
        """[(weight name, nodes)] of the ``FullyConnected`` nodes, two or
        more, that read the same weight (and bias) Variable with the same
        parameters on one device: the time steps of an unrolled recurrent
        cell. A group split over devices by ctx-group placement is left
        out."""
        groups = {}
        for node in self.topo:
            if node.is_variable or node.op.name != "FullyConnected":
                continue
            pvars = [inode for (inode, _idx) in node.inputs[1:]]
            if not all(v.is_variable and not v.is_aux for v in pvars):
                continue
            params = node.params()
            key = (tuple(v.name for v in pvars), params["num_hidden"],
                   params["flatten"])
            groups.setdefault(key, []).append(node)
        return [
            (key[0][0], nodes) for key, nodes in groups.items()
            if len(nodes) > 1
            and len({self.node2dev.get(id(n)) for n in nodes}) == 1
        ]

    def shared_weight_reads(self, names):
        """The op nodes that read an argument of ``names`` which some other
        node reads too: the time steps of an unrolled recurrent cell, the
        passes of a looped stack, a tied head. A node that reads two such
        arguments (a weight and its bias) is one node."""
        readers = Counter()
        for node in self.topo:
            if not node.is_variable:
                readers.update({i.name for (i, _ix) in node.inputs
                                if i.is_variable and not i.is_aux
                                and i.name in names})
        return sum(
            1 for node in self.topo if not node.is_variable and any(
                readers[i.name] > 1 for (i, _ix) in node.inputs
                if i.is_variable and not i.is_aux))

    def evaluate(self, arg_vals, aux_vals, rng, is_train, monitor=None,
                 limit=None, monitor_all=False, fc_batches=None):
        """Run the graph. Returns (head_outputs, aux_updates_list).

        With ``limit`` set, interprets only the first ``limit`` op nodes and
        returns that prefix's last outputs instead of the heads — the
        PartialForward debug contract (one interpreter serves both paths so
        placement/remat/rng handling can never diverge). ``monitor_all``
        additionally reports every VARIABLE value (weights/data/aux) under
        its own name — the reference's SetMonitorCallbackEX input
        monitoring (op outputs already cover all interior edges).
        ``fc_batches`` (:class:`_FCBatches`) is the grad core's: no other
        caller passes it."""
        import jax

        from . import profiler as _prof
        from .ops import layout as _lay

        nhwc = self.layout == "NHWC"
        scoped = 0  # op nodes lowered under their own scope
        kept = 0  # op nodes whose checkpoint kept a residual
        declared = Counter()  # what the nodes' ops declared a launch counts
        policy = KeptResiduals() if self.remat else None
        env = {}
        cl = {}  # id(node) -> per-output channels-last flags (NHWC mode)
        aux_updates = list(aux_vals)
        executed = 0
        last_outs = []
        last_cl = []

        def operands(node, params):
            """The node's inputs as its op takes them, and its layout."""
            ins = [env[id(inode)][idx] for (inode, idx) in node.inputs]
            node_layout = None
            if nhwc:
                # channels-last plane (ops/layout.py): aware ops lower NHWC
                # (activation transposed in at the first one), followers pass
                # channels-last values through, everything else is a graph
                # edge that gets its operands transposed back to NCHW
                in_cl = [cl[id(inode)][idx] for (inode, idx) in node.inputs]
                name = node.op.name
                if _lay.aware(name, params, getattr(ins[0], "ndim", 0)):
                    node_layout = "NHWC"
                    if not in_cl[0]:
                        ins[0] = _lay.to_cl(ins[0])
                    for j in range(1, len(ins)):  # params stay logical
                        if in_cl[j]:
                            ins[j] = _lay.from_cl(ins[j])
                elif any(in_cl):
                    if _lay.follower(name, params) and all(
                        f or getattr(x, "ndim", 0) == 0
                        for f, x in zip(in_cl, ins)
                    ):
                        node_layout = "pass"
                    else:
                        ins = [
                            _lay.from_cl(x) if f else x
                            for f, x in zip(in_cl, ins)
                        ]
            dev = self.node2dev.get(id(node))
            if dev is not None:
                # cross-device edge: move operands onto this node's device
                # (device_put is a no-op when already there, and its vjp
                # transposes the copy so gradients flow back to the source
                # device — the backward _CrossDeviceCopy of the reference)
                ins = [jax.device_put(x, dev) for x in ins]
            return ins, node_layout

        order, skip, batched, probe = self.topo, (), {}, ()
        if fc_batches is not None:
            order = fc_batches.order or self.topo
            skip, batched = fc_batches.skip, fc_batches.batched
            probe = fc_batches.probe
        for node in order:
            if node.is_variable:
                if node.is_aux:
                    env[id(node)] = [aux_vals[self._aux_index[node.name]]]
                else:
                    env[id(node)] = [arg_vals[self._arg_index[node.name]]]
                if nhwc:
                    cl[id(node)] = [False]
                if monitor is not None and monitor_all:
                    monitor(node.name, env[id(node)][0])
                continue
            if limit is not None and executed >= limit:
                break
            if id(node) in skip:
                continue  # ran with the first node of its group
            params = node.params()
            ins, node_layout = operands(node, params)
            group = batched.get(id(node))
            if group:
                # the group's rows stacked on a new leading axis (a batch
                # axis sharded over a mesh stays where it is): one matmul
                # with M = nodes x rows, whose gradient is one too
                ins[0] = jax.numpy.stack([_fc_rows(ins[0], params)] + [
                    _fc_rows(operands(n, params)[0][0], params)
                    for n in group[1:]])
                params = dict(params, flatten=False)
            node_rng = None
            if node.op.need_rng:
                node_rng = jax.random.fold_in(rng, self._rng_serial[id(node)])
            op_layout = "NHWC" if node_layout == "NHWC" else None
            # the node's name on everything it lowers (forward, its
            # transpose, its recomputation): what the trace reader books
            # device time by (profiler.parse_scope)
            scoped += len(group) if group else 1
            with jax.named_scope(_prof.node_scope(
                    node.op.name, node.name, len(group) if group else 0)):
                if self.remat and not node.op.aux_names(params):
                    apply_fn = jax.checkpoint(
                        lambda inner, _op=node.op, _p=params, _m=OpMode(
                            is_train=is_train, rng=node_rng,
                            layout=op_layout, platform=self.platform,
                        ): _op.apply(inner, _p, _m),
                        policy=policy,
                    )
                    # under jax.grad the checkpoint is partially evaluated,
                    # and the policy asked, as it is bound
                    answers = policy.answers
                    outs, new_aux = apply_fn(ins)
                    kept += policy.kept_since(answers, node.op.name, params,
                                              ins)
                else:
                    outs, new_aux = node.op.apply(
                        ins, params,
                        OpMode(is_train=is_train, rng=node_rng,
                               layout=op_layout, platform=self.platform),
                    )
            declared.update(node.op.launch_counts(ins, outs, params,
                                                  self.platform))
            if group:
                for i, n in enumerate(group[1:], 1):
                    env[id(n)] = [outs[0][i]]
                    if nhwc:
                        cl[id(n)] = [False]
                outs = [outs[0][0]]
            elif id(node) in probe:
                fc_batches.seen[id(node)] = (_fc_rows(ins[0], params),
                                             outs[0])
            env[id(node)] = outs
            if nhwc:
                if node_layout == "NHWC":
                    # 4-D outputs are channels-last; BN's mean/var are (C,)
                    cl[id(node)] = [getattr(o, "ndim", 0) == 4 for o in outs]
                elif node_layout == "pass":
                    cl[id(node)] = [True] * len(outs)
                else:
                    cl[id(node)] = [False] * len(outs)
                last_cl = cl[id(node)]
            last_outs = outs
            executed += 1
            if new_aux:
                n_args = len(node.op.arg_names(params))
                for i, na in enumerate(new_aux):
                    aux_node = node.inputs[n_args + i][0]
                    aux_updates[self._aux_index[aux_node.name]] = na
            if monitor is not None:
                for i, o in enumerate(outs[: node.op.num_visible_outputs(params)]):
                    if nhwc and cl[id(node)][i]:
                        o = _lay.from_cl(o)  # monitors see logical layout
                    suffix = "_output" if i == 0 else f"_output{i}"
                    monitor(node.name + suffix, o)
        self.scoped_nodes, self.kept_residual_nodes = scoped, kept
        self.launch_counts = declared
        if limit is not None:
            if nhwc and last_cl:
                last_outs = [
                    _lay.from_cl(o) if f else o
                    for o, f in zip(last_outs, last_cl)
                ]
            return last_outs, aux_updates
        head_outs = [env[id(node)][idx] for (node, idx) in self.heads]
        if nhwc:
            head_outs = [
                _lay.from_cl(o) if cl[id(node)][idx] else o
                for o, (node, idx) in zip(head_outs, self.heads)
            ]
        return head_outs, aux_updates


class Executor:
    """A bound computation (reference ``Executor::Bind``)."""

    def __init__(self, symbol, ctx, args=None, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None, shared_exec=None,
                 in_shardings=None):
        from . import env as _env

        self._symbol = symbol
        self._ctx = ctx if isinstance(ctx, Context) else Context(ctx)
        self._node2dev = self._place_nodes(symbol, group2ctx)
        # NaiveEngine: synchronous un-jitted execution for debugging
        # (reference sync-debug engine toggle, src/engine/engine.cc:14-27)
        self._naive = _env.get("MXNET_ENGINE_TYPE") == "NaiveEngine"
        from .ops import layout as _lay

        self.graph = _CompiledGraph(
            symbol, node2dev=self._node2dev,
            remat=_env.get("MXNET_BACKWARD_DO_MIRROR"),
            layout=_lay.resolve(self._ctx),
            platform=self._ctx.jax_device().platform,
        )
        self.arg_names = self.graph.arg_names
        self.aux_names = self.graph.aux_names
        self.output_names = symbol.list_outputs()
        self._group2ctx = group2ctx
        self._in_shardings = dict(in_shardings or {})
        self._monitor_callback = None

        # --- normalise args ----------------------------------------------
        self.arg_dict = self._norm_arrays(args, self.arg_names, "args")
        self.aux_dict = self._norm_arrays(aux_states, self.aux_names, "aux_states")
        # grad_req per arg
        if isinstance(grad_req, str):
            self.grad_req = {n: grad_req for n in self.arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self.grad_req = dict(zip(self.arg_names, grad_req))
        elif isinstance(grad_req, dict):
            self.grad_req = {n: grad_req.get(n, "null") for n in self.arg_names}
        else:
            raise MXNetError(f"invalid grad_req {grad_req!r}")
        for n, r in self.grad_req.items():
            if r not in _GRAD_REQ:
                raise MXNetError(f"invalid grad_req {r!r} for {n}")
        self.grad_dict = self._norm_arrays(
            args_grad, self.arg_names, "args_grad", allow_missing=True
        )
        for n in self.arg_names:
            if self.grad_req[n] != "null" and n not in self.grad_dict:
                self.grad_req[n] = "null"
        self._wrt_names = [
            n for n in self.arg_names if self.grad_req[n] != "null"
        ]

        # persistent output handles (rebound in place on every run)
        self._output_handles = [
            NDArray(None) for _ in range(len(self.output_names))
        ]
        self._pending = None  # None | 'train' | 'eval'
        self._fresh = False
        self._step = 0
        self._step_dev = None  # device-resident mirror of _step (see _rng_key)
        self._step_dev_val = -1
        import jax

        # executor rng chain derives from the GLOBAL seed at bind time, so
        # mx.random.seed() controls symbolic Dropout/rrelu (reference:
        # per-device Resource kRandom seeded from the global seed)
        from . import random as _random

        self._base_key = _random.next_key()
        self._jit_cache = {}
        self._fused_plan = {}  # _TrainKey -> _TrainPlan
        self._sig_cache = None  # memoized _jit_signature
        self._sym_sha_cache = None  # memoized symbol-graph digest
        self._guard_dev = None  # device [total, consec] non-finite counters
        self._fc_plan = None  # memoized _shared_fc_plan
        self._shared_reads = None  # memoized graph.shared_weight_reads
        self._grads_crowd = None  # memoized _grads_crowd_device
        self._held_memo = None  # memoized _held_bytes
        # op nodes the train programs' trace lowered under a scope, those
        # of them whose recomputation kept a residual the op named, and
        # what their ops declared a launch counts (None: not traced here)
        self._scoped_nodes = 0
        self._kept_residual_nodes = 0
        self._launch_counts = None
        if shared_exec is not None:
            # bucketing: share compiled-function cache and memory with the
            # master executor (reference shared_exec data_pool_ reuse,
            # graph_executor.cc:813-817). jax arrays are refcounted so
            # sharing = simply not duplicating parameter arrays; the jit
            # cache is shared to reuse traced programs across buckets.
            self._jit_cache = shared_exec._jit_cache

    # ------------------------------------------------------------------
    def _place_nodes(self, symbol, group2ctx):
        """Lower ctx_group annotations to a node→device placement map
        (the PlaceDevice pass, reference graph_executor.cc:286-385).

        Returns {} when no annotated node maps to a known group — the graph
        then compiles as one single-device XLA program. With placement the
        graph runs un-jitted: each op dispatches on its assigned device
        (jax computation-follows-data ≈ the reference's per-device engine
        queues) with device_put transfers at group boundaries. Unannotated
        op nodes get the bind context (reference AssignContext default), so
        a node joining two groups always has a device to copy operands to.
        """
        if not group2ctx:
            return {}
        out = {}
        topo = symbol._topo()
        for node in topo:
            grp = node.attrs.get("ctx_group")
            if grp is None or node.is_variable:
                continue
            ctx = group2ctx.get(grp)
            if ctx is not None:
                out[id(node)] = ctx.jax_device()
        if out:
            default_dev = self._ctx.jax_device()
            for node in topo:
                if not node.is_variable and id(node) not in out:
                    out[id(node)] = default_dev
        return out

    def _norm_arrays(self, arrays, names, what, allow_missing=False):
        if arrays is None:
            if allow_missing:
                return {}
            if names:
                raise MXNetError(f"{what}: expected arrays for {names}")
            return {}
        if isinstance(arrays, dict):
            out = {}
            for n in names:
                if n in arrays:
                    if not isinstance(arrays[n], NDArray):
                        raise MXNetError(f"{what}[{n}] must be NDArray")
                    out[n] = arrays[n]
                elif not allow_missing:
                    raise MXNetError(f"{what}: missing array for {n!r}")
            return out
        arrays = list(arrays)
        if len(arrays) != len(names):
            raise MXNetError(
                f"{what}: expected {len(names)} arrays, got {len(arrays)}"
            )
        out = {}
        for n, a in zip(names, arrays):
            if a is None:
                if not allow_missing:
                    raise MXNetError(f"{what}: missing array for {n!r}")
                continue
            out[n] = a
        return out

    # ------------------------------------------------------------------
    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self.arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self.arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self.aux_names]

    @property
    def output_dict(self):
        return dict(zip(self.output_names, self.outputs))

    # ------------------------------------------------------------------
    def _arg_vals(self):
        return [self.arg_dict[n]._data for n in self.arg_names]

    def _aux_vals(self):
        return [self.aux_dict[n]._data for n in self.aux_names]

    # --- small-parameter packing ---------------------------------------
    # A ResNet-scale training step moves ~500 tiny f32 tensors (BN scalars,
    # biases, their grads/momenta/statistics) across the program boundary
    # every iteration; XLA stages each through its own async VMEM copy and
    # the measured wait cost is ~5% of the step (see docs/architecture.md
    # perf notes). Packing them into one flat f32 buffer per family (args /
    # aux / grads / optimizer state) collapses those hundreds of boundary
    # tensors into four. The flat buffers are the device-resident source
    # of truth on the hot path; the per-name NDArray handles stay coherent
    # through lazy slice thunks (a read costs one slice dispatch; a user
    # write is detected and folded back into the flat before the next
    # step). Disabled under meshes/sharding, NaiveEngine, ctx-group
    # placement, or MXNET_PACK_SMALL_PARAMS=0.
    _PACK_MAX_ELEMS = 8192

    def _pack_eligible(self, arr):
        import jax

        return (
            arr is not None
            and str(arr.dtype) == "float32"
            and 0 < arr.size <= self._PACK_MAX_ELEMS
            and isinstance(getattr(arr, "sharding", None),
                           jax.sharding.SingleDeviceSharding)
        )

    def _make_pack(self, keys, arrays):
        """A pack layout over the eligible ``arrays[k]`` for ``k`` in
        ``keys``, or None: not worth one for a handful of tensors."""
        sel = [k for k in keys if self._pack_eligible(arrays[k])]
        if len(sel) < 8:
            return None
        offs, off = {}, 0
        for k in sel:
            a = arrays[k]
            offs[k] = (off, int(a.size), tuple(a.shape))
            off += int(a.size)
        return {"names": sel, "offs": offs, "total": off,
                "flat": None, "cells": {}}

    def _small_state(self):
        """Packing state, built on first use (None when disabled)."""
        if getattr(self, "_small", False) is not False:
            return self._small
        from . import env as _env

        self._small = None
        # the win is the fused train step's boundary; with bulk exec off
        # the per-param update path would pay a slice dispatch per packed
        # grad read plus a flat rebuild per step for no benefit
        if (not _env.get("MXNET_PACK_SMALL_PARAMS")
                or not _env.get("MXNET_EXEC_BULK_EXEC_TRAIN")
                or self._naive or self._node2dev or self._in_shardings):
            return None
        from .parallel.mesh import current_mesh

        if current_mesh() is not None:
            return None

        arg_pack = self._make_pack(
            [n for n in self._wrt_names if self.grad_req[n] == "write"],
            {n: h._d for n, h in self.arg_dict.items()})
        aux_pack = self._make_pack(
            self.aux_names, {n: h._d for n, h in self.aux_dict.items()})
        if arg_pack is None and aux_pack is None:
            return None
        grad_pack = None
        if arg_pack is not None:
            # gradients of the packed args share the arg layout but have
            # their own flat buffer + coherence cells
            grad_pack = {"names": arg_pack["names"],
                         "offs": arg_pack["offs"],
                         "total": arg_pack["total"],
                         "flat": None, "cells": {}}
        self._small = {"arg": arg_pack, "aux": aux_pack, "grad": grad_pack}
        return self._small

    def _install_grad_flat(self, grad_flat):
        small = self._small_state()
        if grad_flat is None or not small or small["grad"] is None:
            return
        self._pack_install(small["grad"], self.grad_dict, grad_flat,
                           force=True)

    def _mark_grads_unpublished(self):
        """After a fused step or window that published nothing (every one
        ``fit`` runs) the gradient buffers were dead-coded out of the
        program; the old handles would silently serve a PREVIOUS step's
        values, so every wrt handle raises loudly until the next publishing
        step overwrites it."""
        for n in self._wrt_names:
            h = self.grad_dict.get(n)
            if h is None:
                continue
            # metadata WITHOUT materializing: a deleted (donated) jax array
            # still exposes its aval shape, and packed-slice and bind-time
            # thunks carry shape on the callback — never resolve _data here
            # (it would slice the pack, or run a scheduled backward, just
            # to throw the value away). A gradient handle that never held
            # an array has its argument's shape.
            shape = next((tuple(v) for v in (
                getattr(x, "shape", None)
                for hd in (h, self.arg_dict[n]) for x in (hd._d, hd._lazy))
                if v is not None), None)

            def thunk(n=n):
                raise MXNetError(
                    f"gradient '{n}' was not published: the last step ran "
                    "inside fit(), whose fused steps and windows return no "
                    "gradients (publish_grads=False: nothing in fit reads "
                    "them), or update() left out gradients that take over "
                    "an eighth of the device's memory. Read them after "
                    "backward() and before update(), or drive the loop by "
                    "hand with update(publish_grads=True) / "
                    "train_window(..., publish_grads=True).")

            if shape is not None:
                thunk.shape = shape
                thunk.dtype = np.float32
            h._d = None  # the stale pre-window value must never be served
            h._set_lazy(thunk)

    @staticmethod
    def _pack_clean(pack, handles):
        """True when no packed handle was written since the last install."""
        cells = pack["cells"]
        for n in pack["names"]:
            h = handles[n]
            c = cells.get(n)
            if c is None:
                return False  # never installed: flat not built yet
            if h._lazy is c or h._d is c or (
                    isinstance(c, tuple) and h._d is c[0]):
                continue
            return False
        return True

    def _pack_gather(self, pack, handles):
        """Current flat for ``pack``, folding in any user writes."""
        import jax.numpy as jnp

        if pack is None:
            return None
        if pack["flat"] is not None and self._pack_clean(pack, handles):
            return pack["flat"]
        flat = jnp.concatenate(
            [jnp.asarray(handles[n]._data, jnp.float32).ravel()
             for n in pack["names"]])
        self._pack_install(pack, handles, flat, fold=True)
        return flat

    def _pack_install(self, pack, handles, flat, fold=False, force=False):
        """Adopt ``flat`` as the family's source of truth; handles become
        lazy slice thunks. A handle written since the last install keeps
        the user's value (last-write-wins) — unless ``fold`` (the flat was
        just built FROM the handles, so their values are already in it and
        they now count as clean)."""
        pack["flat"] = flat
        cells = pack["cells"]
        for n in pack["names"]:
            h = handles[n]
            c = cells.get(n)
            dirty = not force and c is not None and not (
                h._lazy is c or h._d is c
                or (isinstance(c, tuple) and h._d is c[0]))
            if dirty:
                if fold:
                    cells[n] = (h._d,)  # value folded into the new flat
                continue  # keep the handle's (newer) value

            off, size, shape = pack["offs"][n]

            def thunk(h=h, n=n, off=off, size=size, shape=shape,
                      pack=pack, cells=cells):
                if pack["flat"] is None:
                    raise MXNetError(
                        "packed parameter buffer was invalidated by a "
                        "failed fused step; re-initialize via "
                        "set_params()/load before reading")
                val = pack["flat"][off:off + size].reshape(shape)
                cells[n] = (val,)
                h._data = val

            thunk.shape = shape
            thunk.dtype = np.float32
            cells[n] = thunk
            h._set_lazy(thunk)

    def _split_vals(self, names, handles, pack):
        """(vals list with None at packed positions, flat-or-None)."""
        if pack is None:
            return [handles[n]._data for n in names], None
        flat = self._pack_gather(pack, handles)
        packed = set(pack["names"])
        vals = [None if n in packed else handles[n]._data for n in names]
        return vals, flat

    def _arg_vals_split(self):
        small = self._small_state()
        return self._split_vals(
            self.arg_names, self.arg_dict, small["arg"] if small else None)

    def _aux_vals_split(self):
        small = self._small_state()
        return self._split_vals(
            self.aux_names, self.aux_dict, small["aux"] if small else None)

    def _rng_key(self):
        """Per-step rng as a (base_key, step) pair of DEVICE values.

        The fold happens INSIDE the jitted program (``_fold_rng``); both the
        base key and the step counter live on the device. Marshalling even a
        single fresh numpy scalar with each execute costs a blocking
        host->device transfer that stalls the execute pipeline (its cost
        on this runtime: not measured), so the step advances via an
        all-device increment program and is uploaded only when the host
        counter diverges (first use / checkpoint restore).
        """
        import jax

        if self._step_dev is None or self._step_dev_val != self._step:
            self._step_dev = jax.device_put(np.uint32(self._step))
            self._step_dev_val = self._step
        return (self._base_key, self._step_dev)

    def _accept_next_step(self, next_step, scheduled_val):
        """Adopt the step counter a program returned (= scheduled_val + 1),
        keeping the device mirror warm so steady-state training/inference
        loops never re-upload it."""
        self._step_dev = next_step
        self._step_dev_val = scheduled_val + 1

    def _jit_signature(self):
        """Memoized shape/dtype/grad signature of this executor's programs.

        Rebuilding the (name, shape, str(dtype)) tuples for every arg on
        every step costs real dispatch time at ResNet argument counts; the
        signature can only change on rebind/reshape (both create a NEW
        Executor), so it is computed once per executor. The ambient mesh is
        deliberately NOT part of it — ``_get_jit`` adds ``current_mesh()``
        per call, so mesh changes still key distinct programs.
        """
        sig = self._sig_cache
        if sig is None:
            packs = self._packs()
            sig = (
                tuple((n, self.arg_dict[n].shape, str(self.arg_dict[n].dtype))
                      for n in self.arg_names),
                tuple((n, self.aux_dict[n].shape, str(self.aux_dict[n].dtype))
                      for n in self.aux_names),
                tuple(self._wrt_names),
                tuple(sorted((n, r) for n, r in self.grad_req.items())),
                packs.arg_fill,
                packs.aux_fill,
                self.graph.layout,
            )
            self._sig_cache = sig
        return sig

    def _sym_sha(self):
        """Digest of the symbol graph itself — shapes alone cannot key a
        cross-process executable cache (two graphs can share an argument
        signature)."""
        sha = self._sym_sha_cache
        if sha is None:
            import hashlib

            h = hashlib.sha256(self._symbol.tojson().encode())
            h.update(repr(sorted(self._symbol.attr_dict().items())).encode())
            sha = h.hexdigest()
            self._sym_sha_cache = sha
        return sha

    @staticmethod
    def _mesh_token(mesh):
        """Process-stable rendering of an ambient/scheduled mesh for cache
        digests (None when no mesh). Mesh *objects* have no cross-process
        identity; the GraftMesh spec + concrete device assignment does."""
        from .parallel.mesh import as_graft

        gm = as_graft(mesh)
        return None if gm is None else gm.cache_token()

    def _shardings_token(self):
        """Deterministic rendering of the bound input shardings, or None
        when a sharding kind can't be rendered stably (then the program
        must not persist)."""
        out = []
        for n in sorted(self._in_shardings):
            s = self._in_shardings[n]
            spec = getattr(s, "spec", None)
            smesh = getattr(s, "mesh", None)
            if spec is None or smesh is None:  # not a NamedSharding
                return None
            out.append((n, str(spec), self._mesh_token(smesh)))
        return tuple(out)

    def _aot_digest(self, what, fields, mesh):
        """Persistent-cache digest for one of this executor's programs, or
        None when it must not persist: cache off, un-renderable shardings,
        or interpret modes (their "programs" are python closures).
        ``fields`` is what the trace is determined by beyond the symbol
        graph and the argument signature: kind and mode of a forward /
        train-step program, the rendered :class:`_TrainKey` of a fused one
        (state-leaf shapes follow the parameter signature, and
        hyperparameters are traced inputs).
        Mesh-sharded programs persist keyed by the mesh spec + device
        assignment — the GraftMesh cache token joins the signature, so a
        warm process on the same topology (same MXNET_MESH / installed
        spec) rebinds with zero XLA compiles and a different layout never
        false-hits."""
        if not _aot.cache_enabled():
            return None
        if self._node2dev or self._naive:
            return None
        shard_tok = self._shardings_token()
        if shard_tok is None:
            return None
        opts = _compiler_options()
        dev = self._ctx.jax_device()
        return _aot.digest(
            what, self._sym_sha(), self._jit_signature(), fields,
            self._mesh_token(mesh), shard_tok, self.graph.remat,
            self.graph.layout, dev.platform, getattr(dev, "device_kind", ""),
            tuple(sorted(opts.items())) if opts else (),
        )

    # --- non-finite-gradient guard (MXNET_NONFINITE_GUARD) -------------
    @staticmethod
    def _nonfinite_guard_on():
        from . import env as _env

        return str(_env.get("MXNET_NONFINITE_GUARD") or "").lower() in (
            "skip", "rollback", "raise")

    def _guard_zeros(self):
        # uncommitted (no target device/sharding), like the hyper tape:
        # jit replicates it to wherever the program runs, so the same
        # buffer convention works single-device, context-mesh and
        # named-mesh alike (a committed device-0 scalar would conflict
        # with mesh-sharded parameters at lowering)
        import jax

        return jax.device_put(np.zeros(2, np.int32))

    def nonfinite_guard_stats(self):
        """``(total_skips, consecutive_skips)`` of the fused-step guard.

        Blocks on the device counter buffer — call at sync points (epoch
        boundaries), never per batch."""
        g = self._guard_dev
        if g is None:
            return (0, 0)
        import jax

        a = np.asarray(jax.device_get(g))
        return (int(a[0]), int(a[1]))

    def reset_nonfinite_guard(self, keep_total=True):
        """Zero the consecutive-skip counter (after a rollback escalation
        recovered) — or both counters with ``keep_total=False``."""
        if self._guard_dev is None:
            return
        total = self.nonfinite_guard_stats()[0] if keep_total else 0
        import jax

        self._guard_dev = jax.device_put(
            np.asarray([total, 0], np.int32),
            self._guard_dev.sharding,
        )

    def _get_jit(self, kind, is_train=False, with_head_grads=False):
        """Build (lazily) the jitted program for this graph shape-signature.

        Jitted programs come back wrapped in :class:`aot.AOTProgram`:
        ``lower().compile()``d on first call (or deserialized from the
        persistent cache under ``MXNET_AOT_CACHE``) and invoked as concrete
        executables from then on — ``executor.jit_compile`` counts actual
        XLA compiles, so a warm-cache process runs at 0.
        """
        import jax

        from .parallel.mesh import current_mesh

        # ops may bake the ambient mesh into the trace (RingAttention's
        # shard_map); a program traced under one mesh context must not
        # be served under another
        cache_key = (kind, is_train, with_head_grads, self._jit_signature(),
                     current_mesh())
        fn = self._jit_cache.get(cache_key)
        if fn is not None:
            return fn
        packs = self._packs()
        graph = self.graph

        if kind == "forward":

            def _fwd(arg_vals, arg_flat, aux_vals, aux_flat, rng):
                full_args, full_aux = _unpack(packs, arg_vals, arg_flat,
                                              aux_vals, aux_flat)
                outs, aux_upd = graph.evaluate(
                    full_args, full_aux, _fold_rng(rng), is_train
                )
                _, _, aux_big, aux_flat_out = _repack(packs, aux_upd)
                return outs, aux_big, aux_flat_out, _next_step(rng)

            traced = _fwd
        elif kind == "train_step":
            core = self._make_grad_core()

            def _tstep(arg_vals, arg_flat, aux_vals, aux_flat, rng, heads,
                       prev):
                full_args, full_aux = _unpack(packs, arg_vals, arg_flat,
                                              aux_vals, aux_flat)
                outs, aux_upd, grad_map = core(
                    full_args, full_aux, rng, heads, prev
                )
                grad_map, grad_flat, aux_big, aux_flat_out = _repack(
                    packs, aux_upd, grad_map)
                return (outs, aux_big, aux_flat_out, grad_map, grad_flat,
                        _next_step(rng))

            traced = _tstep
        else:
            raise MXNetError(f"unknown jit kind {kind}")

        if self._node2dev or self._naive:
            # ctx-group placement spans devices: XLA compiles single-device
            # (or SPMD-sharded) programs only, so a placed graph executes
            # eagerly — per-op dispatch on the op's device, like the
            # reference engine's per-device worker queues. NaiveEngine
            # interprets synchronously. Either way this IS the "compile"
            # for the signature (the cached-op cache-miss analogue).
            _tm.counter("executor.jit_compile").inc()
            fn = traced
        else:
            fn = _aot.AOTProgram(
                jax.jit(traced,
                        compiler_options=_compiler_options()),
                key_digest=self._aot_digest("jit", cache_key[:3],
                                            cache_key[-1]),
                # a real XLA compile in steady state is a perf bug worth
                # surfacing; deserialized warm starts don't count
                compile_counter="executor.jit_compile",
                compile_span="executor.jit_build",
                label=self._program_label(
                    "train step" if kind == "train_step" else
                    "forward (train)" if is_train else "forward"),
            )
        self._jit_cache[cache_key] = fn
        return fn

    def compile(self, kinds=None):
        """AOT-compile this executor's programs without executing them.

        The jax production warmup recipe (``lower().compile()``): each
        requested program is compiled — or deserialized from the
        persistent cache under ``MXNET_AOT_CACHE`` — so the first real
        step pays no XLA wait, and with the cache enabled every later
        process with the same signature starts at
        ``executor.jit_compile == 0`` (``tools/aot_warm.py`` drives this
        out of band). XLA compilation releases the GIL, so callers may
        warm several executors from threads
        (``BucketingModule.compile``).

        ``kinds`` ⊆ {"forward", "forward_train", "train_step"}; None warms
        eval forward, plus train forward and the fused fwd+bwd program
        when the executor computes gradients and the graph has a loss head
        (a head-grad-less train_step on a loss-free graph is a trace-time
        error, not a warmable program). Returns the kinds compiled;
        interpret modes (monitor / NaiveEngine / ctx-group placement) have
        no XLA program and return [].
        """
        if self._node2dev or self._naive or \
                self._monitor_callback is not None:
            return []
        if kinds is None:
            kinds = ["forward"]
            if self._wrt_names:
                kinds.append("forward_train")
                if any(_head_loss_flags(self.graph)):
                    kinds.append("train_step")
        args_in, args_flat = self._arg_vals_split()
        aux_in, aux_flat = self._aux_vals_split()
        rng = self._rng_key()
        done = []
        for kind in kinds:
            if kind in ("forward", "forward_train"):
                prog = self._get_jit(
                    "forward", is_train=(kind == "forward_train"))
                args = (args_in, args_flat, aux_in, aux_flat, rng)
            elif kind == "train_step":
                prog = self._get_jit("train_step")
                prev = {n: self.grad_dict[n]._data for n in self._wrt_names
                        if self.grad_req[n] == "add"}
                args = (args_in, args_flat, aux_in, aux_flat, rng, None,
                        prev)
            else:
                raise MXNetError(f"unknown compile kind {kind!r}")
            ensure = getattr(prog, "ensure_compiled", None)
            if ensure is not None and ensure(args):
                done.append(kind)
        return done

    def _packs(self):
        """The pack layout this executor's programs are traced with."""
        small = self._small_state() or {"arg": None, "aux": None}

        def fill(order, pack):
            packed = set(pack["names"]) if pack else ()
            return tuple((i, *pack["offs"][n]) for i, n in enumerate(order)
                         if n in packed)

        return _Packs(
            fill(self.arg_names, small["arg"]),
            fill(self.aux_names, small["aux"]),
            tuple(small["arg"]["names"]) if small["arg"] else ())

    def _shared_fc_plan(self):
        """The ``FullyConnected`` groups that this executor's train programs
        run as batched matmuls: ``(batched, order, weights)``, the runs of
        nodes that each become one matmul, the order to run the graph in,
        and the number of shared weights they cover; ``([], None, 0)``
        where there is none.

        An unrolled recurrent cell applies one weight at every time step,
        and under ``jax.grad`` each application is three small matmuls
        (forward, data gradient, weight gradient) that stream the whole
        weight for a batch's rows. Where the nodes of such a group do not
        read each other's outputs (the i2h of every layer: its inputs are
        there before the recurrence starts) a run of them is ONE matmul over
        their stacked rows, and ``jax.grad`` of that is one matmul for the
        data gradient and one for the weight's. A group whose nodes feed
        each other (the h2h chain) is left as it is.

        Decided once an executor from what the graph and the bound shapes
        show (one abstract forward trace, only where the graph has a shared
        weight at all): a group is batched when the weight traffic that
        removes, weight bytes x (nodes - 1), exceeds the copies it adds, the
        nodes' data and output bytes; and it is cut into runs whose rows
        outweigh the weight by less than one node's, because a matmul that
        streams more rows than weight has nothing left to amortise and its
        stacks crowd the weights out of the chip's fast memory (on the v5e
        runs of 10 time steps of the PTB LSTM beat runs of 5, 15, 20 and the
        whole of 30 or 60; PERF.md, PR 25). Nodes of unlike data shape form
        groups of their own.
        """
        if self._fc_plan is not None:
            return self._fc_plan
        import jax

        graph = self.graph
        cand = [g for g in graph.shared_fc_groups()
                if g[0] in self._wrt_names]
        batched, order, weights = [], graph.topo, 0
        if cand:
            probe = _FCBatches(probe={id(n) for g in cand for n in g[1]})

            def forward(arg_vals, aux_vals, key):
                graph.evaluate(arg_vals, aux_vals, key, True,
                               fc_batches=probe)
                return probe.seen

            def structs(arrays, names):
                return [jax.ShapeDtypeStruct(arrays[n].shape, arrays[n].dtype)
                        for n in names]

            seen = jax.eval_shape(
                forward, structs(self.arg_dict, self.arg_names),
                structs(self.aux_dict, self.aux_names), self._base_key)
            for wname, nodes in cand:
                by_shape = {}
                for n in nodes:
                    x, _y = seen[id(n)]
                    by_shape.setdefault((x.shape, x.dtype), []).append(n)
                w = self.arg_dict[wname]
                w_bytes = w.size * w.dtype.itemsize
                for same in by_shape.values():
                    copied = sum(v.size * v.dtype.itemsize
                                 for n in same for v in seen[id(n)])
                    if (w_bytes * (len(same) - 1) > copied
                            and _independent(order, batched, same)):
                        batched.extend(_chunks(
                            same, -(-w_bytes * len(same) // copied)))
                        weights += 1
                        order = _order_with_groups(graph.topo, batched)
        self._fc_plan = (batched, order if batched else None, weights)
        return self._fc_plan

    def _grads_crowd_device(self):
        """True where one set of this executor's gradients is more than an
        eighth of its device's memory: the default of a fused step whose
        caller did not say is then to leave them out of what it returns.
        Only a hand-written loop still reaches this (``Module.update()``
        with no word, which cannot know whether its gradients will be
        read, and the serial fallback of ``train_window``): ``fit`` says
        ``publish_grads=False`` on every step and never asks. Published, a
        step's gradients live beside the next step's (a fourth float32
        copy of a model whose weights and Adam moments already fill most
        of a chip, twice over); left out, they are consumed by the update
        where they are computed, and ``grad_dict`` raises until a
        ``backward()`` that is read runs. An explicit ``publish_grads`` is
        always honoured. False where the device does not report its memory
        (the CPU). The sizes behind the eighth: docs/architecture.md."""
        if self._grads_crowd is None:
            limit = (self._ctx.memory_stats() or {}).get("bytes_limit")
            self._grads_crowd = (bool(limit)
                                 and self._held_bytes()[0] * 8 > limit)
        return self._grads_crowd

    def _held_bytes(self):
        """``(one set of this executor's gradients, its auxiliary states)``,
        a device's share in bytes. A gradient has its argument's shape,
        dtype and sharding; its own handle may be a scheduled backward,
        which a read would run."""
        if self._held_memo is None:
            self._held_memo = (
                _share_bytes(self.arg_dict[n] for n in self._wrt_names),
                _share_bytes(self.aux_dict.values()))
        return self._held_memo

    def _program_label(self, kind):
        """``kind`` and the shapes of the first and the last input no
        gradient is taken of (the batch and its labels: what tells one
        bucket's program from another's), for ``aot.memory_table``'s
        rows."""
        fed = [n for n in self.arg_names if self.grad_req[n] == "null"]
        shown = fed if len(fed) < 3 else [fed[0], None, fed[-1]]
        return f"{kind} [" + ", ".join(
            "..." if n is None else f"{n}{tuple(self.arg_dict[n].shape)}"
            for n in shown) + "]"

    def _declared_from_shapes(self):
        """What the graph's nodes declare one launch of a train program
        counts (``OpDef.launch_counts``), asked over shapes and types
        inferred from the bound arguments: for the launches of a program
        whose trace did not run here (an executable read from the
        ``MXNET_AOT_CACHE`` store, a program another executor traced into
        the ``_jit_cache`` they share). Nothing is inferred for a graph
        none of whose ops declares anything."""
        import jax

        nodes = [n for n in self.graph.topo
                 if not n.is_variable and n.op.launch_instruments]
        counts = Counter()
        if not nodes:
            return counts
        internals = self._symbol.get_internals()
        _, shapes, _ = internals.infer_shape(
            **{n: tuple(a.shape) for n, a in self.arg_dict.items()})
        _, dtypes, _ = internals.infer_type(
            **{n: a.dtype for n, a in self.arg_dict.items()})
        entry = {(id(n), i): jax.ShapeDtypeStruct(s, np_dtype(d))
                 for (n, i), s, d in zip(internals._outputs, shapes, dtypes)}
        for node in nodes:
            params = node.params()
            counts.update(node.op.launch_counts(
                [entry[id(n), i] for n, i in node.inputs],
                [entry[id(node), i]
                 for i in range(node.op.num_visible_outputs(params))],
                params, self.graph.platform))
        return counts

    def _count_train_launch(self):
        """One launch of a train program, counted by what it holds: the op
        nodes its trace lowered under their own scope (0: a path evaluates
        the graph with no names, or the executable came from the
        ``MXNET_AOT_CACHE`` store and was not traced here), those of them
        whose per-operator recomputation (``MXNET_BACKWARD_DO_MIRROR``)
        kept a residual the op named (``ops/registry.keep``), the shared
        weights whose gradient it computes as one matmul, the nodes that
        read a parameter another node reads too (each casts the master
        where it uses it; their gradients are added in float32), and what its
        nodes' ops declared a launch counts (``OpDef.launch_counts``: the
        sparse-expert, attention, linear-attention and convolution layers'
        counters, docs/observability.md), summed while the trace lowered
        them."""
        if self._scoped_nodes:
            _tm.counter("executor.scoped_nodes").inc(self._scoped_nodes)
        if self._kept_residual_nodes:
            _tm.counter("executor.kept_residual_nodes").inc(
                self._kept_residual_nodes)
        weights = self._shared_fc_plan()[2]
        if weights:
            _tm.counter("executor.stacked_wgrad").inc(weights)
        if self._shared_reads is None:
            self._shared_reads = self.graph.shared_weight_reads(
                set(self._wrt_names))
        if self._shared_reads:
            _tm.counter("executor.shared_weight_reads").inc(
                self._shared_reads)
        if self._launch_counts is None:
            self._launch_counts = self._declared_from_shapes()
        for name, n in self._launch_counts.items():
            if n:  # a counter only where the graph holds such a layer
                _tm.counter(name).inc(n)  # graftlint: allow=telemetry-catalog(forwards the literal names the graph's ops list as OpDef.launch_instruments; tests/test_launch_counts.py holds every one to docs/observability.md)

    @staticmethod
    def _note_train_memory(program, grad_bytes, state_bytes):
        """The memory ledger's gauges, at the launch of a train program:
        what ``program``'s executable needs of a device (``aot.AOTProgram.
        memory``, read once where it was resolved), the gradients it
        publishes and the training state it carries, set together where it
        is the heaviest train program launched since the last
        ``telemetry.reset()``, so that the six describe ONE program
        however many a step switches between; a reset zeroes them and the
        next launch sets them again. A few host reads a launch, no device
        call. A program with no analysis (an interpreted one, a backend
        that gives none) sets nothing."""
        mem = getattr(program, "memory", None)
        if mem is None:
            return
        gauges = (_tm.gauge("executor.program_argument_bytes"),
                  _tm.gauge("executor.program_kept_output_bytes"),
                  _tm.gauge("executor.program_temp_bytes"),
                  _tm.gauge("executor.program_code_bytes"))
        if mem.footprint <= sum(g.value for g in gauges):
            return
        with _HEAVIEST_LOCK:
            if mem.footprint <= sum(g.value for g in gauges):
                return
            for g, v in zip(gauges, (mem.argument, mem.kept_output,
                                     mem.temp, mem.code)):
                g.set(v)
            _tm.gauge("executor.published_grad_bytes").set(grad_bytes)
            _tm.gauge("executor.train_state_bytes").set(state_bytes)

    def _make_grad_core(self):
        """Shared fwd+bwd tracing core used by both the plain train_step
        program and the fused train_update program, so loss construction /
        head-grad conventions / add-req accumulation can never diverge."""
        import jax
        import jax.numpy as jnp

        graph = self.graph
        batched, order, _weights = self._shared_fc_plan()
        fc_batches = _FCBatches(batched, order) if batched else None
        wrt_idx = [graph._arg_index[n] for n in self._wrt_names]
        wrt_names = tuple(self._wrt_names)
        add_names = [n for n in self._wrt_names if self.grad_req[n] == "add"]
        # backward() without out_grads: loss-layer heads drive the backward
        # (their custom_vjp ignores the head grad, so ones is a formality);
        # non-loss heads contribute ZERO — the reference executor doesn't
        # inject gradients for extra outputs like Group(loss, features)
        head_is_loss = _head_loss_flags(graph)
        if not any(head_is_loss):
            # no loss head at all: an out_grads-less backward would be all
            # zeros; surface the misuse instead (reference executor errors
            # when a required head gradient is missing)
            head_is_loss = None

        def core(arg_vals, aux_vals, rng, head_grads, prev_grads):
            key = _fold_rng(rng)

            def loss_fn(wrt_vals):
                full = list(arg_vals)
                for i, v in zip(wrt_idx, wrt_vals):
                    full[i] = v
                outs, aux_upd = graph.evaluate(full, aux_vals, key, True,
                                               fc_batches=fc_batches)
                self._scoped_nodes = graph.scoped_nodes
                self._kept_residual_nodes = graph.kept_residual_nodes
                self._launch_counts = graph.launch_counts
                total = None
                for j, o in enumerate(outs):
                    if not jnp.issubdtype(o.dtype, jnp.floating):
                        continue
                    if head_grads is not None:
                        hg = head_grads[j]
                    elif head_is_loss is None:
                        raise MXNetError(
                            "backward() without out_grads requires a loss "
                            "output (SoftmaxOutput/MakeLoss/...); pass "
                            "explicit head gradients for plain outputs"
                        )
                    elif head_is_loss[j]:
                        hg = jnp.ones_like(o)
                    else:
                        continue  # no implicit gradient for non-loss heads
                    t = jnp.sum(o.astype(jnp.float32) * hg.astype(jnp.float32))
                    total = t if total is None else total + t
                if total is None:
                    total = jnp.zeros((), jnp.float32)
                return total, (outs, aux_upd)

            wrt_vals = [arg_vals[i] for i in wrt_idx]
            grads, (outs, aux_upd) = jax.grad(loss_fn, has_aux=True)(wrt_vals)
            grad_map = dict(zip(wrt_names, grads))
            with _phase("accumulate"):
                for n in add_names:
                    grad_map[n] = grad_map[n] + prev_grads[n]
            return outs, aux_upd, grad_map

        return core

    # ------------------------------------------------------------------
    def _bind_inputs(self, kwargs, what):
        """Validate + write new input values into arg_dict (shared by
        forward and partial_forward so validation/sharding can't diverge)."""
        import jax

        for name, arr in kwargs.items():
            if name not in self.arg_dict:
                raise MXNetError(f"{what}: unknown argument {name!r}")
            tgt = self.arg_dict[name]
            src = arr._data if isinstance(arr, NDArray) else jax.numpy.asarray(arr)
            if tuple(src.shape) != tgt.shape:
                raise MXNetError(
                    f"{what}: shape mismatch for {name}: bound {tgt.shape}, "
                    f"got {tuple(src.shape)}"
                )
            src = src.astype(tgt.dtype)
            if name in self._in_shardings:
                src = jax.device_put(src, self._in_shardings[name])
            tgt._data = src

    def forward(self, is_train=False, **kwargs):
        """Bind new input values and schedule a forward pass (lazy)."""
        self._bind_inputs(kwargs, "forward")
        # engine write-ordering: a still-scheduled backward must land its
        # grad/aux/output writes before this newer forward supersedes them
        # (in the steady train loop update() has already consumed it)
        if getattr(self, "_bwd_scheduled", False):
            self._materialize_backward()
        self._pending = "train" if is_train else "eval"
        self._fresh = False
        self._step += 1
        # Snapshot ALL input values at call time: the lazy materialisation
        # and a later fused forward+backward compute from this base, so (a)
        # mutating a bound arg after forward() doesn't change the scheduled
        # result (engine read-ordering semantics, threaded_engine.h:93-195)
        # and (b) BatchNorm moving stats update exactly once per forward().
        self._args_in, self._args_flat_in = self._arg_vals_split()
        self._aux_in, self._aux_flat_in = self._aux_vals_split()
        self._fwd_rng = self._rng_key()
        self._fwd_rng_val = self._step
        # engine read-ordering also covers AMBIENT context: the mesh in
        # effect when forward() was CALLED governs the program (ops like
        # RingAttention bake it into their trace), not the mesh at the
        # lazy materialization
        from .parallel.mesh import current_mesh

        self._fwd_mesh = current_mesh()
        if self._monitor_callback is not None or self._naive:
            self._materialize_forward()  # NaiveEngine: synchronous dispatch
        else:
            for h in self._output_handles:
                h._set_lazy(self._materialize_forward)
        return list(self._output_handles)

    def _materialize_forward(self):
        if self._pending is None:
            return
        is_train = self._pending == "train"
        args_in = getattr(self, "_args_in", None)
        if args_in is None:
            args_in, self._args_flat_in = self._arg_vals_split()
            self._aux_in, self._aux_flat_in = self._aux_vals_split()
        aux_in = self._aux_in
        args_flat = getattr(self, "_args_flat_in", None)
        aux_flat = getattr(self, "_aux_flat_in", None)
        rng = getattr(self, "_fwd_rng", None) or self._rng_key()
        from .parallel.mesh import current_mesh, with_mesh

        mesh = getattr(self, "_fwd_mesh", current_mesh())
        if self._monitor_callback is not None:
            import jax

            packs = self._packs()
            with with_mesh(mesh):
                outs, aux_upd = self.graph.evaluate(
                    *_unpack(packs, args_in, args_flat, aux_in, aux_flat),
                    jax.random.fold_in(rng[0], int(rng[1])),
                    is_train,
                    monitor=self._monitor_callback,
                    monitor_all=getattr(self, "_monitor_all", False),
                )
            # re-pack the interpreter's full aux list (same split as the
            # jitted path)
            _, _, aux_upd, aux_flat_out = _repack(packs, aux_upd)
        else:
            with with_mesh(mesh):
                fn = self._get_jit("forward", is_train=is_train)
                outs, aux_upd, aux_flat_out, next_step = fn(
                    args_in, args_flat, aux_in, aux_flat, rng)
            self._accept_next_step(
                next_step, getattr(self, "_fwd_rng_val", self._step)
            )
        self._set_outputs(outs)
        self._set_aux(aux_upd, flat=aux_flat_out)
        self._pending = None
        self._fresh = True

    def _set_outputs(self, outs):
        for h, o in zip(self._output_handles, outs):
            h._data = o

    def _set_aux(self, aux_upd, snap=None, flat=None):
        if snap is None:
            snap = getattr(self, "_aux_in", None)
        small = self._small_state()
        packed = set(small["aux"]["names"]) if small and small["aux"] else ()
        for i, (n, v) in enumerate(zip(self.aux_names, aux_upd)):
            if n in packed:
                continue  # carried by the flat; installed below
            handle = self.aux_dict[n]
            # last-write-wins: if someone wrote to this aux between forward()
            # and materialisation (e.g. copy_params_from), keep their value —
            # the reference engine would order that write after the forward.
            if snap is not None and handle._d is not snap[i]:
                continue
            handle._data = v
        if packed and flat is not None:
            self._pack_install(small["aux"], self.aux_dict, flat)

    @property
    def outputs(self):
        if self._pending is None and not self._fresh and \
                self._output_handles and self._output_handles[0]._d is None:
            raise MXNetError("outputs accessed before any forward call")
        return list(self._output_handles)

    def backward(self, out_grads=None, is_train=True):
        """Schedule the fused forward+backward program (lazy).

        The program runs when outputs or gradients are first read. If a
        fused optimizer update (``fused_train_update``) consumes the
        schedule first, forward+backward+update all execute as ONE donated
        XLA program — the whole training iteration is a single dispatch.
        """
        if self._pending is None and not self._fresh:
            raise MXNetError("backward called before forward")
        if out_grads is not None and not isinstance(out_grads, (list, tuple)):
            out_grads = [out_grads]
        if out_grads is None:
            flags = _head_loss_flags(self.graph)
            if any(flags) and not all(flags):
                import warnings

                warnings.warn(
                    "backward() without out_grads on a Group mixing loss "
                    "and non-loss outputs: the non-loss heads contribute "
                    "ZERO gradient (pass explicit out_grads, or register "
                    "the op with is_loss=True if its backward ignores the "
                    "head gradient)",
                    stacklevel=2,
                )
        head_grads = None
        if out_grads is not None:
            head_grads = [
                g._data if isinstance(g, NDArray) else g for g in out_grads
            ]
        # capture add-req grad bases BEFORE the handles go lazy, and the
        # input snapshot NOW — a later forward() overwrites _args_in, and
        # this deferred program must compute from the batch it was
        # scheduled against
        self._bwd_prev = {
            n: self.grad_dict[n]._data
            for n in self._wrt_names
            if self.grad_req[n] == "add"
        }
        if getattr(self, "_args_in", None) is not None:
            self._bwd_args = self._args_in
            self._bwd_args_flat = getattr(self, "_args_flat_in", None)
            self._bwd_aux = self._aux_in
            self._bwd_aux_flat = getattr(self, "_aux_flat_in", None)
        else:
            self._bwd_args, self._bwd_args_flat = self._arg_vals_split()
            self._bwd_aux, self._bwd_aux_flat = self._aux_vals_split()
        self._bwd_heads = head_grads
        self._bwd_scheduled = True
        self._bwd_rng = self._rng_key()
        self._bwd_rng_val = self._step
        from .parallel.mesh import current_mesh

        self._bwd_mesh = current_mesh()
        for n in self._wrt_names:
            self.grad_dict[n]._set_lazy(self._materialize_backward)
        for h in self._output_handles:
            h._set_lazy(self._materialize_backward)

    def _materialize_backward(self):
        """Run the scheduled fwd+bwd as one jitted program (no update)."""
        if not getattr(self, "_bwd_scheduled", False):
            return
        head_grads = self._bwd_heads
        with_hg = head_grads is not None
        from .parallel.mesh import current_mesh, with_mesh

        with with_mesh(getattr(self, "_bwd_mesh", current_mesh())):
            fn = self._get_jit("train_step", with_head_grads=with_hg)
            outs, aux_upd, aux_flat_out, grad_map, grad_flat, next_step = fn(
                self._bwd_args, getattr(self, "_bwd_args_flat", None),
                self._bwd_aux, getattr(self, "_bwd_aux_flat", None),
                self._bwd_rng, head_grads, self._bwd_prev,
            )
        # it updates nothing: the auxiliary states are all it carries
        self._finish_backward(
            outs, aux_upd, aux_flat_out, grad_map, grad_flat, next_step,
            program=fn, grad_bytes=self._held_bytes()[0],
            state_bytes=self._held_bytes()[1])

    def _finish_backward(self, outs, aux, aux_flat, grads, grad_flat,
                         next_step, n_steps=1, *, program, grad_bytes,
                         state_bytes):
        """The scheduled backward ran, alone or inside a fused program of
        ``n_steps`` steps: adopt its outputs, aux states, gradients (None:
        not published) and step counter. ``program`` launched it, and
        published ``grad_bytes`` and carried ``state_bytes`` a device."""
        self._count_train_launch()
        self._note_train_memory(program, grad_bytes, state_bytes)
        self._accept_next_step(
            next_step,
            getattr(self, "_bwd_rng_val", self._step) + (n_steps - 1))
        self._bwd_scheduled = False  # only consumed on success
        self._set_outputs(outs)
        self._set_aux(aux, snap=self._bwd_aux, flat=aux_flat)
        if grads is None:
            self._mark_grads_unpublished()
        else:
            for n, g in grads.items():
                self.grad_dict[n]._data = g
            self._install_grad_flat(grad_flat)
        self._pending = None
        self._fresh = True

    def _window_stacks(self, n_steps, data_stacks):
        """``(names, values)`` of a training window's per-iteration inputs,
        cast and placed as ``_bind_inputs`` places serially-fed batches;
        raises where a window cannot run."""
        import jax

        if n_steps <= 1:
            if data_stacks:
                raise MXNetError(
                    "data_stacks requires a window (n_steps>1); a single step "
                    "trains on the bound inputs"
                )
            return (), ()
        if self._bwd_heads is not None:
            raise MXNetError(
                "a training window (n_steps>1) drives loss heads only; "
                "explicit head gradients change per step — run "
                "single-step updates instead"
            )
        if self._bwd_prev:  # non-empty ⇔ grad_req='add' accumulation
            raise MXNetError(
                "a training window requires grad_req='write' (an 'add' "
                "accumulation carried across window iterations would "
                "double-count); use single-step updates"
            )
        names = tuple(sorted(data_stacks or ()))
        vals = ()
        for nm in names:
            if nm not in self.graph._arg_index:
                raise MXNetError(
                    f"data_stacks name '{nm}' is not a bound input"
                )
            v = data_stacks[nm]
            v = v._data if isinstance(v, NDArray) else v
            tgt = self.arg_dict[nm]
            want = (n_steps,) + tuple(tgt.shape)
            if tuple(v.shape) != want:
                raise MXNetError(
                    f"data_stacks['{nm}'] shape {tuple(v.shape)} != "
                    f"(n_steps,)+bound shape {want}"
                )
            v = v.astype(np_dtype(tgt.dtype))
            sh = self._in_shardings.get(nm)
            if sh is not None:
                from jax.sharding import NamedSharding, PartitionSpec

                # the window dim is replicated: every device sees all steps
                if isinstance(sh, NamedSharding):
                    sh = NamedSharding(sh.mesh, PartitionSpec(None, *sh.spec))
                v = jax.device_put(v, sh)
            vals += (v,)
        return names, vals

    def _train_step_fn(self, key, apply_fn, upd_idx, other_idx, st_fill):
        """The traced body of one fused train step: forward + backward (the
        grad core the plain train-step program shares), the optimizer
        applied to every updated parameter, the non-finite guard, and the
        same prologue and epilogue as the other two programs."""
        import jax

        from . import profiler as _prof

        core = self._make_grad_core()
        packs = self._packs()
        packed_args = set(packs.grad_names)
        arg_index = self.graph._arg_index
        n_args = len(self.arg_names)
        update_names, state_td, guard_on = (
            key.update_names, key.state_td, key.guard_on)

        def _step(upd_vals, arg_flat, other_vals, aux_vals, aux_flat, rng,
                  heads, prev_grads, st_leaves, st_flat, hyper, guard):
            import jax.numpy as jnp

            full = [None] * n_args
            for i, v in zip(upd_idx, upd_vals):
                full[i] = v
            for i, v in zip(other_idx, other_vals):
                full[i] = v
            full, full_aux = _unpack(packs, full, arg_flat, aux_vals,
                                     aux_flat)
            with _phase("unpack"):
                st_full = _fill_packed(st_leaves, st_flat, st_fill)
            outs, aux_upd, grad_map = core(
                full, full_aux, rng, heads, prev_grads
            )
            rng_key = _fold_rng(rng)
            lr_v, wd_v, t_v = hyper[0], hyper[1], hyper[2]
            sts = jax.tree_util.tree_unflatten(state_td, st_full)
            new_params, new_states = [], []
            # one scope a parameter: a weight's update is a row of the
            # by-node table, with whatever XLA fused into it
            with _phase("update"):
                for i, nm in enumerate(update_names):
                    with jax.named_scope(_prof.param_scope(nm)):
                        prng = jax.random.fold_in(rng_key, 0x5EED + i)
                        w, s = apply_fn(
                            i, full[upd_idx[i]], grad_map[nm], sts[i],
                            lr_v[i], wd_v[i], t_v[i], prng,
                        )
                    new_params.append(w)
                    new_states.append(s)
            new_guard = guard
            if guard_on:
                with _phase("guard"):
                    # one scalar reduction per gradient, fused into the
                    # backward epilogue: any NaN/Inf element propagates to
                    # the sum (Inf-Inf=NaN included), so isfinite of the
                    # summed sums detects every non-finite gradient without
                    # an elementwise isfinite+all pass per tensor. (A
                    # finite sum overflowing f32 would skip a good batch —
                    # harmless and astronomically rare.)
                    probe = jnp.float32(0)
                    for nm in update_names:
                        probe = probe + jnp.sum(
                            grad_map[nm].astype(jnp.float32))
                    finite = jnp.isfinite(probe)
                    # a non-finite step keeps the OLD params, optimizer
                    # state AND aux (BN running stats already absorbed the
                    # poisoned batch in forward — roll them back too); the
                    # rng/step/t counters still advance, keeping the host's
                    # schedule mirrors coherent without a round trip
                    new_params = [
                        jnp.where(finite, w, full[upd_idx[i]])
                        for i, w in enumerate(new_params)
                    ]
                    new_states = [
                        jax.tree_util.tree_map(
                            lambda nw, ol: jnp.where(finite, nw, ol), ns, os_
                        )
                        for ns, os_ in zip(new_states, sts)
                    ]
                    aux_upd = [
                        jnp.where(finite, a, o)
                        for a, o in zip(aux_upd, full_aux)
                    ]
                    miss = jnp.where(finite, 0, 1).astype(guard.dtype)
                    new_guard = jnp.stack([
                        guard[0] + miss,
                        (guard[1] + miss) * miss,  # consecutive: reset on ok
                    ])
            new_leaves = jax.tree_util.tree_flatten(new_states)[0]
            with _phase("repack"):
                new_leaves, st_flat_out = _split_out(new_leaves, st_fill)
                # pack the small updated params back into their flat
                arg_flat_out = None
                if packed_args:
                    newp = dict(zip(update_names, new_params))
                    new_params = [None if nm in packed_args else w
                                  for nm, w in zip(update_names, new_params)]
                    segs = []
                    for nm in packs.grad_names:
                        w = newp.get(nm)
                        if w is None:  # packed but not updated: carry over
                            w = full[arg_index[nm]]
                        segs.append(w.astype(jnp.float32).ravel())
                    arg_flat_out = jnp.concatenate(segs)
            grad_map, grad_flat, aux_big, aux_flat_out = _repack(
                packs, aux_upd, grad_map)
            # hand the next step its hyperparams without a host round
            # trip: t advances by one for every updated param each step,
            # lr/wd only move when a scheduler fires (host re-uploads
            # then) — so the common-case next hyper is computable here
            next_hyper = hyper.at[2].add(np.float32(1))
            return _StepOut(outs, aux_big, aux_flat_out, grad_map, grad_flat,
                            new_params, arg_flat_out, new_leaves,
                            st_flat_out, next_hyper, new_guard,
                            _next_step(rng))

        return _step

    def _build_train_plan(self, key, apply_fn, state_handles):
        """The fused train program for ``key``: one donating jit over the
        step body (a K-step window of it where ``key.n_steps > 1``), wrapped
        like the executor's other programs in an ``AOTProgram``."""
        import jax

        arg_index = self.graph._arg_index
        upd_idx = [arg_index[n] for n in key.update_names]
        upd_set = set(upd_idx)
        other_idx = [i for i in range(len(self.arg_names))
                     if i not in upd_set]
        # the small optimizer-state leaves ride a pack of their own, whose
        # layout lives in the plan (leaf structure is plan-specific)
        st_pack = None
        if self._small_state() is not None:
            st_pack = self._make_pack(range(len(state_handles)),
                                      [h._data for h in state_handles])
        st_fill = tuple(
            (j, *st_pack["offs"][j]) for j in st_pack["names"]
        ) if st_pack else ()
        step = self._train_step_fn(key, apply_fn, upd_idx, other_idx,
                                   st_fill)
        if key.n_steps > 1:
            traced = _window_of(
                step, key.n_steps,
                tuple(other_idx.index(arg_index[nm])
                      for nm in key.stack_names),
                key.publish)
        elif key.publish:
            traced = step
        else:
            def step_fn(*args):
                return _unpublished(step(*args))

            traced = step_fn
        program = _aot.AOTProgram(
            jax.jit(traced, donate_argnums=_FUSED_DONATE,
                    compiler_options=_compiler_options()),
            key_digest=self._aot_digest(
                "fused",
                key._replace(state_td=repr(key.state_td), mesh=None),
                key.mesh),
            compile_counter="executor.fused_plan_compile",
            compile_span="executor.jit_build",
            donates=True, on_compile=_record_fused_hlo,
            label=self._program_label(
                "fused update" if key.n_steps == 1
                else f"fused window x{key.n_steps}"),
        )
        return _TrainPlan(
            key, program, upd_idx, other_idx, st_pack,
            grad_bytes=self._held_bytes()[0] if key.publish else 0,
            state_bytes=self._held_bytes()[1] + _share_bytes(
                [self.arg_dict[n] for n in key.update_names]
                + list(state_handles)))

    def _stage_train_args(self, plan, state_handles, lrs, wds, ts,
                          stack_vals):
        """``(call_args, hyper_host)``: the fused program's arguments from
        the scheduled backward's snapshot, the optimizer-state handles and
        the host's hyperparameters, and the latter as the host holds them."""
        import jax

        args_in = self._bwd_args
        st_pack, st_flat = plan.st_pack, None
        if st_pack is not None:
            st_flat = self._pack_gather(st_pack, state_handles)
            packed_j = set(st_pack["names"])
            state_leaves = [None if j in packed_j else h._data
                            for j, h in enumerate(state_handles)]
        else:
            state_leaves = [h._data for h in state_handles]
        # Per-step hyperparams stay device-resident: a fresh numpy argument
        # per execute costs a blocking host->device transfer and stalls the
        # pipeline. The program returns next step's hyper (t+1) donated in
        # place; the host keeps a numpy mirror and re-uploads only when the
        # wanted values diverge (lr schedule fired, optimizer/param-set
        # changed, first step).
        hyper_host = np.stack([
            np.asarray(lrs, np.float32),
            np.asarray(wds, np.float32),
            np.asarray(ts, np.float32),
        ])
        cache = getattr(self, "_hyper_dev_cache", None)
        if (
            cache is not None
            and cache[0] is not None
            and cache[1].shape == hyper_host.shape
            and np.array_equal(cache[1], hyper_host)
        ):
            hyper = cache[0]
        else:
            hyper = jax.device_put(hyper_host)
        self._hyper_dev_cache = None  # donated below; never reuse on failure
        # guard counters live on device across steps (donated in, new value
        # out); a fresh zeros buffer only on the first guarded step or after
        # a rollback reset. The same (dead) buffer rides along un-guarded
        # programs so the calling convention stays uniform.
        guard_in = self._guard_dev
        if guard_in is None:
            guard_in = self._guard_zeros()
        call_args = (
            [args_in[i] for i in plan.upd_idx],
            getattr(self, "_bwd_args_flat", None),
            [args_in[i] for i in plan.other_idx], self._bwd_aux,
            getattr(self, "_bwd_aux_flat", None), self._bwd_rng,
            self._bwd_heads, self._bwd_prev, state_leaves, st_flat, hyper,
            guard_in,
        )
        if plan.key.n_steps > 1:
            call_args += (stack_vals,)
        return call_args, hyper_host

    def _install_train_update(self, plan, out, upd_vals, state_handles):
        """Adopt the updated parameters and optimizer state a fused program
        returned, in place."""
        # the snapshots now reference donated buffers — drop them
        self._args_in = None
        self._aux_in = None
        self._bwd_args = None
        self._bwd_aux = None
        self._bwd_args_flat = None
        self._bwd_aux_flat = None
        for nm, w, old in zip(plan.key.update_names, out.params, upd_vals):
            if w is None:
                continue  # packed: carried by out.arg_flat below
            handle = self.arg_dict[nm]
            # last-write-wins: a user write between forward() and update()
            # (set_params / copy_params_from) keeps their value, matching
            # the non-fused path's snapshot guard
            if handle._d is old:
                handle._data = w
        if out.arg_flat is not None:
            self._pack_install(self._small_state()["arg"], self.arg_dict,
                               out.arg_flat)
        for handle, leaf in zip(state_handles, out.states):
            if leaf is not None:  # packed: carried by out.st_flat below
                handle._data = leaf
        if out.st_flat is not None:
            self._pack_install(plan.st_pack, state_handles, out.st_flat)

    def fused_train_update(self, update_names, apply_fn, states, lrs, wds, ts,
                           cache_token, n_steps=1, data_stacks=None,
                           publish_grads=True):
        """Forward + backward + optimizer update as ONE donated XLA program.

        The TPU answer to the reference's fused update kernels
        (``src/operator/optimizer_op.cc:18-167``) applied per-parameter by
        ``Updater``: instead of ~#params separate dispatches per step after a
        separate fwd/bwd launch, the whole training iteration is a single
        jitted computation whose parameter / optimizer-state buffers are
        donated, so XLA updates weights in place and fuses the optimizer
        arithmetic into the backward pass.

        Parameters
        ----------
        update_names : list of arg names to update (⊆ wrt names).
        apply_fn : (i, weight, grad, state, lr, wd, t, rng) -> (w', state'),
            traceable; ``i`` is the position in update_names (static).
        states : ``(treedef, handles)`` — the optimizer states aligned with
            update_names, flattened: their PyTreeDef and the NDArray leaf
            handles. The executor reads the leaves itself (small ones stay
            packed across steps, see ``_small_state``) and they are donated.
        lrs, wds, ts : per-param host scalars, passed traced (no recompile
            when an lr schedule changes them).
        cache_token : hashable identity of the optimizer config; part of the
            plan key.

        Outputs, aux states, gradient arrays, parameter arrays and the
        state handles are updated in place. Requires a scheduled
        backward(); raises MXNetError otherwise. A trace or
        compile failure raises with nothing donated; a failure once the
        program was launched raises ``aot.DonatedCallError`` and the
        executor's parameters are invalid.

        ``n_steps > 1`` runs that many consecutive train steps inside the
        SAME program (a training *window*, ``_window_of``): only the last
        iteration's outputs/gradients are published, and hyperparameters
        are frozen for the window (lr schedulers take effect at window
        granularity). ``data_stacks`` optionally maps input arg names to
        ``(n_steps,) + shape`` arrays; iteration ``i`` then trains on slice
        ``i`` (real epoch windows). The window requires plain ``write``
        gradients and no explicit head gradients.

        ``publish_grads=False`` (what ``fit`` passes on every step and
        window) drops the gradients from what the program returns: each
        then has one consumer, the update, so XLA writes no float32 copy
        of it (nor the casts and the concatenation of the small ones);
        reading ``grad_dict`` then raises MXNetError until the next
        publishing step runs. ``None`` (what ``Module.update()`` called by
        hand passes) publishes unless one set of gradients is over an
        eighth of the device's memory (``_grads_crowd_device``).
        """
        if not getattr(self, "_bwd_scheduled", False):
            raise MXNetError(
                "fused_train_update requires a pending backward(); gradients "
                "were already materialised — use the per-param update path"
            )
        if self._node2dev:
            raise MXNetError(
                "fused_train_update unsupported with ctx-group placement "
                "(multi-device graph cannot be one donated program); use the "
                "imperative update path"
            )
        from .parallel.mesh import current_mesh, with_mesh

        n_steps = int(n_steps)
        stack_names, stack_vals = self._window_stacks(n_steps, data_stacks)
        state_td, state_handles = states
        key = _TrainKey(
            tuple(update_names), cache_token,
            self._bwd_heads is not None, state_td,
            # the mesh snapshotted when backward() was scheduled governs
            # the trace (see _materialize_forward)
            getattr(self, "_bwd_mesh", current_mesh()),
            n_steps, stack_names, self._nonfinite_guard_on(),
            # the caller's word where it gave one (fit: never); update() by
            # hand gives none, and then gradients are published unless they
            # crowd the device
            (not self._grads_crowd_device() if publish_grads is None
             else bool(publish_grads)))
        plan = self._fused_plan.get(key)
        if plan is not None:
            _tm.counter("executor.fused_plan_hit").inc()
        else:
            plan = self._build_train_plan(key, apply_fn, state_handles)
            self._fused_plan[key] = plan
        with _tm.span("executor.stage_args"):
            call_args, hyper_host = self._stage_train_args(
                plan, state_handles, lrs, wds, ts, stack_vals)
        try:
            with with_mesh(key.mesh):
                out = plan.program(*call_args)
        except _aot.DonatedCallError as e:
            # the donated pack flats are consumed: invalidate them, so that
            # packed reads fail LOUDLY (the thunks raise) instead of serving
            # deleted buffers — the same terminal contract as the donated
            # per-param weights. The guard's counters restart at zero.
            small = self._small_state() or {}
            for pack in (small.get("arg"), small.get("aux"), plan.st_pack):
                if pack is not None:
                    pack["flat"] = None
            self._guard_dev = None
            raise _aot.DonatedCallError(
                "fused train step failed after buffer donation; executor "
                "parameters were invalidated — re-initialize via "
                "set_params()/load before continuing",
                memory=e.memory) from e.__cause__
        self._guard_dev = out.guard
        self._finish_backward(
            out.outs, out.aux, out.aux_flat, out.grads, out.grad_flat,
            out.step, n_steps, program=plan.program,
            grad_bytes=plan.grad_bytes, state_bytes=plan.state_bytes)
        # the window consumed n_steps rng values; advance the host counter
        # past them (forward() already took +1) so the device mirror stays
        # warm and the next forward doesn't rewind into consumed streams
        self._step += n_steps - 1
        mirror = hyper_host.copy()
        mirror[2] += n_steps
        self._hyper_dev_cache = (out.hyper, mirror)
        self._install_train_update(plan, out, call_args[0], state_handles)

    # ------------------------------------------------------------------
    def debug_str(self):
        """Human-readable execution plan (reference ``Executor::DebugStr``:
        the graph_executor prints its node schedule + memory plan; here the
        plan is the topo order handed to XLA, with placement when ctx
        groups are active)."""
        lines = [f"Symbol outputs: {', '.join(self.output_names)}",
                 f"ctx: {self._ctx}  mode: "
                 + ("interpret(NaiveEngine)" if self._naive else
                    "interpret(placed)" if self._node2dev else "jit")]
        step = 0  # op-node ordinal — the unit partial_forward(num_nodes=k) counts
        for node in self.graph.topo:
            if node.is_variable:
                kind = "aux" if node.is_aux else "var"
                lines.append(f"  [     ] {kind:8s} {node.name}")
                continue
            step += 1
            dev = self._node2dev.get(id(node))
            where = f" @{dev}" if dev is not None else ""
            lines.append(f"  [{step:4d} ] {node.op.name:20s} {node.name}{where}")
        lines.append(f"Total {step} op nodes "
                     f"({len(self.arg_names)} args, "
                     f"{len(self.aux_names)} aux)")
        return "\n".join(lines)

    def partial_forward(self, is_train=False, num_nodes=None, **kwargs):
        """Run the forward graph up to ``num_nodes`` op nodes in interpret
        mode and return that prefix's last outputs as NDArrays (reference
        ``PartialForward``, graph_executor.cc:61 — step-wise execution for
        debugging; always un-fused like the monitor path). kwargs bind new
        input values through the same binder as ``forward``. ``num_nodes``
        counts OP nodes — the ``step`` ordinals debug_str prints."""
        self._bind_inputs(kwargs, "partial_forward")
        key = _fold_rng(self._rng_key())
        if num_nodes is None:
            num_nodes = len(self.graph.topo)  # run everything, last outputs
        outs, _aux = self.graph.evaluate(
            self._arg_vals(), self._aux_vals(), key, is_train,
            limit=num_nodes,
        )
        return [NDArray(o) for o in outs]

    def set_monitor_callback(self, callback, monitor_all=False):
        """Install a per-op-output stat callback → interpret mode.

        Mirrors ``MXExecutorSetMonitorCallback``; like the reference, fused
        execution is disabled while a monitor is installed.
        """
        def _cb(name, arr):
            callback(name, NDArray(arr))

        self._monitor_callback = _cb if callback is not None else None
        self._monitor_all = bool(monitor_all) and callback is not None

    def copy_params_from(self, arg_params, aux_params=None, allow_extra_params=False):
        for name, arr in arg_params.items():
            if name in self.arg_dict:
                arr.copyto(self.arg_dict[name])
            elif not allow_extra_params:
                raise MXNetError(f"Found name {name!r} not in executor arguments")
        if aux_params:
            for name, arr in aux_params.items():
                if name in self.aux_dict:
                    arr.copyto(self.aux_dict[name])
                elif not allow_extra_params:
                    raise MXNetError(f"Found name {name!r} not in aux states")

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor with new data shapes, sharing parameters.

        Shape-matched arrays are shared outright. Mismatched entries (the
        data/label arrays of a new bucket) become LAZY placeholders that
        allocate only if actually read before being bound — the steady
        bucketing loop overwrites them with each batch, so N bucket
        executors don't pin N copies of input/grad buffers in HBM (the
        reference bounds this with the shared data_pool_,
        graph_executor.cc:813-817; under XLA the pool is PJRT's allocator,
        which can only recycle buffers we never create)."""
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args = {}
        for n, s in zip(self.arg_names, arg_shapes):
            cur = self.arg_dict[n]
            if tuple(cur.shape) == tuple(s):
                new_args[n] = cur
            else:
                if not (partial_shaping or allow_up_sizing or n in kwargs):
                    raise MXNetError(
                        f"reshape: shape of {n} changed {cur.shape}->{s}; "
                        "set partial_shaping=True"
                    )
                new_args[n] = _lazy_placeholder(s, cur.dtype)
        new_grads = {}
        for n, g in self.grad_dict.items():
            s = arg_shapes[self.arg_names.index(n)]
            new_grads[n] = g if tuple(g.shape) == tuple(s) else \
                _lazy_placeholder(s, g.dtype)
        exe = Executor(
            self._symbol,
            self._ctx,
            args=new_args,
            args_grad=new_grads or None,
            grad_req=self.grad_req,
            aux_states=self.aux_dict,
            group2ctx=self._group2ctx,
            shared_exec=self,
            in_shardings=self._in_shardings,
        )
        return exe

    # ------------------------------------------------------------------
    @staticmethod
    def simple_bind(symbol, ctx, grad_req="write", type_dict=None,
                    group2ctx=None, shared_exec=None, in_shardings=None,
                    master_params=None, _inferred_shapes=None, **kwargs):
        """Infer shapes/dtypes and allocate all arrays (reference
        ``GraphExecutor::Init`` simple_bind path, graph_executor.cc:852).

        ``master_params`` restricts the master-dtype rule below to the given
        names (the Module binder passes its parameter list so data-derived
        extra inputs like RNN begin states keep their inferred dtype); None
        applies it to every argument not explicitly typed.
        ``_inferred_shapes`` lets a caller that already ran infer_shape on
        the same kwargs (the TP-annotated executor-group bind) hand the
        result over instead of paying a second full inference.
        """
        arg_shapes, _out_shapes, aux_shapes = (
            _inferred_shapes if _inferred_shapes is not None
            else symbol.infer_shape(**kwargs)
        )
        type_dict = dict(type_dict or {})
        arg_dtypes, _out_dtypes, aux_dtypes = symbol.infer_type(**type_dict)
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        # Master-dtype rule (mixed precision, TPU-idiomatic): parameters and
        # aux states whose dtype was merely INFERRED from low-precision
        # inputs stay float32 — every layer casts them to the activation
        # dtype at use (``_castp``), so compute runs bf16 on the MXU while
        # updates/statistics accumulate in f32. Without this, bf16-data
        # graphs allocate bf16 weights that the (f32-scalar) optimizer
        # update then promotes to f32 after one step: a silent full
        # recompile and a one-step bf16 weight update. Explicitly requested
        # dtypes — a type_dict entry or Variable(dtype=...) (the __dtype__
        # attr) — are honored as given (true fp16/bf16-weight recipes).
        from .base import np_dtype

        explicit = set(type_dict)
        for n, attrs in symbol.attr_dict().items():
            if "__dtype__" in attrs:
                explicit.add(n)
        eligible = (
            (lambda n: n not in explicit) if master_params is None
            else (lambda n, mp=set(master_params): n in mp and n not in explicit)
        )
        lowp = {np_dtype("float16"), np_dtype("bfloat16")}
        arg_dtypes = [
            np_dtype("float32") if eligible(n) and np_dtype(d) in lowp else d
            for n, d in zip(arg_names, arg_dtypes)
        ]
        aux_dtypes = [
            np_dtype("float32")
            if n not in explicit and np_dtype(d) in lowp else d
            for n, d in zip(aux_names, aux_dtypes)
        ]
        args = {}
        for n, s, d in zip(arg_names, arg_shapes, arg_dtypes):
            if shared_exec is not None and n in shared_exec.arg_dict and \
                    tuple(shared_exec.arg_dict[n].shape) == tuple(s):
                args[n] = shared_exec.arg_dict[n]
            else:
                args[n] = _lazy_placeholder(s, d, ctx)
        grad_req_d = (
            {n: grad_req for n in arg_names}
            if isinstance(grad_req, str)
            else (
                dict(zip(arg_names, grad_req))
                if isinstance(grad_req, (list, tuple))
                else {n: grad_req.get(n, "null") for n in arg_names}
            )
        )
        args_grad = {}
        for n, s, d in zip(arg_names, arg_shapes, arg_dtypes):
            if grad_req_d.get(n, "null") != "null":
                if shared_exec is not None and n in shared_exec.grad_dict and \
                        tuple(shared_exec.grad_dict[n].shape) == tuple(s):
                    args_grad[n] = shared_exec.grad_dict[n]
                else:
                    args_grad[n] = _lazy_placeholder(s, d, ctx)
        aux_states = {}
        for n, s, d in zip(aux_names, aux_shapes, aux_dtypes):
            if shared_exec is not None and n in shared_exec.aux_dict and \
                    tuple(shared_exec.aux_dict[n].shape) == tuple(s):
                aux_states[n] = shared_exec.aux_dict[n]
            elif n.endswith(("moving_var", "running_var")):
                # matches the initializer's exact heuristic (initializer.py
                # _init_default): zero variances make an un-init'd eval
                # forward amplify by 1/sqrt(eps) per BatchNorm and overflow
                # on deep nets; moving_inv_var and other aux stay zero
                aux_states[n] = nd_ones(s, ctx=ctx, dtype=d)
            else:
                aux_states[n] = nd_zeros(s, ctx=ctx, dtype=d)
        return Executor(
            symbol,
            ctx,
            args=args,
            args_grad=args_grad or None,
            grad_req=grad_req_d,
            aux_states=aux_states,
            group2ctx=group2ctx,
            shared_exec=shared_exec,
            in_shardings=in_shardings,
        )
