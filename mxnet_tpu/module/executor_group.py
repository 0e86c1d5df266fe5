"""DataParallelExecutorGroup — data-parallel execution over devices.

Reference: ``python/mxnet/module/executor_group.py:82-607`` — slices each
batch across contexts (``decide_slices``), binds one executor per device with
shared memory, scatters/gathers (``_load_data``/``_merge_multi_context``) and
fans out forward/backward per executor; gradients are then reduced by the
KVStore (CommDevice P2P + ElementwiseSum).

TPU-native design: the group binds **one** executor whose arrays are sharded
over a ``jax.sharding.Mesh`` of the given contexts — batch axis sharded for
data/label, replicated for parameters. XLA's SPMD partitioner then splits
the single jitted step per device and inserts ``psum`` over ICI for the
parameter gradients, which *is* the gradient reduction the reference does by
hand afterwards. Scatter = ``jax.device_put`` with a batch sharding; gather
is free (outputs are one global array). The class keeps the reference's
surface (forward/backward/get_outputs/update_metric/slices) so Module and
BucketingModule port unchanged.
"""

from __future__ import annotations

import logging

import numpy as np

from ..base import MXNetError
from ..context import Context
from ..executor import Executor
from ..io import DataDesc
from ..ndarray import NDArray, array, zeros


def _as_desc_list(shapes):
    out = []
    for s in shapes or []:
        if isinstance(s, DataDesc):
            out.append(s)
        else:
            name, shape = s[0], s[1]
            out.append(DataDesc(name, shape, *s[2:]))
    return out


class DataParallelExecutorGroup:
    def __init__(self, symbol, contexts, workload, data_shapes, label_shapes,
                 param_names, for_training, inputs_need_grad, shared_group=None,
                 logger=logging, fixed_param_names=None, grad_req="write",
                 state_names=None, in_shardings=None):
        self.symbol = symbol
        self.contexts = list(contexts)
        # accepted for parity; SPMD shards evenly — warn when a caller asks
        # for an uneven split it will not get (reference decide_slices
        # weights shards by workload, executor_group.py:216)
        self.workload = workload
        if workload and len(set(workload)) > 1:
            import warnings

            warnings.warn(
                "non-uniform workload ignored: the SPMD executor shards "
                "the batch evenly across devices (uneven per-device "
                "workloads have no benefit on identical TPU cores)",
                stacklevel=3,
            )
        self.param_names = param_names
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.logger = logger
        self.fixed_param_names = set(fixed_param_names or [])
        self.state_names = set(state_names or [])
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.shared_group = shared_group

        self.grad_req = {}
        for name in self.arg_names:
            if name in self.param_names:
                self.grad_req[name] = (
                    "null" if name in self.fixed_param_names or not for_training
                    else (grad_req if isinstance(grad_req, str) else grad_req.get(name, "write"))
                )
            elif name in self.state_names:
                self.grad_req[name] = "null"
            else:
                # data/label inputs
                self.grad_req[name] = (
                    "write" if inputs_need_grad and for_training else "null"
                )

        self._mesh = None
        self._data_sharding = None
        self._param_sharding = None
        self._dp_size = 1
        from ..parallel import mesh as _meshmod

        # one GraftMesh binds the whole module family; precedence:
        # explicitly installed mesh (with_mesh) > MXNET_MESH environment
        # spec > the Context list (a pure-dp mesh over those devices, the
        # reference's multi-context data parallelism). Batch shards over
        # the 'dp' axis (if any); params replicate unless a __shard__
        # annotation splits them over 'tp' (parallel/tensor_parallel.py);
        # a 'pp' axis is driven by SequentialModule's GPipe engine, not
        # here.
        gm = _meshmod.current_graft()
        if gm is None and len(self.contexts) > 1:
            gm = _meshmod.GraftMesh.from_contexts(self.contexts)
        if gm is not None:
            self._mesh = gm
            self._data_sharding = gm.batch_sharding()
            self._param_sharding = gm.replicated()
            self._dp_size = gm.dp

        self.bind_exec(data_shapes, label_shapes, shared_group)

    # ------------------------------------------------------------------
    @property
    def execs(self):
        """Reference exposes per-device executors; here there is one SPMD
        executor (kept as a 1-list for scripts that poke exec_group.execs)."""
        return [self._exec]

    def bind_exec(self, data_shapes, label_shapes, shared_group=None, reshape=False):
        self.data_shapes = _as_desc_list(data_shapes)
        self.label_shapes = _as_desc_list(label_shapes) if label_shapes else []
        self.data_names = [d.name for d in self.data_shapes]
        self.label_names = [d.name for d in self.label_shapes]
        self.batch_size = self.data_shapes[0].shape[0]
        if self._mesh is not None and self.batch_size % self._dp_size != 0:
            raise MXNetError(
                f"batch size {self.batch_size} not divisible by the data-"
                f"parallel degree {self._dp_size}"
            )

        shape_kwargs = {d.name: d.shape for d in self.data_shapes}
        shape_kwargs.update({d.name: d.shape for d in self.label_shapes})
        # complete partial __shape__ hints (0 = batch) on extra input args —
        # RNN begin states etc. (the reference resolves these via nnvm's
        # 0-dim shape unification; here the binder substitutes the batch)
        attrs = self.symbol.attr_dict()
        batch_axis = DataDesc.get_batch_axis(
            getattr(self.data_shapes[0], "layout", "NCHW")
        )
        bsz = self.data_shapes[0].shape[batch_axis if batch_axis >= 0 else 0]
        from ..base import parse_shape

        for name in self.arg_names:
            if name in shape_kwargs or name in self.param_names:
                continue
            hint = attrs.get(name, {}).get("__shape__")
            if hint:
                s = parse_shape(hint)
                if s:
                    shape_kwargs[name] = tuple(
                        bsz if d == 0 else d for d in s
                    )
        type_kwargs = {d.name: d.dtype for d in self.data_shapes}
        type_kwargs.update({d.name: d.dtype for d in self.label_shapes})

        in_shardings = {}
        inferred = None
        if self._mesh is not None:
            from ..parallel.tensor_parallel import (
                collect_shard_specs,
                shard_spec_sharding,
            )

            specs = collect_shard_specs(self.symbol)
            arg_shape = {}
            if any(n in specs for n in self.param_names):
                # inference result is handed down to simple_bind so the
                # graph is walked once, not twice
                inferred = self.symbol.infer_shape(**shape_kwargs)
                arg_shape = dict(zip(self.arg_names, inferred[0]))
            for n in self.data_names + self.label_names:
                in_shardings[n] = self._data_sharding
            for n in self.arg_names:
                if n in in_shardings:
                    continue
                if n in specs and n in self.param_names:
                    in_shardings[n] = shard_spec_sharding(
                        self._mesh, specs[n], len(arg_shape[n] or ())
                    )
                else:
                    in_shardings[n] = self._param_sharding

        self._in_shardings = in_shardings
        shared_exec = shared_group._exec if shared_group is not None else None
        if shared_exec is None and reshape and \
                getattr(self, "_exec", None) is not None:
            # a reshape of a LIVE group (Module.forward on a new batch
            # shape) must keep its trained parameters/grads/aux: share the
            # old executor's arrays — simple_bind shares every
            # shape-matched entry (the params) and reallocates only the
            # shape-changed data/label buffers. Without this, a mid-epoch
            # partial batch silently reset training to zeros.
            shared_exec = self._exec
        self._exec = Executor.simple_bind(
            self.symbol,
            self.contexts[0],
            grad_req=self.grad_req,
            type_dict=type_kwargs,
            shared_exec=shared_exec,
            in_shardings=in_shardings,
            master_params=self.param_names,
            _inferred_shapes=inferred,
            **shape_kwargs,
        )
        if self._mesh is not None:
            import jax

            for n, arr in self._exec.arg_dict.items():
                arr._data = jax.device_put(arr._data, in_shardings[n])
            for n, arr in self._exec.aux_dict.items():
                arr._data = jax.device_put(arr._data, self._param_sharding)
        # reference-surface parity (decide_slices): the per-shard batch
        # ranges; partitioning degree is the mesh's dp axis, not the raw
        # context count (a (dp,tp) mesh splits the batch dp ways only)
        self.slices = _even_slices(self.batch_size, self._dp_size)

    def reshape(self, data_shapes, label_shapes):
        if (_as_desc_list(data_shapes) == self.data_shapes and
                _as_desc_list(label_shapes or []) == self.label_shapes):
            return
        self.bind_exec(data_shapes, label_shapes, self.shared_group, reshape=True)

    # ------------------------------------------------------------------
    def set_params(self, arg_params, aux_params, allow_extra=False):
        self._exec.copy_params_from(arg_params, aux_params, allow_extra_params=allow_extra)
        if self._mesh is not None:
            import jax

            for n in self.param_names:
                if n in self._exec.arg_dict:
                    self._exec.arg_dict[n]._data = jax.device_put(
                        self._exec.arg_dict[n]._data,
                        self._in_shardings.get(n, self._param_sharding),
                    )

    def get_params(self, arg_params, aux_params):
        for name in self.param_names:
            if name in self._exec.arg_dict:
                self._exec.arg_dict[name].copyto(arg_params[name]) if name in arg_params \
                    else arg_params.__setitem__(name, self._exec.arg_dict[name].copy())
        for name in self.aux_names:
            if name in aux_params:
                self._exec.aux_dict[name].copyto(aux_params[name])
            else:
                aux_params[name] = self._exec.aux_dict[name].copy()

    # ------------------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        if is_train is None:
            is_train = self.for_training
        feed = {}
        for name, arr in zip(self.data_names, data_batch.data):
            feed[name] = arr
        if self.label_shapes and data_batch.label is not None:
            for name, arr in zip(self.label_names, data_batch.label):
                feed[name] = arr
        self._exec.forward(is_train=is_train, **feed)

    def backward(self, out_grads=None):
        assert self.for_training, "re-bind with for_training=True to run backward"
        self._exec.backward(out_grads)

    def get_outputs(self, merge_multi_context=True):
        outs = self._exec.outputs
        if merge_multi_context:
            return outs
        return [[o] for o in outs]

    def get_input_grads(self, merge_multi_context=True):
        assert self.inputs_need_grad
        grads = [self._exec.grad_dict.get(n) for n in self.data_names]
        if merge_multi_context:
            return grads
        return [[g] for g in grads]

    @property
    def grad_arrays(self):
        """Per-arg gradient list-of-lists (reference layout: [arg][device]);
        None placeholder for fixed/no-grad params keeps alignment with
        param_arrays (reference _update_params skips grad_list[0] is None)."""
        return [[self._exec.grad_dict.get(n)] for n in self.param_names
                if n in self._exec.arg_dict]

    @property
    def param_arrays(self):
        return [[self._exec.arg_dict[n]] for n in self.param_names
                if n in self._exec.arg_dict]

    @property
    def aux_arrays(self):
        return [[self._exec.aux_dict[n]] for n in self.aux_names]

    def update_metric(self, eval_metric, labels):
        # prefer on-device accumulation (no per-batch asnumpy sync); metrics
        # without a device formula fall back to numpy inside device_update
        dev = getattr(eval_metric, "device_update", None)
        if dev is not None:
            dev(labels, self.get_outputs())
        else:
            eval_metric.update(labels, self.get_outputs())

    def install_monitor(self, mon):
        mon.install(self._exec)

    # ------------------------------------------------------------------
    def has_pending_backward(self):
        return getattr(self._exec, "_bwd_scheduled", False)

    def update_fused(self, optimizer, updater, n_steps=1, data_stacks=None,
                     publish_grads=None):
        """Apply the optimizer inside the executor's jitted train step.

        TPU replacement for the reference's per-parameter ``Updater`` loop
        over fused update kernels (``src/operator/optimizer_op.cc:18-167``):
        forward, backward and every parameter/optimizer-state update execute
        as one donated XLA program (see ``Executor.fused_train_update``).
        Optimizer state stays in ``updater.states`` as the same NDArray
        pytrees the imperative path uses, so state save/load and fallback to
        that path remain coherent.
        """
        import jax

        exe = self._exec
        opt_token = _optimizer_token(optimizer)
        host = getattr(self, "_fused_host", None)
        if host is not None and any(
            updater.states.get(i) is not obj
            for i, obj in zip(host["keys"], host["state_objs"])
        ):
            host = None  # set_states/load replaced the state pytrees
        if (
            host is None
            or host["ids"] != (id(exe), id(optimizer), id(updater))
            or host["token"] != opt_token
        ):
            # one-time structure build: which params update, their optimizer
            # states as a flat NDArray-leaf list (the per-step loop below is
            # on the training hot path — at hundreds of parameters, pytree
            # walks and per-param bookkeeping each step cost milliseconds
            # of dispatch that the device then idles through)
            keys, names, nd_states = [], [], []
            for i, n in enumerate(self.param_names):
                if (
                    n not in exe.arg_dict
                    or exe.grad_req.get(n, "null") == "null"
                ):
                    continue
                w = exe.arg_dict[n]
                if i not in updater.states:
                    st = optimizer.create_state(i, w)
                    # co-locate state with the weight (sharding-aware) so the
                    # donated jit inputs alias without per-step resharding
                    st = _map_state(
                        st,
                        lambda nd: NDArray(
                            jax.device_put(nd._data, w._data.sharding)
                        ),
                    )
                    updater.states[i] = st
                keys.append(i)
                names.append(n)
                nd_states.append(updater.states[i])
            nd_leaves, state_td = jax.tree_util.tree_flatten(
                [_map_state(st, lambda nd: nd) for st in nd_states],
                is_leaf=lambda x: isinstance(x, NDArray),
            )

            def apply_fn(i, wv, gv, sv, lr, wd, t, rng):
                return optimizer.jax_apply(wv, gv, sv, lr, wd, t, rng)

            host = {
                "ids": (id(exe), id(optimizer), id(updater)),
                "token": opt_token,
                "keys": keys,
                "names": names,
                "nd_leaves": nd_leaves,
                "state_td": state_td,
                "apply_fn": apply_fn,
                # strong refs: identity comparison against live objects is
                # sound; an id()-only stamp could false-match on address
                # reuse after a state container is freed
                "state_objs": [updater.states[i] for i in keys],
            }
            self._fused_host = host
        keys = host["keys"]
        names = host["names"]
        nd_leaves = host["nd_leaves"]
        # lr/wd/t are the FIRST step's values (the program advances t
        # on-device each iteration; lr/wd stay frozen for the window), so
        # read them after one count advance, then land the host count on
        # the window-end value
        for i in keys:
            optimizer._update_count(i)
        iuc = optimizer._index_update_count
        lrs = [optimizer._get_lr(i) for i in keys]
        wds = [optimizer._get_wd(i) for i in keys]
        ts = [iuc[i] for i in keys]
        for _ in range(n_steps - 1):
            for i in keys:
                optimizer._update_count(i)

        try:
            # the executor extracts leaf values itself so small state
            # leaves can stay packed across steps (reading nd._data here
            # would materialize their lazy slices every step)
            exe.fused_train_update(
                names, host["apply_fn"], (host["state_td"], nd_leaves),
                lrs, wds, ts, cache_token=opt_token,
                n_steps=n_steps, data_stacks=data_stacks,
                publish_grads=publish_grads,
            )
        except Exception:
            # roll back the update counts so a retried/fallback update sees
            # the right t and lr schedule. The executor says which failure
            # this was: a trace or compile failure donated nothing and can
            # be retried; aot.DonatedCallError is terminal
            for i in keys:
                optimizer._index_update_count[i] -= n_steps
            optimizer.num_update = max(
                [optimizer.begin_num_update]
                + list(optimizer._index_update_count.values())
            )
            raise


def _optimizer_token(optimizer):
    """Hashable identity of everything an optimizer's jax_apply bakes into
    the trace (hyperparams are trace constants except lr/wd/t); value-based
    so a new or mutated optimizer never reuses a stale compiled program."""
    # lr/wd/t are traced inputs; the count/schedule bookkeeping mutates
    # every step and must not key the cache
    mutable = {"lr", "wd", "num_update", "begin_num_update"}
    static = {
        k: v for k, v in sorted(vars(optimizer).items())
        if k not in mutable and isinstance(v, (int, float, bool, str, type(None)))
    }
    return (type(optimizer).__name__,) + tuple(static.items())


def _map_state(st, f):
    """Map a leaf function over an optimizer-state pytree (None/tuple/NDArray)."""
    if st is None:
        return None
    if isinstance(st, (list, tuple)):
        return tuple(_map_state(x, f) for x in st)
    return f(st)


def _even_slices(batch_size, num):
    step = batch_size // num
    return [slice(i * step, (i + 1) * step) for i in range(num)]
