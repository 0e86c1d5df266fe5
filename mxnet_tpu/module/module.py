"""Module — the standard intermediate-level training module.

Reference: ``python/mxnet/module/module.py:22-726`` — bind creates a
``DataParallelExecutorGroup``, ``init_optimizer`` decides
``update_on_kvstore`` (+ distributed epoch-size adjustment), ``update()``
pushes/pulls through the kvstore, checkpoints save params + optimizer states.

Differences forced by the TPU design are internal only: the executor group
is one SPMD executor (see executor_group.py), so `update()`'s kvstore push
receives already-psum'd gradients and the `local` kvstore path reduces to
the updater application.
"""

from __future__ import annotations

import logging
import warnings

from .. import context as ctx_mod
from .. import optimizer as opt
from .. import telemetry as _tm
from ..base import MXNetError
from ..initializer import InitDesc, Uniform
from ..model import (
    BatchEndParam,
    _create_kvstore,
    _initialize_kvstore,
    _update_params,
    _update_params_on_kvstore,
    load_checkpoint,
    save_checkpoint,
)
from ..ndarray import zeros
from .base_module import BaseModule, _check_input_names
from .executor_group import DataParallelExecutorGroup


class WindowBoundary:
    """Deferred handle to a dispatched training window's boundary state.

    ``Module.train_window`` returns one per window so a pipelined caller
    (``Module.fit`` with dispatch depth >= 2) can keep several windows in
    flight and pay only for the boundary state it actually consumes:

    - :meth:`wait` blocks until the window's device execution has retired
      — the pipeline's backpressure fence (an execution barrier, never a
      device->host transfer).
    - :attr:`outputs` wrap the last iteration's output arrays (device
      futures captured at dispatch, so a later window overwriting the
      executor's live handles cannot race a deferred reader).
    - :meth:`grads` returns the per-parameter gradient handles when the
      window published them; a window dispatched with
      ``publish_grads=False`` raises instead (its f32 gradient
      publication was dead-coded out of the program).

    Boundary consumers that touch none of these (Speedometer's
    nonblocking reads, counters-only callbacks) cost nothing.
    """

    __slots__ = ("n_steps", "_outs", "_grads")

    def __init__(self, n_steps, outs, grads=None):
        self.n_steps = n_steps
        self._outs = list(outs or [])
        self._grads = grads

    def wait(self):
        """Block until the window's execution retired (backpressure
        fence); returns self."""
        if self._outs:
            import jax

            jax.block_until_ready(self._outs)
        return self

    @property
    def outputs(self):
        """The window's last-iteration outputs as NDArrays."""
        from ..ndarray import NDArray

        return [NDArray(o) for o in self._outs]

    def grads(self):
        """This window's gradients (captured at dispatch), if published."""
        if self._grads is None:
            raise MXNetError(
                "this training window was dispatched with "
                "publish_grads=False; re-run with publish_grads=True to "
                "read per-window gradients")
        return dict(self._grads)


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None):
        super().__init__(logger=logger)
        self._symbol = symbol
        self._context, self._work_load_list = self._normalize_contexts(
            context, work_load_list)

        # Each name group is validated against the symbol's argument list up
        # front so a typo'd name fails at construction, not at bind.
        groups = {}
        for kind, names, required in (
                ("data", data_names, True),
                ("label", label_names, False),
                ("state", state_names, True),
                ("fixed_param", fixed_param_names, True)):
            names = [] if names is None else list(names)
            _check_input_names(symbol, names, kind, required)
            groups[kind] = names
        self._data_names = groups["data"]
        self._label_names = groups["label"]
        self._state_names = groups["state"]
        self._fixed_param_names = groups["fixed_param"]

        # Everything the symbol takes that is not fed per-batch is a learnable
        # parameter owned by this module.
        fed = set(self._data_names) | set(self._label_names) | set(self._state_names)
        self._param_names = [n for n in symbol.list_arguments() if n not in fed]
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()

        # Lifecycle state, all unset until bind/init_params/init_optimizer.
        self._arg_params = self._aux_params = None
        self._params_dirty = False
        self._optimizer = self._kvstore = self._updater = None
        self._update_on_kvstore = self._preload_opt_states = None
        self._grad_req = self._exec_group = None
        self._data_shapes = self._label_shapes = None

    def _require(self, *, bound=False, params=False, optimizer=False, msg=None):
        """Guard for lifecycle preconditions (bind → init_params → init_optimizer)."""
        if bound and not self.binded:
            raise AssertionError(msg or "Module is not bound; call bind() first")
        if params and not self.params_initialized:
            raise AssertionError(msg or "parameters are not initialized; call init_params()")
        if optimizer and not self.optimizer_initialized:
            raise AssertionError(msg or "optimizer is not initialized; call init_optimizer()")

    @staticmethod
    def _normalize_contexts(context, work_load_list):
        """Resolve the ``context`` / ``work_load_list`` pair to parallel lists."""
        if context is None:
            context = ctx_mod.cpu()
        ctxs = [context] if isinstance(context, ctx_mod.Context) else list(context)
        if work_load_list is None:
            work_load_list = [1] * len(ctxs)
        if len(work_load_list) != len(ctxs):
            raise ValueError(
                f"work_load_list has {len(work_load_list)} entries for {len(ctxs)} contexts")
        return ctxs, list(work_load_list)

    # ------------------------------------------------------------------
    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params, mod._aux_params = args, auxs
        mod.params_initialized = True
        if load_optimizer_states:
            # deferred: states can only be applied once an optimizer exists
            mod._preload_opt_states = f"{prefix}-{epoch:04d}.states"
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """Save symbol/params(/optimizer state) under ``prefix``. Every
        file commits atomically (write-to-temp + fsync + rename) so a
        crash mid-save never leaves a torn file. For crash-consistent
        periodic checkpointing WITH auto-resume, prefer
        ``fit(checkpoint=CheckpointConfig(dir))``."""
        from ..checkpoint import atomic_path

        with atomic_path(f"{prefix}-symbol.json") as tmp:
            self._symbol.save(tmp)
        param_name = f"{prefix}-{epoch:04d}.params"
        with atomic_path(param_name) as tmp:
            self.save_params(tmp)
        logging.info("Saved checkpoint to \"%s\"", param_name)
        if save_optimizer_states:
            state_name = f"{prefix}-{epoch:04d}.states"
            with atomic_path(state_name) as tmp:
                self.save_optimizer_states(tmp)
            logging.info("Saved optimizer state to \"%s\"", state_name)

    # ------------------------------------------------------------------
    def _reset_bind(self):
        self.binded = False
        self._exec_group = self._data_shapes = self._label_shapes = None

    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        self._require(bound=True)
        return self._data_shapes

    @property
    def label_shapes(self):
        self._require(bound=True)
        return self._label_shapes

    @property
    def output_shapes(self):
        self._require(bound=True)
        shape_kwargs = {d.name: d.shape for d in self._data_shapes}
        shape_kwargs.update({d.name: d.shape for d in self._label_shapes or []})
        _args, outs, _aux = self._symbol.infer_shape(**shape_kwargs)
        return list(zip(self._output_names, outs))

    # ------------------------------------------------------------------
    def get_params(self):
        self._require(bound=True, params=True)
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    @_tm.span("module.init_params")
    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        if self.params_initialized and not force_init:
            warnings.warn(
                "Parameters already initialized and force_init=False. "
                "init_params call ignored.", stacklevel=2,
            )
            return
        self._require(bound=True, msg="call bind before initializing the parameters")

        def _impl(name, arr, cache):
            # preference order: user-supplied value > initializer > error
            supplied = None if cache is None else cache.get(name)
            if supplied is not None:
                if supplied is not arr:
                    supplied.copyto(arr)
                return
            if cache is not None and not allow_missing:
                raise RuntimeError(f"{name} is not presented")
            if initializer is not None:
                initializer(name, arr)

        attrs = self._symbol.attr_dict()
        for name, arr in sorted(self._exec_group._exec.arg_dict.items()):
            if name not in self._param_names:
                continue
            desc = InitDesc(name, attrs.get(name, None))
            _impl(desc, arr, arg_params)
        for name, arr in sorted(self._exec_group._exec.aux_dict.items()):
            desc = InitDesc(name, attrs.get(name, None))
            _impl(desc, arr, aux_params)

        self.params_initialized, self._params_dirty = True, False
        self._arg_params = {
            n: self._exec_group._exec.arg_dict[n].copy() for n in self._param_names
            if n in self._exec_group._exec.arg_dict
        }
        self._aux_params = {
            n: arr.copy() for n, arr in self._exec_group._exec.aux_dict.items()
        }

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        if not allow_missing:
            self.init_params(
                initializer=None, arg_params=arg_params, aux_params=aux_params,
                allow_missing=allow_missing, force_init=force_init,
            )
            return
        if self.params_initialized and not force_init:
            warnings.warn(
                "Parameters already initialized and force_init=False. "
                "set_params call ignored.", stacklevel=2,
            )
            return
        self._exec_group.set_params(arg_params, aux_params, allow_extra=True)
        self._params_dirty = True
        self.params_initialized = True

    # ------------------------------------------------------------------
    @_tm.span("module.bind")
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return

        if inputs_need_grad and not for_training:
            raise ValueError("inputs_need_grad requires for_training=True")
        self.binded, self.for_training = True, for_training
        self.inputs_need_grad, self._grad_req = inputs_need_grad, grad_req

        shared_group = None
        if shared_module is not None:
            assert isinstance(shared_module, Module) and shared_module.binded \
                and shared_module.params_initialized
            shared_group = shared_module._exec_group

        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list, data_shapes,
            label_shapes, self._param_names, for_training, inputs_need_grad,
            shared_group, logger=self.logger,
            fixed_param_names=self._fixed_param_names, grad_req=grad_req,
            state_names=self._state_names,
        )
        self._data_shapes = self._exec_group.data_shapes
        self._label_shapes = self._exec_group.label_shapes

        if shared_module is not None and shared_module.params_initialized:
            self.params_initialized = True
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
        elif self.params_initialized:
            # bind() after load(): push loaded params into executors
            self._exec_group.set_params(self._arg_params, self._aux_params)

    def reshape(self, data_shapes, label_shapes=None):
        self._require(bound=True)
        self._exec_group.reshape(data_shapes, label_shapes)
        self._data_shapes = self._exec_group.data_shapes
        self._label_shapes = self._exec_group.label_shapes

    @property
    def input_shardings(self):
        """name → placement for each bound data/label input: the executor's
        NamedSharding under a mesh, else the module's device. This is what
        DevicePrefetchIter stages against (fit/score async pipeline)."""
        if not self.binded:
            return None
        shardings = self._exec_group._in_shardings or {}
        dev = self._context[0].jax_device()
        return {
            n: shardings.get(n) if shardings.get(n) is not None else dev
            for n in self._data_names + self._label_names
        }

    def prepare(self, data_batch):
        """Stage a not-yet-consumed batch's arrays into device memory with
        the bound input shardings (async; a no-op for batches a
        DevicePrefetchIter already staged)."""
        if not self.binded or getattr(data_batch, "staged", False):
            return
        import jax

        shardings = self.input_shardings
        for names, arrs in ((self._data_names, data_batch.data or []),
                            (self._label_names, data_batch.label or [])):
            for name, arr in zip(names, arrs):
                from ..ndarray import NDArray as _ND

                if isinstance(arr, _ND) and arr._lazy is None:
                    arr._data = jax.device_put(arr._data, shardings[name])
        data_batch.staged = True

    def compile(self, kinds=None):
        """AOT-compile the bound executor's programs without running them
        (``Executor.compile``): warm starts for deployments, and — with
        ``MXNET_AOT_CACHE=1`` — a populated on-disk executable cache that
        later processes bind against with zero XLA compiles."""
        self._require(bound=True)
        return self._exec_group._exec.compile(kinds)

    # ------------------------------------------------------------------
    @_tm.span("module.init_optimizer")
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        self._require(bound=True, params=True)
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return

        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._arg_params
        )
        batch_size = self._exec_group.batch_size
        if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        if isinstance(optimizer, str):
            # one SPMD executor ⇒ one arg array per param, so updater keys
            # are plain param indices in both update paths (the reference's
            # i*num_device+k numbering collapses to i with num_device=1)
            idx2name = dict(enumerate(self._exec_group.param_names))
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(
                optimizer, sym=self.symbol, param_idx2name=idx2name,
                **optimizer_params,
            )
        else:
            assert isinstance(optimizer, opt.Optimizer)
            if optimizer.rescale_grad != rescale_grad:
                warnings.warn(
                    f"Optimizer created manually outside Module but "
                    f"rescale_grad is not normalized to 1.0/batch_size/"
                    f"num_workers ({optimizer.rescale_grad} vs. {rescale_grad}). "
                    "Is this intended?", stacklevel=2,
                )

        self._optimizer, self._kvstore = optimizer, kvstore
        self._update_on_kvstore, self._updater = update_on_kvstore, None

        if kvstore:
            _initialize_kvstore(
                kvstore=kvstore, param_arrays=self._exec_group.param_arrays,
                arg_params=self._arg_params,
                param_names=self._exec_group.param_names,
                update_on_kvstore=update_on_kvstore,
            )
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:  # updates applied locally, store (if any) only aggregates
            self._updater = opt.get_updater(optimizer)
        if kvstore is not None and hasattr(kvstore, "membership_event"):
            # elastic plane: remember the dp degree rescale_grad was
            # normalized for, so a fenced reshard can re-normalize
            self._elastic_rescale_workers = kvstore.num_workers
        self.optimizer_initialized = True

        if self._preload_opt_states:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None  # only forget after a successful load

    def borrow_optimizer(self, shared_module):
        """Share another module's optimizer (reference borrow_optimizer,
        used by BucketingModule so all buckets update through one state)."""
        shared_module._require(optimizer=True)
        for attr in ("_optimizer", "_kvstore", "_update_on_kvstore", "_updater"):
            setattr(self, attr, getattr(shared_module, attr))
        self.optimizer_initialized = True

    # ------------------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        self._require(bound=True, params=True)
        curr_data_shapes = tuple(i.shape for i in self._data_shapes)
        new_data_shapes = tuple(i.shape for i in data_batch.data)
        if curr_data_shapes != new_data_shapes:
            if hasattr(data_batch, "provide_data") and data_batch.provide_data:
                new_dshape = data_batch.provide_data
            else:
                new_dshape = [
                    (i.name, shape) for i, shape in
                    zip(self._data_shapes, new_data_shapes)
                ]
            if hasattr(data_batch, "provide_label") and data_batch.provide_label:
                new_lshape = data_batch.provide_label
            elif data_batch.label:
                new_lshape = [
                    (i.name, j.shape) for i, j in
                    zip(self._label_shapes, data_batch.label)
                ]
            else:
                new_lshape = None
            self.reshape(new_dshape, new_lshape)
        self._exec_group.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        self._require(bound=True, params=True)
        self._exec_group.backward(out_grads=out_grads)

    def update(self, publish_grads=None):
        """Apply the optimizer to the gradients of the last backward.

        Where the step runs as one fused program, ``publish_grads`` says
        whether that program returns its gradients for a later read of
        ``grad_dict``: True or False is honoured; None, for a caller who
        cannot know (a hand-written loop), publishes them unless one set
        is over an eighth of the device's memory
        (``Executor._grads_crowd_device``). ``fit`` knows, and says False
        on every step (``_update_unread``): a read of ``grad_dict`` after
        one of its steps raises. The unfused path (a monitor installed, an
        optimizer that cannot be traced) always leaves them readable."""
        self._require(bound=True, params=True, optimizer=True)
        self._params_dirty = True
        if self._fusable_update():
            updater = (
                self._kvstore._updater if self._update_on_kvstore
                else self._updater
            )
            self._exec_group.update_fused(self._optimizer, updater,
                                          publish_grads=publish_grads)
            self._sync_kvstore_after_fused()
            return
        if self._nonfinite_skip_imperative():
            return  # guard tripped: update suppressed, counters advanced
        if self._update_on_kvstore:
            _update_params_on_kvstore(
                self._exec_group.param_arrays, self._exec_group.grad_arrays,
                self._kvstore, self._exec_group.param_names,
            )
        else:
            # one SPMD executor ⇒ arg/grad lists have length 1, so updater
            # keys are param indices (num_device=1 regardless of contexts)
            _update_params(
                self._exec_group.param_arrays, self._exec_group.grad_arrays,
                updater=self._updater, num_device=1,
                kvstore=self._kvstore, param_names=self._exec_group.param_names,
            )

    def _update_unread(self):
        self.update(publish_grads=False)

    def train_window(self, data_batch, n_steps=1, batches=None,
                     publish_grads=True):
        """Run ``n_steps`` full train steps (forward+backward+update) as ONE
        XLA program — a TPU-native *training window*.

        The reference dispatches one engine push per op per step; this
        module already fuses a whole step into one donated program, and a
        window goes one further: ``lax.fori_loop`` advances parameters,
        optimizer state, BatchNorm statistics and the rng counter on-device
        across iterations, so K steps cost one host dispatch — where the
        per-execute host cost is what bounds the loop, the window divides
        it by K.

        ``data_batch`` alone trains every iteration on that batch (the
        reference's ``--benchmark 1`` synthetic methodology). ``batches``
        (a list of DataBatch, overrides ``n_steps``) stacks the inputs on
        device and trains iteration ``i`` on ``batches[i]`` — one h2d
        upload per window. lr schedules apply at window granularity; the
        last iteration's outputs/gradients are published for metrics.

        Falls back to ``n_steps`` plain step loops when the step cannot run
        as one program (monitor installed, non-traceable optimizer, dist
        kvstore, NaiveEngine...), keeping semantics identical.

        Returns a :class:`WindowBoundary` — a deferred handle a pipelined
        caller uses as its backpressure fence and (optionally) to read the
        boundary outputs/gradients. ``publish_grads=False`` elides the
        per-window f32 gradient publication from the fused program
        (``Executor.fused_train_update``); the boundary's ``grads()`` then
        raises instead of serving stale values.
        """
        self._require(bound=True, params=True, optimizer=True)
        if batches is not None:
            if not batches:
                return None  # empty window (e.g. a drained iterator chunk)
            n_steps = len(batches)
            data_batch = batches[0]
        # pending-backward is a per-step precondition the window creates
        # for itself below — gate only on the step-shape conditions here;
        # 'add' gradient accumulation across window iterations would
        # double-count, so those modules take the serial loop (documented
        # fallback, not an executor error mid-flight)
        has_add = any(
            r == "add"
            for r in self._exec_group._exec.grad_req.values()
        )
        if (n_steps <= 1 or has_add
                or not self._fusable_update(require_pending=False)):
            for i in range(max(1, n_steps)):
                b = batches[i] if batches is not None else data_batch
                self.forward_backward(b)
                # asked for, gradients are published; not asked for, the
                # step keeps update()'s own default
                self.update(publish_grads=True if publish_grads else None)
            # the serial loop leaves real values in grad_dict either way
            # (but for gradients that crowd the device, Module.update);
            # honoring publish_grads skips the per-window by-value snapshot
            # (len(_wrt_names) NDArray wraps + packed-slice materializations)
            # the pipelined fit loop would immediately discard
            return self._window_boundary(n_steps, published=publish_grads)
        data_stacks = None
        if batches is not None and n_steps > 1:
            import jax.numpy as _jnp

            from ..ndarray import NDArray as _ND

            # stack ON DEVICE in the BOUND dtype: each batch uploads once
            # (h2d), the cast fuses into the stack, and forward() below is
            # fed zero-copy slice-0 views — a host-side np.stack would pull
            # device-resident batches BACK (d2h), re-upload the whole stack
            # uncast, and then upload batch 0 a second time: the exact
            # transfer costs windows exist to amortize
            exe = self._exec_group._exec
            data_stacks = {}
            names_arrays = [
                (self._data_names, [b.data for b in batches]),
                (self._label_names if batches[0].label else [],
                 [b.label for b in batches]),
            ]
            for names, rows in names_arrays:
                for j, name in enumerate(names):
                    if name not in exe.arg_dict:
                        continue  # unused label: serial feed drops it too
                    stk = _jnp.stack(
                        [r[j]._data if isinstance(r[j], _ND)
                         else _jnp.asarray(r[j]) for r in rows]
                    )
                    data_stacks[name] = _ND(
                        stk.astype(exe.arg_dict[name].dtype)
                    )
            from ..io import DataBatch as _DataBatch

            lbl0 = [_ND(data_stacks[n]._data[0])
                    for n in self._label_names if n in data_stacks]
            data_batch = _DataBatch(
                data=[_ND(data_stacks[n]._data[0])
                      for n in self._data_names],
                label=lbl0 or None,
            )
        self.forward(data_batch, is_train=True)
        self.backward()
        self._params_dirty = True
        updater = (
            self._kvstore._updater if self._update_on_kvstore
            else self._updater
        )
        self._exec_group.update_fused(
            self._optimizer, updater, n_steps=n_steps,
            data_stacks=data_stacks, publish_grads=publish_grads,
        )
        self._sync_kvstore_after_fused()
        return self._window_boundary(n_steps, published=publish_grads)

    def _window_boundary(self, n_steps, published):
        """Capture the just-dispatched window's boundary state (output
        futures + optional gradients) as a WindowBoundary. Gradients are
        snapshotted BY VALUE: the executor's live grad_dict handles are
        overwritten (or invalidated) by the next dispatched window, and a
        deferred reader must see THIS window's values. Resolving `_data`
        here materializes packed-gradient slices — acceptable on the
        opt-in publish path only; the pipelined fit loop never publishes."""
        exe = self._exec_group._exec
        grads = None
        if published:
            from ..ndarray import NDArray as _ND

            grads = {n: _ND(exe.grad_dict[n]._data) for n in exe._wrt_names
                     if n in exe.grad_dict}
        return WindowBoundary(
            n_steps, [o._data for o in exe.outputs], grads)

    def _nonfinite_skip_imperative(self):
        """Non-finite guard for the IMPERATIVE update path (NaiveEngine,
        monitors, dist kvstores — everywhere the fused program can't run).
        The fused path folds the same check into the XLA program with no
        host sync; here the check blocks on an all-finite reduction, which
        is fine — this path already dispatches per parameter. Returns True
        when the update must be skipped."""
        from ..executor import Executor

        if not Executor._nonfinite_guard_on():
            return False
        import jax.numpy as jnp

        finite = True
        for grad_list in self._exec_group.grad_arrays:
            if grad_list[0] is None:
                continue
            for g in grad_list:
                finite = jnp.logical_and(
                    finite, jnp.all(jnp.isfinite(g._data)))
        kv = self._kvstore
        if (kv is not None and "dist" in kv.type and "async" not in kv.type
                and kv.num_workers > 1 and hasattr(kv, "_allreduce")):
            # sync-dist: the skip decision MUST be global. A rank-local
            # skip would leave this rank out of the per-key allreduce its
            # peers are blocking in (one poisoned shard → whole-job hang).
            # One extra scalar allreduce — every rank runs it every batch,
            # so the collective schedule stays symmetric — makes all ranks
            # agree: any rank's non-finite gradient skips the batch
            # everywhere (matching the fused guard's semantics, where the
            # psum'd gradient is non-finite for every rank).
            from ..ndarray import NDArray as _ND

            bad_local = jnp.where(finite, 0.0, 1.0).reshape(1)
            bad_total = kv._allreduce(_ND(bad_local))
            finite = bad_total.sum() == 0
        if bool(finite):
            gh = getattr(self, "_guard_host", None)
            if gh:
                gh[1] = 0
            return False
        total, consec = getattr(self, "_guard_host", None) or (0, 0)
        self._guard_host = [total + 1, consec + 1]
        return True

    def nonfinite_stats(self):
        """``(total_skips, consecutive_skips)`` of the non-finite-gradient
        guard, summed over the fused (device-counted) and imperative
        (host-counted) update paths. Blocks on the device counters — call
        at sync points (fit does so at epoch boundaries)."""
        et, ec = self._exec_group._exec.nonfinite_guard_stats()
        ht, hc = getattr(self, "_guard_host", None) or (0, 0)
        return (et + ht, max(ec, hc))

    def reset_nonfinite_consec(self):
        """Zero the consecutive-skip counters (rollback escalation
        recovered; totals are preserved)."""
        self._exec_group._exec.reset_nonfinite_guard(keep_total=True)
        if getattr(self, "_guard_host", None):
            self._guard_host = [self._guard_host[0], 0]

    def _sync_kvstore_after_fused(self):
        if not self._update_on_kvstore:
            return
        # keep the kvstore's master weights coherent (reference semantics:
        # push applies the update to the store, pull copies it out) —
        # zero-copy ref share with exec arrays
        from ..kvstore import _key_str

        exe = self._exec_group._exec
        for i, n in enumerate(self._exec_group.param_names):
            k = _key_str(i)
            if k in self._kvstore._store and n in exe.arg_dict:
                src = exe.arg_dict[n]
                dst = self._kvstore._store[k]
                if src._lazy is not None:
                    # packed small params: alias lazily so the store stays
                    # coherent without materializing a slice per parameter
                    # per step
                    dst._set_lazy(
                        lambda dst=dst, src=src:
                        setattr(dst, "_data", src._data))
                else:
                    dst._data = src._d

    def _elastic_reseed(self):
        """Coordinator-restart recovery: a push/pull hit a restarted
        elastic server whose in-memory store is empty. This survivor's
        executor holds the trained weights — force-init every key
        (replace semantics: the restarted rank 0's own fresh ``init`` is
        first-init-wins, so the trained copy beats it regardless of
        arrival order), then let ``fit`` re-run the interrupted update —
        the server's per-round worker dedupe makes the replay idempotent."""
        kv = self._kvstore
        _tm.counter("kvstore.elastic_reseed").inc()
        self.logger.warning(
            "elastic kvstore: coordinator restarted with an empty store; "
            "re-seeding %d parameters from live executor state",
            len(self._exec_group.param_names))
        arg_params, _ = self.get_params()
        for idx, name in enumerate(self._exec_group.param_names):
            kv._force_init(idx, arg_params[name])

    def _elastic_reshard(self, event, epoch, nbatch, manager=None):
        """The fenced membership transition ``fit`` runs when the elastic
        kvstore reports an epoch change (worker join/leave/death): meet
        every survivor at the coordinator's fence, agree on the consensus
        cursor (min over survivors' positions), re-normalize
        ``rescale_grad`` to the new dp degree (rank 0's optimizer object
        IS the server updater's closure target, so the mutation takes
        effect server-side), and snapshot via the async checkpoint writer
        so the new topology has a resume point. Training then continues —
        each survivor keeps consuming its own shard; the recorded cursor
        positions any later restart."""
        kv = self._kvstore
        self.logger.warning(
            "elastic kvstore: %s; entering reshard fence at "
            "epoch %d batch %d", event, epoch, nbatch)
        with _tm.span("kvstore.elastic_reshard"):
            mepoch, nw, ce, cb = kv.reshard_barrier(epoch, nbatch)
        prev = getattr(self, "_elastic_rescale_workers", nw) or nw
        if nw != prev and getattr(self._optimizer, "rescale_grad", None):
            self._optimizer.rescale_grad *= prev / nw
            self._elastic_rescale_workers = nw
        self.logger.warning(
            "elastic kvstore: resharded to dp=%d at membership epoch %d "
            "(consensus cursor: epoch %d batch %d)", nw, mepoch, ce, cb)
        if manager is not None and hasattr(manager, "save_local_async"):
            manager.save_local_async(ce, cb, epoch=ce, nbatch=cb)

    def _fusable_update(self, require_pending=True):
        """True when this step can run as one fwd+bwd+update XLA program.

        Requires a traceable optimizer (``jax_apply``), an in-process
        gradient reduction (no dist kvstore — cross-process push must see
        raw gradients), and a still-pending backward (if gradients were
        already materialised, e.g. under a monitor or manual grad edits,
        the imperative per-param path preserves those semantics).
        ``require_pending=False`` asks only about the step-shape conditions
        (``train_window`` schedules its own forward/backward afterwards).
        """
        from .. import env as _env

        if not _env.get("MXNET_EXEC_BULK_EXEC_TRAIN"):
            return False  # user disabled single-program training steps
        if getattr(self._optimizer, "jax_apply", None) is None:
            return False
        if self._kvstore is not None and "dist" in self._kvstore.type:
            return False
        if require_pending and not self._exec_group.has_pending_backward():
            return False
        if getattr(self._exec_group._exec, "_monitor_callback", None):
            return False  # monitored steps run unfused (interpret mode)
        exe = self._exec_group._exec
        if getattr(exe, "_node2dev", None):
            return False  # ctx-group placed graph runs per-device, unfused
        if getattr(exe, "_naive", False):
            return False  # NaiveEngine debugs un-jitted, never fused
        return True

    def get_outputs(self, merge_multi_context=True):
        self._require(bound=True, params=True)
        return self._exec_group.get_outputs(merge_multi_context=merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        self._require(bound=True, params=True)
        if not self.inputs_need_grad:
            raise AssertionError("bind was not called with inputs_need_grad=True")
        return self._exec_group.get_input_grads(merge_multi_context=merge_multi_context)

    def update_metric(self, eval_metric, labels):
        self._exec_group.update_metric(eval_metric, labels)

    def _step_token(self):
        # the rng step counter the step's program returned (executor.py)
        return self._exec_group._exec._step_dev

    # ------------------------------------------------------------------
    def _sync_params_from_devices(self):
        self._exec_group.get_params(self._arg_params, self._aux_params)
        self._params_dirty = False

    def save_optimizer_states(self, fname):
        self._require(optimizer=True)
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with open(fname, "wb") as fout:
                fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        self._require(optimizer=True)
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as f:
                self._updater.set_states(f.read())

    def install_monitor(self, mon):
        self._require(bound=True)
        self._exec_group.install_monitor(mon)
