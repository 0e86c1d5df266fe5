"""BucketingModule — variable-length training via per-bucket programs.

Reference: ``python/mxnet/module/bucketing_module.py:18-470`` —
``sym_gen(bucket_key)`` produces a (symbol, data_names, label_names) triple
per bucket; ``switch_bucket`` binds a child Module sharing memory with the
default bucket's executor (``shared_module``).

TPU mapping (SURVEY.md §2.5 sequence row): one jitted XLA program per bucket
key is the natural fit — the shared ``shared_module`` path shares parameter
arrays (jax arrays are refcounted, so "sharing the data pool" is free) and
the jit cache, so switching buckets after warmup is just picking an already
compiled executable.
"""

from __future__ import annotations

import logging
import warnings

from ..base import MXNetError
from ..initializer import Uniform
from .. import telemetry as _tm
from .base_module import BaseModule, _check_input_names
from .module import Module, WindowBoundary


class BucketingModule(BaseModule):
    def __init__(self, sym_gen, default_bucket_key=None, logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None):
        super().__init__(logger=logger)
        assert default_bucket_key is not None
        self._sym_gen = sym_gen
        self._default_bucket_key = default_bucket_key
        self._context = context
        self._work_load_list = work_load_list
        self._fixed_param_names = list(fixed_param_names or [])
        self._state_names = list(state_names or [])
        self._validate_sym_gen()
        self._buckets = {}
        self._curr_module = None
        self._curr_bucket_key = None
        self._params_dirty = False

    def _validate_sym_gen(self):
        """Check the sym_gen contract on the default bucket up front:
        every declared name family must resolve against the generated
        symbol's arguments — a bad generator should fail at construction,
        not at the first bucket switch mid-training."""
        symbol, data_names, label_names = \
            self._sym_gen(self._default_bucket_key)
        for names, kind, required in (
                (list(data_names or []), "data", True),
                (list(label_names or []), "label", False),
                (self._state_names, "state", True),
                (self._fixed_param_names, "fixed_param", True)):
            _check_input_names(symbol, names, kind, required)

    def _module_for(self, bucket_key):
        """A fresh (unbound) Module for one bucket key — the single place
        the per-bucket construction recipe lives."""
        symbol, data_names, label_names = self._sym_gen(bucket_key)
        return Module(
            symbol, data_names, label_names, logger=self.logger,
            context=self._context, work_load_list=self._work_load_list,
            fixed_param_names=self._fixed_param_names,
            state_names=self._state_names,
        )

    def _require(self, *, bound=False, params=False, optimizer=False,
                 grads=False):
        """State preconditions, Module-style: one place instead of a
        per-method assert chain."""
        if bound:
            assert self.binded, "call bind() first"
        if params:
            assert self.params_initialized, "call init_params() first"
        if optimizer:
            assert self.optimizer_initialized, "call init_optimizer() first"
        if grads:
            assert self.inputs_need_grad, "bind with inputs_need_grad=True"

    def _reset_bind(self):
        self.binded = False
        self._buckets = {}
        self._curr_module = None
        self._curr_bucket_key = None

    @property
    def data_names(self):
        if self.binded:
            return self._curr_module.data_names
        _, data_names, _ = self._sym_gen(self._default_bucket_key)
        return data_names

    @property
    def output_names(self):
        if self.binded:
            return self._curr_module.output_names
        symbol, _, _ = self._sym_gen(self._default_bucket_key)
        return symbol.list_outputs()

    @property
    def data_shapes(self):
        self._require(bound=True)
        return self._curr_module.data_shapes

    @property
    def label_shapes(self):
        self._require(bound=True)
        return self._curr_module.label_shapes

    @property
    def output_shapes(self):
        self._require(bound=True)
        return self._curr_module.output_shapes

    @property
    def symbol(self):
        self._require(bound=True)
        return self._curr_module.symbol

    def get_params(self):
        self._require(bound=True, params=True)
        self._curr_module._params_dirty = self._params_dirty
        params = self._curr_module.get_params()
        self._params_dirty = False
        return params

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
        self._curr_module.set_params(
            arg_params, aux_params, allow_missing=allow_missing,
            force_init=force_init,
        )
        self._params_dirty = False
        self.params_initialized = True

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        if self.params_initialized and not force_init:
            return
        self._require(bound=True)
        self._curr_module.init_params(
            initializer=initializer, arg_params=arg_params,
            aux_params=aux_params, allow_missing=allow_missing,
            force_init=force_init,
        )
        self._params_dirty = False
        self.params_initialized = True

    def get_states(self, merge_multi_context=True):
        self._require(bound=True, params=True)
        return self._curr_module.get_states(
            merge_multi_context=merge_multi_context)

    def set_states(self, states=None, value=None):
        self._require(bound=True, params=True)
        self._curr_module.set_states(states, value)

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        assert shared_module is None, "shared_module for BucketingModule is not supported"
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True

        module = self._module_for(self._default_bucket_key)
        module.bind(
            data_shapes, label_shapes, for_training, inputs_need_grad,
            force_rebind=False, shared_module=None, grad_req=grad_req,
        )
        self._curr_module = module
        self._curr_bucket_key = self._default_bucket_key
        self._buckets[self._default_bucket_key] = module

    def switch_bucket(self, bucket_key, data_shapes, label_shapes=None):
        """Bind (or reuse) the module for ``bucket_key``
        (reference bucketing_module.py:307+).

        Telemetry mirrors the ``executor.jit_compile`` invariant:
        ``bucketing.switch`` counts every change of the active bucket and
        ``bucketing.compile_on_switch`` counts switches that had to bind
        (and later compile) a NEW bucket — steady-state bucket-miss
        recompiles are a perf bug worth surfacing.
        """
        self._require(bound=True)
        if bucket_key != self._curr_bucket_key:
            _tm.counter("bucketing.switch").inc()
        if bucket_key not in self._buckets:
            _tm.counter("bucketing.compile_on_switch").inc()
            default = self._buckets[self._default_bucket_key]
            module = self._module_for(bucket_key)
            module.bind(
                data_shapes, label_shapes, self._curr_module.for_training,
                self._curr_module.inputs_need_grad, force_rebind=False,
                shared_module=default,
            )
            if self.optimizer_initialized:
                module.borrow_optimizer(default)
            self._buckets[bucket_key] = module
        self._curr_module = self._buckets[bucket_key]
        self._curr_bucket_key = bucket_key

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        self._require(bound=True, params=True)
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring.")
            return
        self._curr_module.init_optimizer(
            kvstore, optimizer, optimizer_params, force_init=force_init
        )
        for mod in self._buckets.values():
            if mod is not self._curr_module:
                mod.borrow_optimizer(self._curr_module)
        self.optimizer_initialized = True

    def compile(self, buckets=None, parallel=True):
        """Pre-compile bucket programs ahead of the data (the warmup /
        cache-population recipe for bucketed models).

        ``buckets``: iterable of ``(bucket_key, data_shapes, label_shapes)``
        to bind first (the shapes a ``switch_bucket`` for that key would
        see); None warms only the already-bound buckets. Each bucket's
        executor is then ``Executor.compile``d — in a thread pool when
        ``parallel`` (XLA compilation releases the GIL, so N buckets
        compile concurrently), which with ``MXNET_AOT_CACHE=1`` also
        populates the persistent executable cache. The active bucket is
        restored. Returns ``{bucket_key: [kinds compiled]}``.
        """
        self._require(bound=True)
        original_key = self._curr_bucket_key
        for spec in buckets or ():
            key, data_shapes, label_shapes = spec
            self.switch_bucket(key, data_shapes, label_shapes)
        self.switch_bucket(original_key, None, None)
        items = list(self._buckets.items())

        def warm(mod):
            return mod._exec_group._exec.compile()

        if parallel and len(items) > 1:
            from concurrent.futures import ThreadPoolExecutor

            import os as _os

            with ThreadPoolExecutor(
                max_workers=min(len(items), _os.cpu_count() or 1)
            ) as pool:
                compiled = list(pool.map(lambda kv: warm(kv[1]), items))
        else:
            compiled = [warm(mod) for _key, mod in items]
        return {key: kinds for (key, _mod), kinds in zip(items, compiled)}

    @property
    def input_shardings(self):
        """Input placements of the ACTIVE bucket. All buckets bind the same
        devices/mesh and the same input names (only shapes differ per
        bucket), so the current module's map is valid for every staged
        batch — this is what lets ``DevicePrefetchIter`` stage bucketed
        batches ahead exactly like ``Module.fit``'s pipeline."""
        if not self.binded:
            return None
        return self._curr_module.input_shardings

    def prepare(self, data_batch):
        """Pre-bind the batch's bucket without making it current (the
        prefetch path warms the program for batch N+1 this way) and stage
        the batch's arrays onto the device with that bucket's shardings."""
        self._require(bound=True, params=True)
        active = self._curr_bucket_key
        self.switch_bucket(data_batch.bucket_key, data_batch.provide_data,
                           data_batch.provide_label)
        self._curr_module.prepare(data_batch)
        self.switch_bucket(active, None, None)

    def train_window(self, data_batch, n_steps=1, batches=None,
                     publish_grads=True):
        """Fused K-step windows for bucketed training.

        A chunk of batches is grouped by ``bucket_key`` (stable order) and
        each group dispatches through its bucket Module's
        :meth:`Module.train_window` — one fused, donated XLA program per
        ``(bucket, group size)`` pair, all sharing parameters, optimizer
        state and the AOT cache through the ``shared_module`` machinery.
        After one pass over the bucket set the fused programs are all
        cached, so steady-state training issues ZERO compiles and zero
        per-batch host syncs: ``switch_bucket`` is a pure cache pick.

        The group containing the chunk's LAST batch dispatches last, so
        ``fit``'s window-granular ``update_metric(eval_metric,
        chunk[-1].label)`` reads the matching bucket's outputs. Returns a
        combined :class:`WindowBoundary` covering every group (its
        ``wait()`` fences the whole chunk); gradients, when published,
        are the final group's — the chunk-end values a deferred reader
        expects.
        """
        self._require(bound=True, params=True, optimizer=True)
        if batches is None:
            self.switch_bucket(data_batch.bucket_key,
                               data_batch.provide_data,
                               data_batch.provide_label)
            self._params_dirty = True
            _tm.counter("bucketing.window").inc()
            return self._curr_module.train_window(
                data_batch, n_steps=n_steps, publish_grads=publish_grads)
        if not batches:
            return None
        groups = {}
        for b in batches:
            groups.setdefault(b.bucket_key, []).append(b)
        last_key = batches[-1].bucket_key
        keys = [k for k in groups if k != last_key] + [last_key]
        total, outs, boundary = 0, [], None
        for key in keys:
            grp = groups[key]
            self.switch_bucket(key, grp[0].provide_data,
                               grp[0].provide_label)
            _tm.counter("bucketing.window").inc()
            boundary = self._curr_module.train_window(
                None, batches=grp, publish_grads=publish_grads)
            total += boundary.n_steps
            outs.extend(boundary._outs)
        self._params_dirty = True
        if len(keys) == 1:
            return boundary
        return WindowBoundary(total, outs,
                              boundary._grads if publish_grads else None)

    def forward(self, data_batch, is_train=None):
        self._require(bound=True, params=True)
        self.switch_bucket(
            data_batch.bucket_key, data_batch.provide_data,
            data_batch.provide_label,
        )
        self._curr_module.forward(data_batch, is_train=is_train)

    def backward(self, out_grads=None):
        self._require(bound=True, params=True)
        self._curr_module.backward(out_grads=out_grads)

    def update(self, publish_grads=None):
        """The current bucket's ``Module.update``, ``publish_grads`` and
        all."""
        self._require(bound=True, params=True, optimizer=True)
        self._params_dirty = True
        self._curr_module.update(publish_grads=publish_grads)

    def _update_unread(self):
        self.update(publish_grads=False)

    def get_outputs(self, merge_multi_context=True):
        self._require(bound=True, params=True)
        return self._curr_module.get_outputs(
            merge_multi_context=merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        self._require(bound=True, params=True, grads=True)
        return self._curr_module.get_input_grads(
            merge_multi_context=merge_multi_context)

    def update_metric(self, eval_metric, labels):
        self._require(bound=True, params=True)
        self._curr_module.update_metric(eval_metric, labels)

    def _step_token(self):
        return self._curr_module._step_token()

    def install_monitor(self, mon):
        self._require(bound=True)
        for mod in self._buckets.values():
            mod.install_monitor(mon)

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        # save the default bucket's symbol (reference behaviour)
        self._buckets[self._default_bucket_key]._symbol.save(f"{prefix}-symbol.json")
        param_name = f"{prefix}-{epoch:04d}.params"
        self.save_params(param_name)
        if save_optimizer_states:
            self._curr_module.save_optimizer_states(f"{prefix}-{epoch:04d}.states")
