"""BaseModule — the abstract training-loop interface.

Reference: ``python/mxnet/module/base_module.py`` (``fit`` at :375-533,
``predict``/``score``/``iter_predict``/``forward_backward``). The epoch loop
is ported faithfully: bind → init_params → init_optimizer → per-batch
forward_backward/update/update_metric → epoch metric log + callbacks +
optional eval — because user scripts and the examples drive exactly this
surface.
"""

from __future__ import annotations

import logging
import time
from collections import deque

import numpy as np

from ..base import MXNetError
from .. import metric as metric_mod
from .. import io as io_mod
from .. import telemetry as _tm
from ..initializer import Uniform
from ..kvstore_transport import ElasticServerLost
from ..ndarray import NDArray


def _as_list(obj):
    if isinstance(obj, list):
        return obj
    return [obj]


def _fast_forward(data_iter, n):
    """Advance ``data_iter`` past ``n`` batches as cheaply as possible:
    ``iter_next()`` moves the cursor without building batch arrays where
    the iterator supports it (NDArrayIter etc.); iterators exposing only
    ``next()`` fall back to drawing and discarding. Returns the number of
    batches actually skipped (< n when the epoch is shorter)."""
    skipped = 0
    use_next = False
    with _tm.span("fit.data_wait"):
        while skipped < n:
            try:
                if use_next:
                    next(data_iter)
                elif not data_iter.iter_next():
                    break
            except NotImplementedError:
                use_next = True
                continue
            except StopIteration:
                break
            skipped += 1
    return skipped


class _StepsInFlight:
    """How far the host runs ahead of the device: observed after every
    dispatch, how many of the last <= ``RING`` dispatched steps (a window
    counts as one) have not finished (``fit.steps_in_flight``).

    A step is represented by the rng step counter its program returned: a
    scalar that is never donated and that nothing else waits for. Reading
    ``is_ready()`` blocks on nothing and dispatches nothing."""

    RING = 64

    def __init__(self):
        self._ring = deque(maxlen=self.RING)
        self._hist = _tm.histogram("fit.steps_in_flight")

    def observe(self, token):
        if token is None:  # a module that cannot name its last step
            return
        ring = self._ring
        ring.append(token)
        # one device queue retires steps in order: the oldest finish first
        while ring and ring[0].is_ready():
            ring.popleft()
        self._hist.observe(len(ring))

    def clear(self):
        self._ring.clear()


def _check_input_names(symbol, names, typename, throw):
    args = symbol.list_arguments()
    for name in names:
        if name in args:
            continue
        candidates = [arg for arg in args if not arg.endswith("_weight")
                      and not arg.endswith("_bias") and not arg.endswith("_gamma")
                      and not arg.endswith("_beta")]
        msg = (
            f"\033[91mYou created Module with Module(..., {typename}_names={names}) "
            f"but input with name '{name}' is not found in symbol.list_arguments(). "
            f"Did you mean one of:\n\t%s\033[0m" % "\n\t".join(candidates)
        )
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


class _NonfiniteGuard:
    """Escalation policy for ``MXNET_NONFINITE_GUARD`` (the detection/skip
    math lives inside the fused train step — :meth:`Executor.
    fused_train_update` — and runs with no per-batch host sync; this class
    only reads the device counters at sync points and decides what to do).

    Modes: ``skip`` counts skips (``fit.nonfinite_skip``) and keeps going;
    ``rollback`` additionally restores the last checkpoint after
    ``MXNET_NONFINITE_TOLERANCE`` consecutive skips, and raises if the
    blowup persists past a rollback; ``raise`` fails on the first skipped
    batch (a per-batch host check — debug mode, documented as the one
    guard mode that syncs).
    """

    def __init__(self, module, mode, tolerance):
        self.module = module
        self.mode = mode
        self.tolerance = max(1, int(tolerance))
        # counters persist across fit() calls on the same module; only
        # skips from THIS run may feed fit.nonfinite_skip
        try:
            self._reported = module.nonfinite_stats()[0]
        except Exception:
            self._reported = 0
        self._rolled_back = False

    @staticmethod
    def from_env(module):
        from .. import env as _env

        mode = str(_env.get("MXNET_NONFINITE_GUARD") or "").lower()
        if mode not in ("skip", "rollback", "raise"):
            return None
        if not hasattr(module, "nonfinite_stats"):
            logging.warning(
                "MXNET_NONFINITE_GUARD set but %s exposes no guard "
                "counters; updates are still guarded at the executor "
                "level where fusable, but escalation is off",
                type(module).__name__)
            return None
        return _NonfiniteGuard(module, mode,
                               _env.get("MXNET_NONFINITE_TOLERANCE"))

    def _flush(self):
        total, consec = self.module.nonfinite_stats()
        if total > self._reported:
            _tm.counter("fit.nonfinite_skip").inc(total - self._reported)
            self._reported = total
        return total, consec

    def after_batch(self):
        if self.mode != "raise":
            return
        total, consec = self._flush()
        if consec:
            raise MXNetError(
                f"non-finite gradients: update skipped ({total} total); "
                "MXNET_NONFINITE_GUARD=raise fails fast — use 'skip' or "
                "'rollback' to train through it")

    def on_epoch(self, manager, logger):
        total, consec = self._flush()
        if consec == 0:
            self._rolled_back = False  # finite progress re-arms rollback
            return
        logger.warning(
            "fit: %d consecutive non-finite-gradient skips at epoch end "
            "(%d total this run)", consec, total)
        if self.mode != "rollback" or consec < self.tolerance:
            return
        loaded = manager.load_latest() if manager is not None else None
        if self._rolled_back or loaded is None:
            raise MXNetError(
                f"{consec} consecutive non-finite-gradient skips "
                + ("persisted after a checkpoint rollback — training "
                   "cannot make progress" if self._rolled_back else
                   "and no checkpoint to roll back to (enable "
                   "fit(checkpoint=...) for rollback escalation)"))
        logger.warning(
            "fit: rolling back to checkpoint %s after %d consecutive "
            "non-finite-gradient skips", loaded.path, consec)
        manager.restore(loaded, self.module)
        self.module.reset_nonfinite_consec()
        _tm.counter("fit.nonfinite_rollback").inc()
        self._rolled_back = True


class BaseModule:
    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # --- high-level -------------------------------------------------------
    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    @property
    def input_shardings(self):
        """name → jax sharding/device for bound data+label inputs, or None
        when this module type cannot say (then fit/score skip device
        prefetch). Concrete modules override."""
        return None

    def _wrap_device_prefetch(self, data_iter):
        """Wrap ``data_iter`` in a DevicePrefetchIter staging with this
        module's input shardings; returns ``data_iter`` unchanged when
        prefetch is off, already wrapped, or unsupported here."""
        from .. import env as _env

        if not _env.get("MXNET_DEVICE_PREFETCH"):
            return data_iter
        if isinstance(data_iter, io_mod.DevicePrefetchIter):
            return data_iter
        shardings = self.input_shardings
        if shardings is None:
            return data_iter
        kwargs = {}
        cfg = _env.get("MXNET_PREFETCH_DEPTH")
        if cfg > 0:
            kwargs["depth"] = cfg  # explicit depth; 0 = auto (fit grows
            # the queue to cover dispatch_depth x K once windows engage)
        return io_mod.DevicePrefetchIter(data_iter, shardings=shardings,
                                         **kwargs)

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()
        # wrap only a full, fresh pass: with num_batch (or reset=False) the
        # staging thread would over-consume the caller's iterator past the
        # position an unwrapped score leaves it at
        staged_data = (
            self._wrap_device_prefetch(eval_data)
            if reset and num_batch is None else eval_data
        )
        try:
            actual_num_batch = self._score_loop(
                staged_data, eval_metric, num_batch, batch_end_callback, epoch)
        finally:
            if staged_data is not eval_data:
                staged_data.close()
        if score_end_callback:
            from ..model import BatchEndParam

            params = BatchEndParam(
                epoch=epoch, nbatch=actual_num_batch, eval_metric=eval_metric,
                locals=locals(),
            )
            for callback in _as_list(score_end_callback):
                callback(params)
        return eval_metric.get_name_value()

    def _score_loop(self, eval_data, eval_metric, num_batch,
                    batch_end_callback, epoch):
        actual_num_batch = 0
        batches = iter(eval_data)
        while True:
            with _tm.span("score.data_wait"):
                eval_batch = next(batches, None)
            if eval_batch is None:
                break
            nbatch = actual_num_batch
            if num_batch is not None and nbatch == num_batch:
                break
            with _tm.span("score.dispatch"):
                self.forward(eval_batch, is_train=False)
            with _tm.span("score.metric"):
                self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                from ..model import BatchEndParam

                batch_end_params = BatchEndParam(
                    epoch=epoch, nbatch=nbatch, eval_metric=eval_metric,
                    locals=locals(),
                )
                with _tm.span("score.callback"):
                    for callback in _as_list(batch_end_callback):
                        callback(batch_end_params)
            actual_num_batch += 1
        _tm.counter("score.batches").inc(actual_num_batch)
        return actual_num_batch

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [
                out[0:out.shape[0] - (pad or 0)] for out in self.get_outputs()
            ]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [
                out[0:out.shape[0] - (pad or 0)].copy()
                for out in self.get_outputs()
            ]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            import jax.numpy as jnp

            num_outputs = len(output_list[0])
            for out in output_list:
                assert len(out) == num_outputs, (
                    "Cannot merge batches, as num of outputs is not the same "
                    "in mini-batches. Maybe bucketing is used?"
                )
            output_list2 = [
                NDArray(jnp.concatenate([out[i]._data for out in output_list]))
                for i in range(num_outputs)
            ]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=Uniform(0.01), arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, checkpoint=None):
        """Train the module (reference base_module.py:375-533).

        ``checkpoint`` — a :class:`mxnet_tpu.checkpoint.CheckpointConfig`
        (or a directory path) enables crash-consistent periodic
        checkpointing AND auto-resume: if the directory already holds a
        valid checkpoint, fit resumes epoch / batch cursor / params /
        optimizer state / RNG from it (``begin_epoch``/``arg_params`` are
        superseded), so a killed job relaunched by ``tools/launch.py
        --max-restarts`` continues mid-training instead of restarting.
        ``None`` consults ``MXNET_CHECKPOINT_DIR``.

        ``fit`` reads no gradients, so where a step (or a window of them)
        runs as one fused program that program returns none: a
        ``batch_end_callback`` that reads ``grad_dict`` raises
        ``MXNetError``. To see gradients, install a ``monitor`` (the step
        then runs unfused), or drive the loop by hand: read them after
        ``backward()`` and before ``update()``, or call
        ``update(publish_grads=True)``.
        """
        assert num_epoch is not None, "please specify number of epochs"

        from .. import checkpoint as ckpt_mod
        from .. import faultinject as _fi

        ckpt_cfg = ckpt_mod.CheckpointConfig.coerce(checkpoint)
        manager = None
        resumed = None
        resume_skip = 0
        if ckpt_cfg is not None:
            manager = ckpt_mod.CheckpointManager(ckpt_cfg, module=self,
                                                 logger=self.logger)
            if ckpt_cfg.resume:
                from .. import kvstore as kvs_mod

                if isinstance(kvstore, str) and "dist" in kvstore \
                        and "async" not in kvstore:
                    # the resume decision must be job-wide BEFORE bind/
                    # init_optimizer: materialize the dist kvstore now
                    # (init_optimizer accepts the instance) so rank 0's
                    # verified choice broadcasts through it instead of
                    # every rank scanning the directory independently
                    kvstore = kvs_mod.create(kvstore)
                if isinstance(kvstore, kvs_mod.KVStore):
                    manager.kvstore = kvstore
                resumed = manager.decide_resume()  # graftlint: allow=host-sync(resume decision runs once before the epoch loop — the checkpoint subtree it reaches is a deliberate cold boundary)
            if resumed is not None:
                arg_params = resumed.arg_params
                aux_params = resumed.aux_params
                force_init = True
                begin_epoch = resumed.next_epoch
                resume_skip = resumed.next_batch
                _tm.counter("checkpoint.resume").inc()
                self.logger.info(
                    "Resuming from checkpoint %s at epoch %d batch %d",
                    resumed.path, begin_epoch, resume_skip)
                if begin_epoch >= num_epoch:
                    self.logger.info(
                        "Checkpoint is already at epoch %d >= num_epoch "
                        "%d; nothing to train", begin_epoch, num_epoch)

        self.bind(
            data_shapes=train_data.provide_data,
            label_shapes=train_data.provide_label,
            for_training=True, force_rebind=force_rebind,
        )
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(
            initializer=initializer, arg_params=arg_params,
            aux_params=aux_params, allow_missing=allow_missing,
            force_init=force_init,
        )
        self.init_optimizer(
            kvstore=kvstore, optimizer=optimizer,
            optimizer_params=optimizer_params,
        )
        if manager is not None:
            manager.attach(self, kvstore=getattr(self, "_kvstore", None))
        if resumed is not None:
            manager.restore_optimizer(resumed)  # graftlint: allow=host-sync(one-shot optimizer/RNG restore before training starts — cold checkpoint boundary)
        guard = _NonfiniteGuard.from_env(self)

        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)

        from ..model import BatchEndParam

        # async pipeline: a staging thread device_puts batch N+1 (with the
        # executor's input shardings) while batch N computes — the
        # TPU-native analogue of the reference's iter_prefetcher.h double
        # buffering. The epoch loop below never reads device values: the
        # metric accumulates on device (metric.device_update via
        # update_metric) and only the epoch-end get_name_value() syncs.
        orig_train_data = train_data
        # transient data-source failures (flaky network mounts, object
        # stores) retry with exponential backoff instead of failing the
        # epoch (MXNET_IO_RETRY; telemetry io.retry.*)
        from .. import env as _env

        retries = _env.get("MXNET_IO_RETRY")
        if retries > 0 and not isinstance(train_data, io_mod.RetryingIter):
            train_data = io_mod.RetryingIter(
                train_data, max_retries=retries,
                backoff=_env.get("MXNET_IO_RETRY_BACKOFF"),
                logger=self.logger)
        if resume_skip:
            # mid-epoch resume: fast-forward past the already-trained
            # batches BEFORE the device-prefetch wrap — iter_next()
            # advances most iterators without materializing (let alone
            # device-staging) the skipped data. Exact replay for
            # deterministic iterators; see docs/robustness.md.
            resume_skip = _fast_forward(train_data, resume_skip)
            _tm.counter("checkpoint.resume_skipped_batches").inc(
                resume_skip)
        train_data = self._wrap_device_prefetch(train_data)
        # adaptive/fixed training windows (MXNET_TRAIN_WINDOW): chunks of K
        # batches dispatch as ONE fused program via Module.train_window;
        # 'auto' probes single-step batches and picks K from the measured
        # dispatch-vs-residual telemetry ratio (aot.TrainWindowScheduler).
        # None when the env is unset, the module has no train_window, or a
        # monitor is installed (monitored steps stay per-batch, unfused).
        from .. import aot as _aot

        window = _aot.TrainWindowScheduler.from_env(self, monitor)
        if window is not None and _fi.active():
            # fault injection addresses exact batch ordinals; window
            # dispatch would blur them (and a crash-at-K inside a fused
            # program is not a per-batch event)
            window = None
        if window is not None and guard is not None and \
                guard.mode == "raise":
            # raise is the fail-on-FIRST-skip debug mode: it needs the
            # per-batch check the window branch cannot make (a window
            # publishes one counter update per K steps)
            window = None
        if window is not None and guard is not None and \
                guard.mode == "rollback":
            # boundary-fence classes (docs/architecture.md): rollback
            # escalation restores checkpointed state, so its decision
            # points must see a fully drained pipeline — no window may
            # still be in flight past a boundary it could roll back over.
            # The gauge reports the capped depth so a trace reader knows
            # this is policy, not a pipelining regression.
            window.cap_depth("nonfinite-rollback")
            self.logger.info(
                "fit: dispatch depth capped at 1 "
                "(MXNET_NONFINITE_GUARD=rollback fences every window "
                "boundary)")
        if window is None:
            _tm.gauge("fit.dispatch_depth").set(1)
        # pipelined window dispatch: up to window.depth WindowBoundary
        # handles stay in flight; the host fences only on the OLDEST one
        # (fit.window_wait) before assembling the next chunk, so window
        # N+1's stack build + dispatch overlap window N's execution
        inflight = deque()
        prefetch_auto = _env.get("MXNET_PREFETCH_DEPTH") == 0
        fit_completed = False
        done = 0  # batches dispatched by the epochs before this one
        ahead = _StepsInFlight()
        try:
            for epoch in range(begin_epoch, num_epoch):
                tic = time.time()
                eval_metric.reset()
                # the first resumed epoch starts its batch numbering past
                # the fast-forwarded cursor (the underlying iterator was
                # advanced before wrapping, above)
                nbatch = resume_skip
                resume_skip = 0
                batches = iter(train_data)
                with _tm.span("fit.data_wait"):
                    pending = next(batches, None)
                while pending is not None:
                    # one whole iteration, batch-end callback included: the spans
                    # below are its children, and the profiler groups device work
                    # by its step_num (batches dispatched before it)
                    with _tm.span("fit.step", step_num=done + nbatch):
                        data_batch = pending
                        k = window.next_k() if window is not None else 1
                        if k > 1:
                            # window dispatch: the program publishes only the
                            # last iteration's outputs, so metric updates and
                            # batch callbacks move to window granularity (the
                            # same contract train_window documents for lr
                            # schedules)
                            chunk = [data_batch]
                            with _tm.span("fit.data_wait"):
                                while len(chunk) < k:
                                    nxt = next(batches, None)
                                    if nxt is None:
                                        break
                                    chunk.append(nxt)
                            if len(chunk) < k:
                                # epoch tail shorter than K: dispatch single
                                # steps — a partial window would trace (and
                                # persist) an extra fused program shape per
                                # tail size that runs once per epoch
                                for b in chunk:
                                    with _tm.span("fit.dispatch"):
                                        self.forward_backward(b)
                                        self._update_unread()
                                    ahead.observe(self._step_token())
                                    with _tm.span("fit.metric"):
                                        self.update_metric(eval_metric, b.label)
                                    nbatch += 1
                                window.observe(len(chunk))
                                pending = None  # chunk short ⇔ iterator drained
                            else:
                                if (prefetch_auto
                                        and isinstance(
                                            train_data,
                                            io_mod.DevicePrefetchIter)
                                        and train_data.depth
                                        < k * window.depth + 1):
                                    # the pipeline is only as deep as the data
                                    # already staged: cover depth windows of K
                                    # batches (+1 so the producer never idles)
                                    train_data.set_depth(k * window.depth + 1)
                                # per-window span: the merged host+device trace
                                # shows each window's dispatch/boundary work
                                # and the operative (k, depth) on its args
                                with _tm.span("fit.window", k=k,
                                              depth=window.depth,
                                              in_flight=len(inflight)):
                                    with _tm.span("fit.dispatch"):
                                        # boundary publication is LAZY: the
                                        # window's f32 gradient publish is
                                        # dead-coded; the metric below reads
                                        # only the (published) outputs
                                        boundary = self.train_window(
                                            None, batches=chunk,
                                            publish_grads=False)
                                    ahead.observe(self._step_token())
                                    if boundary is not None:
                                        inflight.append(boundary)
                                        _tm.gauge("fit.windows_in_flight").set(
                                            len(inflight))
                                    with _tm.span("fit.data_wait"):
                                        pending = next(batches, None)
                                        if pending is not None:
                                            self.prepare(pending)
                                    with _tm.span("fit.metric"):
                                        self.update_metric(eval_metric,
                                                           chunk[-1].label)
                                nbatch += len(chunk)
                                window.observe(len(chunk))
                            if batch_end_callback is not None:
                                batch_end_params = BatchEndParam(
                                    epoch=epoch, nbatch=nbatch - 1,
                                    eval_metric=eval_metric, locals=locals(),
                                )
                                with _tm.span("fit.callback"):
                                    for callback in _as_list(batch_end_callback):
                                        callback(batch_end_params)
                            if manager is not None:
                                # a boundary that checkpoints is a real fence:
                                # the save reads this window's params, which
                                # blocks on everything dispatched so far
                                manager.batch_tick(epoch, nbatch)  # graftlint: allow=host-sync(a boundary that checkpoints is a real fence by design — cold checkpoint subtree)
                            while len(inflight) >= window.depth:
                                # backpressure: fence on the OLDEST in-flight
                                # window (an execution barrier, not a d2h
                                # read) so at most `depth` windows are queued
                                # — each holds K staged batches of device
                                # memory — while the next chunk assembles
                                with _tm.span("fit.window_wait"):
                                    inflight.popleft().wait()
                                _tm.gauge("fit.windows_in_flight").set(
                                    len(inflight))
                            continue
                        if monitor is not None:
                            monitor.tic()
                        data_batch = _fi.on_train_batch(data_batch)
                        with _tm.span("fit.dispatch"):
                            self.forward_backward(data_batch)
                            try:
                                self._update_unread()
                            except ElasticServerLost as e:
                                # the elastic coordinator restarted and lost
                                # its store: re-seed it from this survivor's
                                # live params, then replay the update (the
                                # server dedupes per-round contributions, so
                                # any half-pushed keys are idempotent)
                                if not hasattr(self, "_elastic_reseed"):
                                    raise
                                self.logger.warning("fit: %s", e)
                                self._elastic_reseed()  # graftlint: allow=host-sync(coordinator-restart recovery — a one-shot re-seed of the restarted store is a deliberate cold fence)
                                self._update_unread()
                        ahead.observe(self._step_token())
                        # fetch + stage the successor while this step's results
                        # are still in flight (the device computes under the
                        # host's data work — the same overlap the reference's
                        # threaded iterators buy)
                        with _tm.span("fit.data_wait"):
                            pending = next(batches, None)
                            if pending is not None:
                                self.prepare(pending)
                        with _tm.span("fit.metric"):
                            self.update_metric(eval_metric, data_batch.label)
                        if monitor is not None:
                            monitor.toc_print()  # graftlint: allow=host-sync(installing a Monitor opts into per-batch stat fetches — debug instrument, cold by contract)
                        if batch_end_callback is not None:
                            batch_end_params = BatchEndParam(
                                epoch=epoch, nbatch=nbatch,
                                eval_metric=eval_metric, locals=locals(),
                            )
                            with _tm.span("fit.callback"):
                                for callback in _as_list(batch_end_callback):
                                    callback(batch_end_params)
                        nbatch += 1
                        if guard is not None:
                            guard.after_batch()  # 'raise' mode only (syncs)  # graftlint: allow=host-sync(guard 'raise' mode documents the per-batch sync it buys — deliberate debug boundary)
                        if manager is not None:
                            manager.batch_tick(epoch, nbatch)  # graftlint: allow=host-sync(periodic checkpoint tick — the save it may trigger is a deliberate fence, cold checkpoint subtree)
                        ekv = getattr(self, "_kvstore", None)
                        if ekv is not None and hasattr(ekv,
                                                       "membership_event"):
                            # elastic plane: a join/leave/death observed on
                            # any reply since the last fence surfaces here
                            # (polling — the push/pull hot path stays
                            # exception-free), and the fenced reshard runs
                            # BETWEEN batches, never mid-update
                            ev = ekv.membership_event()
                            if ev is not None:
                                self._elastic_reshard(ev, epoch, nbatch,  # graftlint: allow=host-sync(membership transition IS a fence: survivors block at the reshard barrier and snapshot — cold by design)
                                                      manager)
                        if window is not None:
                            window.observe(1)
                if inflight:
                    # drain the pipeline: every boundary retires before the
                    # epoch's sync points (metric read, guard escalation,
                    # epoch checkpoint) — their view must include the last
                    # window, and a rollback must never race an in-flight
                    # update
                    with _tm.span("fit.window_wait"):
                        while inflight:
                            inflight.popleft().wait()
                    _tm.gauge("fit.windows_in_flight").set(0)
                _tm.counter("fit.batches").inc(nbatch)
                done += nbatch
                _tm.counter("fit.epochs").inc()

                with _tm.span("fit.metric"):
                    epoch_values = eval_metric.get_name_value()
                for name, val in epoch_values:
                    self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
                toc = time.time()
                self.logger.info("Epoch[%d] Time cost=%.3f", epoch, (toc - tic))

                # refresh the module-level param snapshot from the executor
                # (what the reference's get_params+set_params round trip
                # achieves; with ONE SPMD executor, pushing the just-copied
                # values back is a pure no-op — two full parameter copy
                # passes per epoch dropped from the pipeline)
                with _tm.span("fit.param_sync"):
                    arg_params_, aux_params_ = self.get_params()

                # guard escalation + periodic checkpoint at the epoch
                # boundary — the one place the loop syncs anyway, so the
                # no-per-batch-host-sync invariant holds with both on
                if guard is not None:
                    guard.on_epoch(manager, self.logger)  # graftlint: allow=host-sync(epoch boundary — the one place the loop syncs anyway; guard escalation + checkpoint are cold here)
                if manager is not None:
                    manager.epoch_tick(epoch)  # graftlint: allow=host-sync(epoch-boundary checkpoint — deliberate fence, cold checkpoint subtree)

                if epoch_end_callback is not None:
                    for callback in _as_list(epoch_end_callback):
                        callback(epoch, self.symbol, arg_params_, aux_params_)

                if eval_data:
                    res = self.score(
                        eval_data, validation_metric,
                        score_end_callback=eval_end_callback,
                        batch_end_callback=eval_batch_end_callback, epoch=epoch,
                    )
                    for name, val in res:
                        self.logger.info(
                            "Epoch[%d] Validation-%s=%f", epoch, name, val)

                # after the FINAL epoch the wrapper is not reset here — that
                # would restart the staging thread and upload batches the
                # finally block immediately discards; close() + base reset
                # below leaves the same clean state
                if epoch < num_epoch - 1 or train_data is orig_train_data:
                    train_data.reset()
            fit_completed = True
        finally:
            ahead.clear()
            if manager is not None:
                # drain the async checkpoint writer: a commit handed off
                # right before fit returned (or raised) must land
                manager.finalize()
            if train_data is not orig_train_data:
                # staging thread gone; freshly reset on the success path
                # (matching unwrapped fit). On the exception path the
                # iterator is left un-reset, but — inherent to any
                # prefetcher, the reference's PrefetchingIter included —
                # it may already be up to `depth` batches past the last
                # trained one (the staged queue is discarded).
                train_data.close()
                if fit_completed:
                    orig_train_data.reset()

    # --- symbol/params interface ------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        self.init_params(
            initializer=None, arg_params=arg_params, aux_params=aux_params,
            allow_missing=allow_missing, force_init=force_init,
        )

    def save_params(self, fname):
        arg_params, aux_params = self.get_params()
        save_dict = {f"arg:{k}": v for k, v in arg_params.items()}
        save_dict.update({f"aux:{k}": v for k, v in aux_params.items()})
        from ..ndarray import save

        save(fname, save_dict)

    def load_params(self, fname):
        from ..ndarray import load

        save_dict = load(fname)
        arg_params = {}
        aux_params = {}
        for k, value in save_dict.items():
            arg_type, name = k.split(":", 1)
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise ValueError(f"Invalid param file {fname}")
        self.set_params(arg_params, aux_params)

    # --- computation ------------------------------------------------------
    def prepare(self, data_batch):
        pass

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def _update_unread(self):
        """``update()`` for the caller who knows that nobody reads this
        step's gradients: ``fit``. A module whose step is one fused program
        then leaves them out of what the program returns
        (``Module.update(publish_grads=False)``)."""
        self.update()

    def _step_token(self):
        """A device scalar that the last dispatched step's program returned,
        never donated and a few bytes long, or None: ``fit`` asks it whether
        that step has finished (``fit.steps_in_flight``)."""
        return None

    # --- binding ----------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError()

    def install_monitor(self, mon):
        raise NotImplementedError()
