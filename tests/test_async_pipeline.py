"""Async end-to-end training pipeline: device prefetch + device metrics.

Pins the three pieces that make ``Module.fit`` pipeline-clean (ISSUE 1):
(1) device-resident metric accumulation matches the numpy implementations;
(2) ``DevicePrefetchIter`` preserves ordering/reset/pad semantics while
staging batches off-thread; (3) the fit hot path performs NO per-batch
host sync — asserted on the framework's own telemetry counters
(``ndarray.asnumpy`` / ``ndarray.wait_to_read`` count every host-blocking
sync, ``metric.numpy_fallback`` every synchronous metric batch), which
must not scale with the number of batches — and produces the same epoch
metrics as the eager numpy path.
"""

import os
import sys

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import metric as metric_mod  # noqa: E402
from mxnet_tpu import telemetry as tm  # noqa: E402
from mxnet_tpu.ndarray import NDArray  # noqa: E402


# ---------------------------------------------------------------------------
# device-resident metrics
# ---------------------------------------------------------------------------
def _cls_batch(rng, n=32, k=10):
    p = rng.uniform(0.05, 1.0, (n, k)).astype(np.float32)
    p /= p.sum(axis=1, keepdims=True)
    l = rng.randint(0, k, (n,)).astype(np.float32)
    return [mx.nd.array(l)], [mx.nd.array(p)]


def _reg_batch(rng, n=32, shape=(1,)):
    p = rng.uniform(-1, 1, (n,) + shape).astype(np.float32)
    l = rng.uniform(-1, 1, (n,)).astype(np.float32)
    return [mx.nd.array(l)], [mx.nd.array(p)]


@pytest.mark.parametrize("name,factory,kind", [
    ("accuracy", lambda: metric_mod.Accuracy(), "cls"),
    ("top_k", lambda: metric_mod.TopKAccuracy(3), "cls"),
    ("ce", lambda: metric_mod.CrossEntropy(), "cls"),
    ("mse", lambda: metric_mod.MSE(), "reg"),
    ("mae", lambda: metric_mod.MAE(), "reg"),
    ("rmse", lambda: metric_mod.RMSE(), "reg"),
    ("loss", lambda: metric_mod.Loss(), "reg"),
])
def test_device_metric_parity(name, factory, kind):
    rng = np.random.RandomState(7)
    m_np, m_dev = factory(), factory()
    for _ in range(6):
        labels, preds = (_cls_batch(rng) if kind == "cls"
                         else _reg_batch(rng))
        m_np.update(labels, preds)
        assert m_dev.device_update(labels, preds), \
            f"{name}: device formula did not run"
    ref, got = m_np.get()[1], m_dev.get()[1]
    assert got == pytest.approx(ref, rel=1e-5, abs=1e-6), (name, ref, got)


def test_device_metric_2d_regression_parity():
    # the numpy paths reshape 1-D labels to (N,1); a (N,) pred then
    # broadcasts to (N,N) — the device formula must mirror that quirk
    rng = np.random.RandomState(1)
    for m_np, m_dev in [(metric_mod.MSE(), metric_mod.MSE()),
                        (metric_mod.MAE(), metric_mod.MAE())]:
        p = rng.uniform(-1, 1, (8,)).astype(np.float32)
        l = rng.uniform(-1, 1, (8,)).astype(np.float32)
        m_np.update([mx.nd.array(l)], [mx.nd.array(p)])
        m_dev.device_update([mx.nd.array(l)], [mx.nd.array(p)])
        assert m_dev.get()[1] == pytest.approx(m_np.get()[1], rel=1e-5)


def test_device_metric_fallback_and_reset():
    class NoDevice(metric_mod.Accuracy):
        def _device_batch(self, label, pred):
            return None

    rng = np.random.RandomState(2)
    labels, preds = _cls_batch(rng)
    m = NoDevice()
    assert m.device_update(labels, preds) is False  # numpy fallback ran
    assert m.num_inst == 32
    m2 = metric_mod.Accuracy()
    m2.device_update(labels, preds)
    m2.reset()
    assert m2._dev_sum is None and m2.num_inst == 0
    assert np.isnan(m2.get()[1])


def test_device_metric_nonblocking_and_composite():
    rng = np.random.RandomState(3)
    comp = metric_mod.create(["acc", "mse"])
    labels, preds = _cls_batch(rng)
    comp.device_update(labels, preds)
    nb = dict(comp.get_name_value_nonblocking())
    blocking = dict(comp.get_name_value())
    # after the blocking read both views agree
    assert set(nb) == {"accuracy", "mse"} == set(blocking)
    single = metric_mod.Accuracy()
    single.device_update(labels, preds)
    name, val = single.get_nonblocking()
    assert name == "accuracy" and (np.isnan(val) or 0.0 <= val <= 1.0)
    # after a blocking get() drains the accumulator, the two views agree
    # (comparing in the other order races on the accumulator's readiness)
    drained = single.get()[1]
    assert single.get_nonblocking()[1] == drained
    # composite nonblocking read must work even while children are pending
    class PendingAcc(metric_mod.Accuracy):
        def device_pending(self):
            return True

    comp2 = metric_mod.CompositeEvalMetric([PendingAcc()])
    comp2.device_update(labels, preds)
    assert comp2.device_pending()
    names, vals = comp2.get_nonblocking()  # must not raise, not block
    assert names == ["accuracy"]
    assert comp2.get_name_value_nonblocking()[0][0] == "accuracy"


def test_device_metric_interleaved_paths():
    """Mixing update() and device_update() must never drop or double-count."""
    rng = np.random.RandomState(4)
    m_ref, m_mix = metric_mod.Accuracy(), metric_mod.Accuracy()
    for i in range(4):
        labels, preds = _cls_batch(rng)
        m_ref.update(labels, preds)
        if i % 2:
            m_mix.update(labels, preds)
        else:
            m_mix.device_update(labels, preds)
    assert m_mix.get()[1] == pytest.approx(m_ref.get()[1], rel=1e-6)


# ---------------------------------------------------------------------------
# DevicePrefetchIter
# ---------------------------------------------------------------------------
def _iter_fixture(n=37, batch=8, last="pad"):
    rng = np.random.RandomState(5)
    data = rng.uniform(size=(n, 4)).astype(np.float32)
    label = rng.randint(0, 3, (n,)).astype(np.float32)
    return mx.io.NDArrayIter(data, label, batch_size=batch,
                             last_batch_handle=last)


def test_device_prefetch_iter_ordering_and_pad():
    base = _iter_fixture()
    ref = [(b.data[0].asnumpy(), b.label[0].asnumpy(), b.pad) for b in base]
    base.reset()
    it = mx.io.DevicePrefetchIter(base)
    got = [(b.data[0].asnumpy(), b.label[0].asnumpy(), b.pad,
            getattr(b, "staged", False)) for b in it]
    assert len(got) == len(ref)
    for (d1, l1, p1), (d2, l2, p2, staged) in zip(ref, got):
        np.testing.assert_array_equal(d1, d2)
        np.testing.assert_array_equal(l1, l2)
        assert p1 == p2 and staged
    # exhausted until reset, like the underlying iterator contract
    assert it.iter_next() is False
    it.close()


def test_device_prefetch_iter_reset_semantics():
    it = mx.io.DevicePrefetchIter(_iter_fixture())
    first = [b.data[0].asnumpy() for b in it]
    it.reset()
    second = [b.data[0].asnumpy() for b in it]
    assert len(first) == len(second)
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a, b)
    # mid-epoch reset restarts from the top
    it.reset()
    got = it.next().data[0].asnumpy()
    np.testing.assert_array_equal(got, first[0])
    it.reset()
    again = it.next().data[0].asnumpy()
    np.testing.assert_array_equal(again, first[0])
    it.close()
    with pytest.raises(mx.base.MXNetError):
        it.iter_next()


def test_device_prefetch_iter_provides_and_shardings():
    import jax

    base = _iter_fixture()
    dev = jax.devices()[0]
    it = mx.io.DevicePrefetchIter(
        base, shardings={"data": dev, "softmax_label": dev})
    assert it.provide_data == base.provide_data
    assert it.provide_label == base.provide_label
    b = it.next()
    assert list(b.data[0]._data.devices()) == [dev]
    it.close()


def test_prefetching_iter_device_staging():
    base = _iter_fixture(n=32, batch=8, last="discard")
    it = mx.io.PrefetchingIter(base, context=mx.cpu())
    batches = list(it)
    assert len(batches) == 4
    assert all(getattr(b, "staged", False) for b in batches)
    assert all(isinstance(b.data[0], NDArray) for b in batches)


def test_prefetching_iter_staging_error_raises_not_hangs():
    base = _iter_fixture(n=32, batch=8, last="discard")
    it = mx.io.PrefetchingIter(base, shardings={"data": "not-a-device"})
    with pytest.raises(BaseException):
        it.next()


def test_device_prefetch_iter_staging_error_raises_not_hangs():
    base = _iter_fixture(n=32, batch=8, last="discard")
    it = mx.io.DevicePrefetchIter(base, shardings={"data": "not-a-device"})
    with pytest.raises(BaseException):
        it.next()
    it.close()


# ---------------------------------------------------------------------------
# fit loop: no per-batch sync + metric parity with the eager path
# ---------------------------------------------------------------------------
def _mlp():
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, num_hidden=16, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(h, name="softmax")


_FIT_X = np.random.RandomState(0).uniform(-1, 1, (96, 10)).astype(np.float32)
_FIT_Y = np.random.RandomState(1).randint(0, 4, (96,)).astype(np.float32)


_SYNC_COUNTERS = ("ndarray.asnumpy", "ndarray.wait_to_read",
                  "metric.numpy_fallback", "metric.drain_sync")


def _run_fit(nbatches, metric, batch=8, num_epoch=2):
    """Run fit and return the telemetry sync counters it accrued."""
    it = mx.io.NDArrayIter(
        _FIT_X[:nbatches * batch], _FIT_Y[:nbatches * batch],
        batch_size=batch, last_batch_handle="discard")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mx.random.seed(11)
    tm.reset()
    mod.fit(it, eval_metric=metric, num_epoch=num_epoch,
            optimizer_params={"learning_rate": 0.05})
    return {name: tm.counter(name).value for name in _SYNC_COUNTERS}


def test_fit_no_per_batch_sync():
    """Host syncs in fit must be O(epochs), not O(batches): doubling the
    batch count must not change the telemetry sync-counter totals."""
    m1, m2 = mx.metric.Accuracy(), mx.metric.Accuracy()
    c_small = _run_fit(4, m1)
    batches = tm.counter("fit.batches").value
    staged = tm.counter("io.prefetch.batches").value
    c_large = _run_fit(8, m2)
    assert c_small == c_large, (
        f"per-batch host sync detected: 4 batches -> {c_small}, "
        f"8 batches -> {c_large}")
    # the blocking-sync counts are zero outright on this path; the only
    # metric drains are the per-epoch get_name_value reads
    assert c_large["ndarray.asnumpy"] == 0
    assert c_large["ndarray.wait_to_read"] == 0
    assert c_large["metric.numpy_fallback"] == 0
    assert c_large["metric.drain_sync"] == 2  # one per epoch
    # and the pipeline instrumentation itself saw the run: every batch
    # counted, every batch staged through the prefetcher
    assert batches == 4 * 2
    assert staged >= 4 * 2
    assert tm.counter("fit.batches").value == 8 * 2
    assert tm.counter("metric.device_update").value == 8 * 2
    assert tm.histogram("fit.data_wait").count > 0
    assert tm.histogram("fit.dispatch").count > 0


def test_fit_device_metrics_match_eager_path(monkeypatch):
    class EagerAccuracy(mx.metric.Accuracy):
        def _device_batch(self, label, pred):
            return None  # force the numpy path

    m_dev = mx.metric.Accuracy()
    _run_fit(6, m_dev)
    dev_val = m_dev.get()[1]

    monkeypatch.setenv("MXNET_DEVICE_PREFETCH", "0")
    m_eager = EagerAccuracy()
    _run_fit(6, m_eager)
    assert dev_val == pytest.approx(m_eager.get()[1], abs=1e-9)


def test_score_uses_device_pipeline():
    it = mx.io.NDArrayIter(_FIT_X, _FIT_Y, batch_size=8)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mx.random.seed(11)
    mod.init_params(initializer=mx.init.Xavier())
    res = dict(mod.score(it, "acc"))
    assert 0.0 <= res["accuracy"] <= 1.0
    # the caller's iterator is reusable afterwards (staging thread gone)
    it.reset()
    assert it.next() is not None


def test_module_prepare_stages_batch():
    it = mx.io.NDArrayIter(_FIT_X, _FIT_Y, batch_size=8)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    batch = it.next()
    assert not getattr(batch, "staged", False)
    mod.prepare(batch)
    assert batch.staged
    shardings = mod.input_shardings
    assert set(shardings) == {"data", "softmax_label"}


def test_speedometer_device_pending_safe(caplog):
    """Speedometer must neither block on nor discard an in-flight device
    accumulator: while device_pending() it logs speed-only and leaves the
    metric accumulating; once landed it logs real (never nan) values."""
    import logging as _logging

    import jax

    from mxnet_tpu.callback import Speedometer

    class Param:
        epoch, nbatch = 0, 1
        eval_metric = None

    rng = np.random.RandomState(8)
    m = metric_mod.Accuracy()
    labels, preds = _cls_batch(rng)
    m.device_update(labels, preds)
    ref_count = m.num_inst + m._dev_inst

    class Pending(metric_mod.Accuracy):
        def device_pending(self):
            return True

    pending = Pending()
    pending.device_update(labels, preds)
    p = Param()
    p.eval_metric = pending
    s = Speedometer(batch_size=32, frequent=1)
    with caplog.at_level(_logging.INFO):
        s(p)            # arms the meter
        p.nbatch = 2
        s(p)            # pending -> speed-only line, NO reset
    assert pending._dev_sum is not None  # accumulation survived the tick
    assert not any("Train-" in r.message for r in caplog.records)
    assert any("samples/sec" in r.message for r in caplog.records)

    jax.block_until_ready(m._dev_sum)  # landed: the log+reset path
    p.eval_metric = m
    s2 = Speedometer(batch_size=32, frequent=1)
    with caplog.at_level(_logging.INFO):
        p.nbatch = 1
        s2(p)
        p.nbatch = 2
        s2(p)
    logged = [r for r in caplog.records if "Train-accuracy" in str(r.msg) or
              "Train-%s" in str(r.msg)]
    assert logged, "ready metric was not logged"
    assert m.num_inst == 0 and m._dev_sum is None  # reset after logging
    assert ref_count == 32


# ---------------------------------------------------------------------------
# pipelined window dispatch (ISSUE 6): >=2 windows in flight, lazy boundary
# ---------------------------------------------------------------------------
def _run_fit_windows(monkeypatch, nbatches, depth, k=2, batch=8,
                     num_epoch=2, seed=11):
    """fit with fused K-step windows at the given dispatch depth; returns
    (module, sync-counter dict) — counters read AFTER the run."""
    monkeypatch.setenv("MXNET_TRAIN_WINDOW", str(k))
    monkeypatch.setenv("MXNET_DISPATCH_DEPTH", str(depth))
    it = mx.io.NDArrayIter(
        _FIT_X[:nbatches * batch], _FIT_Y[:nbatches * batch],
        batch_size=batch, last_batch_handle="discard")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mx.random.seed(seed)
    tm.reset()
    mod.fit(it, eval_metric=mx.metric.Accuracy(), num_epoch=num_epoch,
            optimizer_params={"learning_rate": 0.05})
    return mod, {name: tm.counter(name).value for name in _SYNC_COUNTERS}


def test_fit_pipelined_windows_zero_per_window_sync(monkeypatch):
    """Steady-state fit with dispatch depth 2 must issue ZERO per-window
    host syncs: doubling the window count must not move the sync counters
    (which must be zero outright), while the depth telemetry proves >=2
    windows were actually in flight."""
    _, c_small = _run_fit_windows(monkeypatch, 4, depth=2)  # 2 win/epoch
    small_windows = tm.histogram("fit.window").count
    assert tm.gauge("fit.dispatch_depth").value == 2
    assert tm.gauge("fit.windows_in_flight").max >= 2
    _, c_large = _run_fit_windows(monkeypatch, 8, depth=2)  # 4 win/epoch
    assert c_small == c_large, (
        f"per-window host sync detected: 2 windows/epoch -> {c_small}, "
        f"4 windows/epoch -> {c_large}")
    assert c_large["ndarray.asnumpy"] == 0
    assert c_large["ndarray.wait_to_read"] == 0
    assert c_large["metric.numpy_fallback"] == 0
    assert c_large["metric.drain_sync"] == 2  # one per epoch
    # the pipeline instrumentation saw the run: every full window spanned,
    # every boundary retired through the backpressure fence
    assert small_windows == 2 * 2
    assert tm.histogram("fit.window").count == 4 * 2
    assert tm.histogram("fit.window_wait").count > 0
    assert tm.gauge("fit.windows_in_flight").max >= 2
    assert tm.gauge("fit.windows_in_flight").value == 0  # drained


def test_fit_dispatch_depth_parity_bit_identical(monkeypatch):
    """Pipelining is a host-scheduling change only: depth=2 must produce
    BIT-identical parameters to depth=1 for a fixed RNG run (same fused
    programs, same data order, same rng stream)."""
    mod1, _ = _run_fit_windows(monkeypatch, 6, depth=1)
    mod2, _ = _run_fit_windows(monkeypatch, 6, depth=2)
    a1, x1 = mod1.get_params()
    a2, x2 = mod2.get_params()
    for k in a1:
        np.testing.assert_array_equal(
            a1[k].asnumpy(), a2[k].asnumpy(), err_msg=k)
    for k in x1:
        np.testing.assert_array_equal(
            x1[k].asnumpy(), x2[k].asnumpy(), err_msg=k)


def test_fit_window_metrics_match_per_batch_path(monkeypatch):
    """The pipelined window loop's epoch metric (window-granular: last
    batch of each window) must match an unpipelined window run — the
    depth must not change WHAT the metric sees."""
    monkeypatch.setenv("MXNET_TRAIN_WINDOW", "2")
    monkeypatch.setenv("MXNET_DISPATCH_DEPTH", "2")
    m = mx.metric.Accuracy()
    it = mx.io.NDArrayIter(_FIT_X[:48], _FIT_Y[:48], batch_size=8,
                           last_batch_handle="discard")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mx.random.seed(7)
    mod.fit(it, eval_metric=m, num_epoch=1,
            optimizer_params={"learning_rate": 0.05})
    val2 = m.get()[1]
    monkeypatch.setenv("MXNET_DISPATCH_DEPTH", "1")
    m1 = mx.metric.Accuracy()
    it.reset()
    mod1 = mx.mod.Module(_mlp(), context=mx.cpu())
    mx.random.seed(7)
    mod1.fit(it, eval_metric=m1, num_epoch=1,
             optimizer_params={"learning_rate": 0.05})
    assert val2 == pytest.approx(m1.get()[1], abs=1e-9)


def test_fit_rollback_guard_caps_dispatch_depth(monkeypatch):
    """MXNET_NONFINITE_GUARD=rollback must fence every boundary: the
    dispatch-depth gauge reports the policy cap at 1 and at most one
    window is ever in flight. ``skip`` decides on the device: it caps
    nothing and reads nothing back."""
    monkeypatch.setenv("MXNET_NONFINITE_GUARD", "skip")
    _, syncs = _run_fit_windows(monkeypatch, 6, depth=2)
    assert tm.gauge("fit.dispatch_depth").value == 2
    assert tm.gauge("fit.windows_in_flight").max >= 2
    assert syncs == dict(dict.fromkeys(_SYNC_COUNTERS, 0),
                         **{"metric.drain_sync": 2})    # one an epoch
    monkeypatch.setenv("MXNET_NONFINITE_GUARD", "rollback")
    _run_fit_windows(monkeypatch, 6, depth=2)
    assert tm.gauge("fit.dispatch_depth").value == 1
    assert tm.gauge("fit.windows_in_flight").max <= 1


def test_prefetch_queue_grows_to_cover_pipeline(monkeypatch):
    """Auto prefetch depth must cover dispatch_depth x K batches (+1) once
    windows engage — the pipeline is only as deep as the staged data."""
    depths = []
    orig = mx.io.DevicePrefetchIter.set_depth

    def spy(self, depth):
        depths.append(depth)
        return orig(self, depth)

    monkeypatch.setattr(mx.io.DevicePrefetchIter, "set_depth", spy)
    _run_fit_windows(monkeypatch, 6, depth=2, k=3)
    assert depths and max(depths) == 3 * 2 + 1
    # an explicit MXNET_PREFETCH_DEPTH wins over auto sizing
    monkeypatch.setenv("MXNET_PREFETCH_DEPTH", "4")
    depths.clear()
    _run_fit_windows(monkeypatch, 6, depth=2, k=3)
    assert not depths


# ---------------------------------------------------------------------------
# kvstore create spellings (satellite)
# ---------------------------------------------------------------------------
def test_kvstore_create_reference_spellings():
    assert mx.kv.create("LOCAL").type == "local"
    assert mx.kv.create("Device").type == "device"
    # plain "dist" is reference shorthand for the default sync store
    assert mx.kv.create("dist").type == "dist_sync"
    with pytest.raises(ValueError):
        mx.kv.create("no_such_store")
