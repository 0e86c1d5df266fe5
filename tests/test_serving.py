"""Serving subsystem acceptance tests (ISSUE 5).

- concurrent clients get outputs bitwise-identical to a sequential
  Predictor.forward of the same program shape (the batcher annotates each
  response with the bucket that served it; within one bucket program,
  outputs are bitwise independent of row position and batch-mates);
- a warmed server performs ZERO XLA compiles on the request path
  (executor.jit_compile counter-verified);
- overload sheds fast (ServerOverloaded + serving.shed) instead of
  queueing unboundedly;
- hot reload mid-traffic drops no in-flight request and subsequent
  responses reflect the new weights.
"""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.base import MXNetError
from mxnet_tpu.predictor import Predictor
from mxnet_tpu.serving import (DeadlineExceeded, LatencyHistogram,
                               ModelServer, ServerClosed, ServerOverloaded,
                               ServingConfig)

# batcher/replica-pool/server threads: tier-1 runs this suite under the
# runtime lock-order sanitizer (opt out with MXNET_SANITIZER=0)
pytestmark = pytest.mark.sanitize


def _mlp_params(seed=0, num_classes=4, scale=1.0):
    sym = models.mlp(num_classes=num_classes)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 6), softmax_label=(1,))
    rng = np.random.RandomState(seed)
    params = {}
    for n, s in zip(sym.list_arguments(), arg_shapes):
        if n in ("data", "softmax_label"):
            continue
        params[n] = mx.nd.array(
            (scale * rng.randn(*s)).astype(np.float32))
    return sym, params


def _combined(params):
    return {f"arg:{k}": v for k, v in params.items()}


def _server(sym, params, buckets=(1, 2, 4), **cfg):
    cfg.setdefault("max_delay_ms", 3.0)
    cfg.setdefault("queue_depth", 64)
    return ModelServer(sym, params, {"data": (6,)},
                       config=ServingConfig(buckets=buckets, **cfg))


def test_concurrent_bitwise_identical_to_sequential():
    sym, params = _mlp_params()
    srv = _server(sym, params).start()
    try:
        # sequential references: a plain Predictor per bucket shape — the
        # exact "sequential Predictor.forward" computation. Within one
        # program shape XLA results are bitwise independent of row
        # position/batch-mates, so row 0 of [x, 0...] is THE answer for x
        # at that bucket.
        refs = {b: Predictor(sym, _combined(params), {"data": (b, 6)})
                for b in (1, 2, 4)}
        rng = np.random.RandomState(7)
        xs = [rng.uniform(-1, 1, (6,)).astype(np.float32)
              for _ in range(24)]
        expected = {}
        for i, x in enumerate(xs):
            for b, ref in refs.items():
                batch = np.zeros((b, 6), np.float32)
                batch[0] = x
                expected[(i, b)] = ref.run(data=batch)[0][0]

        results = [None] * len(xs)

        def client(i):
            fut = srv.submit({"data": xs[i]})
            results[i] = (fut.result(30), fut)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(xs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        buckets_seen = set()
        for i, (outs, fut) in enumerate(results):
            b = fut.bucket
            buckets_seen.add(b)
            want = expected[(i, b)]
            assert outs[0].tobytes() == want.tobytes(), (
                f"request {i} (bucket {b}) differs from the sequential "
                f"Predictor.forward: {np.abs(outs[0] - want).max()}")
            # and numerically consistent with the batch-1 answer across
            # every bucket (bit-exactness across SHAPES is not an XLA
            # contract; docs/serving.md documents the per-bucket one)
            np.testing.assert_allclose(outs[0], expected[(i, 1)],
                                       rtol=1e-5, atol=1e-6)
        assert buckets_seen - {1, 2, 4} == set()
        # 24 near-simultaneous clients must actually coalesce: if every
        # request ran alone at bucket 1, the batcher did nothing
        assert max(buckets_seen) > 1, (
            f"no batching happened (buckets seen: {buckets_seen})")
    finally:
        srv.close()


def test_zero_request_path_compiles_after_warmup():
    sym, params = _mlp_params()
    srv = _server(sym, params)
    srv.warmup()
    srv.start()
    try:
        compiles = mx.telemetry.counter("executor.jit_compile")
        aot_trace = mx.telemetry.counter("aot.trace_compile")
        c0, a0 = compiles.value, aot_trace.value
        rng = np.random.RandomState(3)
        for wave in range(4):  # mixed batch sizes → every bucket exercised
            futs = [srv.submit({"data": rng.uniform(-1, 1, (6,))
                                .astype(np.float32)})
                    for _ in range(1 + wave)]
            for f in futs:
                f.result(30)
        assert compiles.value - c0 == 0, (
            "XLA compile on the warmed request path")
        assert aot_trace.value - a0 == 0
        assert mx.telemetry.counter("serving.request").value > 0
    finally:
        srv.close()


def test_overload_sheds_instead_of_queueing():
    sym, params = _mlp_params()
    srv = _server(sym, params, buckets=(1,), queue_depth=3,
                  max_delay_ms=0.0)
    entered = threading.Event()
    release = threading.Event()
    real_infer = srv._infer

    def slow_infer(bucket, stacked, n_valid):
        entered.set()
        assert release.wait(30)
        return real_infer(bucket, stacked, n_valid)

    srv._batcher._runner = slow_infer
    srv.start()
    try:
        shed = mx.telemetry.counter("serving.shed")
        s0 = shed.value
        x = np.zeros((6,), np.float32)
        blocked = srv.submit({"data": x})  # taken by the worker
        assert entered.wait(10)
        queued = [srv.submit({"data": x}) for _ in range(3)]  # fills queue
        with pytest.raises(ServerOverloaded):
            srv.submit({"data": x})
        assert shed.value - s0 >= 1
        release.set()
        # nothing that was admitted is lost
        assert len(blocked.result(30)) > 0
        for f in queued:
            assert len(f.result(30)) > 0
    finally:
        release.set()
        srv.close()


def test_deadline_expired_requests_are_dropped():
    sym, params = _mlp_params()
    srv = _server(sym, params, buckets=(1,), max_delay_ms=0.0)
    entered = threading.Event()
    release = threading.Event()
    real_infer = srv._infer

    def slow_infer(bucket, stacked, n_valid):
        entered.set()
        assert release.wait(30)
        return real_infer(bucket, stacked, n_valid)

    srv._batcher._runner = slow_infer
    srv.start()
    try:
        x = np.zeros((6,), np.float32)
        first = srv.submit({"data": x})
        assert entered.wait(10)
        doomed = srv.submit({"data": x}, deadline_ms=10)
        time.sleep(0.05)  # deadline passes while queued behind slow_infer
        release.set()
        with pytest.raises(DeadlineExceeded):
            doomed.result(30)
        assert len(first.result(30)) > 0
        assert mx.telemetry.counter("serving.deadline_expired").value >= 1
    finally:
        release.set()
        srv.close()


def test_deadline_shorter_than_max_delay_still_serves():
    """A lone request with a deadline SHORTER than the coalescing
    max_delay must dispatch early and be served on an idle server — the
    batching wait must never outlive a queued deadline. No stopwatch: a
    request whose deadline has passed when its batch is formed is failed
    and counted, so "served, and ``serving.deadline_expired`` did not
    move" says the batcher woke for the deadline (half a second) and not
    for max_delay (a minute: ``predict`` would give up first, and that
    error is not caught here). The batcher wakes 1 ms before the deadline;
    on a host so loaded that the wake itself comes over 1 ms late the
    request expires, as it should, and is asked again."""
    sym, params = _mlp_params()
    srv = _server(sym, params, buckets=(1, 4), max_delay_ms=60_000.0).start()
    expired = mx.telemetry.counter("serving.deadline_expired")
    try:
        for _ in range(6):
            before = expired.value
            try:
                out = srv.predict({"data": np.zeros((6,), np.float32)},
                                  timeout=30, deadline_ms=500)
            except DeadlineExceeded:
                continue
            assert len(out) > 0
            assert expired.value == before
            break
        else:
            pytest.fail("six lone requests in a row expired in the queue "
                        "of an idle server")
    finally:
        srv.close()


def test_future_is_stamped_with_compute_version():
    """Each future carries the weight version its batch computed against
    (reading server.version after the result races a concurrent
    reload)."""
    sym, params = _mlp_params()
    srv = _server(sym, params).start()
    try:
        fut = srv.submit({"data": np.zeros((6,), np.float32)})
        fut.result(30)
        assert fut.version == 0
        srv.reload({f"arg:{k}": v * 2.0 for k, v in params.items()})
        fut = srv.submit({"data": np.zeros((6,), np.float32)})
        fut.result(30)
        assert fut.version == 1
    finally:
        srv.close()


def test_hot_reload_mid_traffic_loses_nothing(tmp_path):
    sym, params_v1 = _mlp_params(seed=0)
    _, params_v2 = _mlp_params(seed=42, scale=2.0)
    srv = _server(sym, params_v1).start()
    failures = []
    stop = threading.Event()
    served = [0]
    try:
        ref_v2 = Predictor(sym, _combined(params_v2), {"data": (1, 6)})
        rng = np.random.RandomState(11)
        xs = [rng.uniform(-1, 1, (6,)).astype(np.float32)
              for _ in range(8)]

        def pound():
            i = 0
            while not stop.is_set():
                try:
                    srv.predict(xs[i % len(xs)], timeout=30)
                    served[0] += 1
                except Exception as e:  # noqa: BLE001 — the assertion
                    failures.append(repr(e))
                    return
                i += 1

        clients = [threading.Thread(target=pound, daemon=True)
                   for _ in range(4)]
        for t in clients:
            t.start()
        time.sleep(0.2)
        # reload from a .params FILE (the save_checkpoint artifact)
        pfile = str(tmp_path / "v2.params")
        mx.nd.save(pfile, _combined(params_v2))
        v = srv.reload(pfile)
        assert v == 1
        time.sleep(0.2)
        stop.set()
        for t in clients:
            t.join()
        assert not failures, failures
        assert served[0] > 0
        # post-reload responses carry the NEW weights, bitwise (a lone
        # request runs at bucket 1 — the reference's exact program shape)
        out = srv.predict(xs[0], timeout=30)
        want = ref_v2.run(data=xs[0][None])[0][0]
        assert out[0].tobytes() == want.tobytes()
    finally:
        stop.set()
        srv.close()


def test_reload_from_checkpoint_dir_and_watch(tmp_path):
    from mxnet_tpu.checkpoint import CheckpointConfig, CheckpointManager

    sym, params_v1 = _mlp_params(seed=1)
    _, params_v2 = _mlp_params(seed=2, scale=3.0)

    class _FakeModule:  # what CheckpointManager needs from a Module
        def __init__(self, symbol, args):
            self.symbol = symbol
            self._args = args

        def get_params(self):
            return self._args, {}

    ckpt_dir = str(tmp_path / "ckpts")
    mgr = CheckpointManager(CheckpointConfig(ckpt_dir),
                            module=_FakeModule(sym, params_v1))
    mgr.save(next_epoch=1, next_batch=0)

    # initial weights FROM the checkpoint dir; watcher polls LATEST
    srv = ModelServer(
        sym, ckpt_dir, {"data": (6,)},
        config=ServingConfig(buckets=(1, 2), max_delay_ms=1.0,
                             watch_dir=ckpt_dir, watch_period=0.05))
    srv.start()
    try:
        x = np.linspace(-1, 1, 6).astype(np.float32)
        ref_v1 = Predictor(sym, _combined(params_v1), {"data": (1, 6)})
        out = srv.predict(x, timeout=30)
        assert out[0].tobytes() == \
            ref_v1.run(data=x[None])[0][0].tobytes()

        # trainer commits a new checkpoint → watcher hot-reloads
        mgr.module = _FakeModule(sym, params_v2)
        mgr.save(next_epoch=2, next_batch=0)
        deadline = time.monotonic() + 10
        while srv.version == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert srv.version >= 1, "watcher never picked up the new LATEST"
        ref_v2 = Predictor(sym, _combined(params_v2), {"data": (1, 6)})
        out = srv.predict(x, timeout=30)
        assert out[0].tobytes() == \
            ref_v2.run(data=x[None])[0][0].tobytes()
        assert mx.telemetry.counter("serving.reload").value >= 1
    finally:
        srv.close()


def test_checkpoint_committed_before_start_still_reloads(tmp_path):
    """A checkpoint landing between __init__'s load and start() must hot
    reload: start() must not mark the current LATEST as already seen."""
    from mxnet_tpu.checkpoint import CheckpointConfig, CheckpointManager

    sym, params_v1 = _mlp_params(seed=5)
    _, params_v2 = _mlp_params(seed=6, scale=2.0)

    class _FakeModule:
        def __init__(self, symbol, args):
            self.symbol = symbol
            self._args = args

        def get_params(self):
            return self._args, {}

    ckpt_dir = str(tmp_path / "ckpts")
    mgr = CheckpointManager(CheckpointConfig(ckpt_dir),
                            module=_FakeModule(sym, params_v1))
    mgr.save(next_epoch=1, next_batch=0)
    srv = ModelServer(
        sym, ckpt_dir, {"data": (6,)},
        config=ServingConfig(buckets=(1,), max_delay_ms=1.0,
                             watch_dir=ckpt_dir, watch_period=0.05))
    # the trainer commits v2 in the window before start()
    mgr.module = _FakeModule(sym, params_v2)
    mgr.save(next_epoch=2, next_batch=0)
    srv.start()
    try:
        deadline = time.monotonic() + 10
        while srv.version == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert srv.version >= 1, (
            "checkpoint committed before start() was never reloaded")
        x = np.linspace(-1, 1, 6).astype(np.float32)
        ref_v2 = Predictor(sym, _combined(params_v2), {"data": (1, 6)})
        out = srv.predict(x, timeout=30)
        assert out[0].tobytes() == \
            ref_v2.run(data=x[None])[0][0].tobytes()
    finally:
        srv.close()


def test_bfloat16_input_types_supported():
    """ModelServer's input-dtype probe must go through base.np_dtype:
    'bfloat16' is a framework dtype numpy's own parser rejects."""
    import ml_dtypes

    data = mx.sym.Variable("data")
    out = mx.sym.Flatten(data, name="flat")
    srv = ModelServer(out, {}, {"data": (3,)},
                      config=ServingConfig(buckets=(1,), max_delay_ms=0.0),
                      input_types={"data": "bfloat16"}).start()
    try:
        got = srv.predict(np.array([1.0, 2.0, 0.5], np.float32),
                          timeout=30)
        assert got[0].dtype == ml_dtypes.bfloat16
        assert got[0].tolist() == [1.0, 2.0, 0.5]
    finally:
        srv.close()


def test_buckets_share_device_weights():
    """Every bucket predictor binds the SAME device array per weight (one
    HBM copy server-wide), and a reload swaps them all through the shared
    object."""
    sym, params = _mlp_params()
    srv = _server(sym, params, buckets=(1, 2, 4))
    preds = [srv.predictor(b) for b in (1, 2, 4)]
    for name in params:
        bound = [p._exec.arg_dict[name] for p in preds]
        assert all(b is bound[0] for b in bound), (
            f"{name} duplicated across bucket predictors")
    srv.close()


def _bn_net_params(seed=0, scale=1.0):
    """Conv + BatchNorm + FC: exercises the server-level BN fold."""
    data = mx.sym.Variable("data")
    c = mx.sym.Convolution(data, num_filter=4, kernel=(3, 3), name="conv0")
    b = mx.sym.BatchNorm(c, name="bn0")
    a = mx.sym.Activation(b, act_type="relu", name="relu0")
    fc = mx.sym.FullyConnected(mx.sym.Flatten(a), num_hidden=3, name="fc")
    sym = mx.sym.SoftmaxOutput(fc, name="softmax")
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=(1, 2, 8, 8), softmax_label=(1,))
    rng = np.random.RandomState(seed)
    args, auxs = {}, {}
    for n, s in zip(sym.list_arguments(), arg_shapes):
        if n in ("data", "softmax_label"):
            continue
        if "gamma" in n:
            args[n] = mx.nd.array(
                (1 + 0.1 * scale * rng.rand(*s)).astype(np.float32))
        else:
            args[n] = mx.nd.array(
                (scale * rng.randn(*s)).astype(np.float32))
    for n, s in zip(sym.list_auxiliary_states(), aux_shapes):
        auxs[n] = mx.nd.array(
            (1 + rng.rand(*s)).astype(np.float32) if "var" in n
            else (0.1 * scale * rng.randn(*s)).astype(np.float32))
    return sym, args, auxs


def test_hot_reload_of_batchnorm_folded_model(tmp_path):
    """Reload must survive the server-level BN fold: the fold's output
    dict keeps folded-out gamma/beta keys that are NOT arguments of the
    folded graph — reload filters them before the strict swap."""
    sym, args1, auxs1 = _bn_net_params(seed=0)
    _, args2, auxs2 = _bn_net_params(seed=9, scale=2.0)
    srv = ModelServer(sym, dict(args1, **{f"aux:{k}": v
                                          for k, v in auxs1.items()}),
                      {"data": (2, 8, 8)},
                      config=ServingConfig(buckets=(1, 2),
                                           max_delay_ms=1.0))
    srv.start()
    try:
        x = np.random.RandomState(4).uniform(
            -1, 1, (2, 8, 8)).astype(np.float32)
        out_v1 = srv.predict(x, timeout=30)

        pfile = str(tmp_path / "v2.params")
        save = {f"arg:{k}": v for k, v in args2.items()}
        save.update({f"aux:{k}": v for k, v in auxs2.items()})
        mx.nd.save(pfile, save)
        assert srv.reload(pfile) == 1

        out_v2 = srv.predict(x, timeout=30)
        assert out_v1[0].tobytes() != out_v2[0].tobytes()
        # matches a fresh fold-enabled Predictor over the v2 weights
        ref = Predictor(sym, save, {"data": (1, 2, 8, 8)})
        want = ref.run(data=x[None])[0][0]
        assert out_v2[0].tobytes() == want.tobytes()
    finally:
        srv.close()


def test_cancelled_future_does_not_kill_the_worker():
    """fut.cancel() on a queued request (with a deadline) must not crash
    the single batcher thread — the post-cancel traffic still serves."""
    sym, params = _mlp_params()
    srv = _server(sym, params, buckets=(1,), max_delay_ms=0.0)
    entered = threading.Event()
    release = threading.Event()
    real_infer = srv._infer

    def slow_infer(bucket, stacked, n_valid):
        entered.set()
        assert release.wait(30)
        return real_infer(bucket, stacked, n_valid)

    srv._batcher._runner = slow_infer
    srv.start()
    try:
        x = np.zeros((6,), np.float32)
        first = srv.submit({"data": x})
        assert entered.wait(10)
        doomed = srv.submit({"data": x}, deadline_ms=1)
        assert doomed.cancel()  # client gives up while it is still queued
        time.sleep(0.02)  # its deadline also expires
        release.set()
        assert len(first.result(30)) > 0
        # worker survived: fresh traffic still flows
        srv._batcher._runner = real_infer
        assert len(srv.predict({"data": x}, timeout=30)) > 0
    finally:
        release.set()
        srv.close()


def test_close_drains_queued_requests():
    sym, params = _mlp_params()
    srv = _server(sym, params, buckets=(1, 4), max_delay_ms=50.0).start()
    x = np.zeros((6,), np.float32)
    futs = [srv.submit({"data": x}) for _ in range(6)]
    srv.close(drain=True)
    for f in futs:
        assert len(f.result(5)) > 0  # already resolved by the drain
    with pytest.raises(ServerClosed):
        srv.submit({"data": x})


def test_submit_validation():
    sym, params = _mlp_params()
    srv = _server(sym, params).start()
    try:
        with pytest.raises(MXNetError):
            srv.submit({"wrong_name": np.zeros((6,), np.float32)})
        with pytest.raises(MXNetError):
            srv.submit({"data": np.zeros((7,), np.float32)})
        # bare array accepted for single-input models
        out = srv.predict(np.zeros((6,), np.float32), timeout=30)
        assert out[0].shape == (4,)
    finally:
        srv.close()


def test_http_frontend_predict_healthz_metrics():
    from mxnet_tpu.serving import make_http_server

    sym, params = _mlp_params()
    srv = _server(sym, params).start()
    httpd = make_http_server(srv, host="127.0.0.1", port=0)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        x = np.linspace(-1, 1, 6).astype(np.float32)
        body = json.dumps({"inputs": {"data": x.tolist()}}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            payload = json.loads(r.read())
        want = srv.predict(x, timeout=30)
        np.testing.assert_allclose(
            np.asarray(payload["outputs"][0], np.float32), want[0],
            rtol=1e-6)

        # raw float32 round-trip
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict", data=x.tobytes(),
            headers={"Content-Type": "application/octet-stream",
                     "Accept": "application/octet-stream"})
        with urllib.request.urlopen(req, timeout=30) as r:
            raw = np.frombuffer(r.read(), np.float32)
        assert raw.shape == (4,)

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=30) as r:
            health = json.loads(r.read())
        assert health["status"] == "ok"
        assert health["buckets"] == [1, 2, 4]

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
            text = r.read().decode()
        assert "mxnet_serving_request" in text

        # direct-inputs form WITH deadline_ms: the key must act as the
        # deadline, not be rejected as an unknown input name
        body = json.dumps({"data": x.tolist(),
                           "deadline_ms": 10000}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            payload = json.loads(r.read())
        np.testing.assert_allclose(
            np.asarray(payload["outputs"][0], np.float32), want[0],
            rtol=1e-6)

        # malformed body → 400, not a worker crash
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict", data=b"not json",
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 400

        # a 404'd POST must drain its body: on one keep-alive connection
        # the next legitimate request must still parse
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        try:
            conn.request("POST", "/wrong", body=b'{"x": 1}',
                         headers={"Content-Type": "application/json"})
            r1 = conn.getresponse()
            r1.read()
            assert r1.status == 404
            body = json.dumps({"inputs": {"data": x.tolist()}}).encode()
            conn.request("POST", "/predict", body=body,
                         headers={"Content-Type": "application/json"})
            r2 = conn.getresponse()
            assert r2.status == 200, (
                "keep-alive connection corrupted by the 404's unread body")
            np.testing.assert_allclose(
                np.asarray(json.loads(r2.read())["outputs"][0], np.float32),
                want[0], rtol=1e-6)
        finally:
            conn.close()
    finally:
        httpd.shutdown()
        httpd.server_close()
        srv.close()


def test_latency_histogram_percentiles():
    h = LatencyHistogram(lo_us=1.0, hi_us=1e6, ratio=2.0)
    for v in [100.0] * 90 + [10000.0] * 10:
        h.observe_us(v)
    assert h.count == 100
    p50, p99 = h.percentile(50), h.percentile(99)
    assert 64 <= p50 <= 256        # covering bucket of 100µs
    assert 4096 <= p99 <= 32768    # covering bucket of 10ms
    assert h.percentile(99) >= h.percentile(50)
    snap = h.snapshot()
    assert snap["count"] == 100
    h.reset()
    assert h.count == 0 and h.percentile(50) == 0.0


def test_batcher_pad_and_bucket_telemetry():
    sym, params = _mlp_params()
    srv = _server(sym, params, buckets=(4,), max_delay_ms=20.0).start()
    try:
        bs = mx.telemetry.histogram("serving.batch_size")
        pw = mx.telemetry.histogram("serving.pad_waste")
        c0, w0 = bs.count, pw.sum
        futs = [srv.submit({"data": np.zeros((6,), np.float32)})
                for _ in range(3)]
        for f in futs:
            f.result(30)
        assert bs.count > c0
        assert pw.sum - w0 >= 1  # 3 requests padded into the 4-bucket
        assert futs[0].bucket == 4
    finally:
        srv.close()


def test_int8_variant_parity_and_stats():
    """ModelServer(variant="int8") serves post-training-quantized weights
    (models/recipe.py int8_weights, applied after BN folding): outputs
    stay within the int8 parity tolerance of the f32 server, stats()
    names the quantized tensors, and reload re-quantizes."""
    net = models.lenet(num_classes=10)
    shape = (2, 1, 28, 28)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[mx.io.DataDesc("data", shape)],
             label_shapes=[mx.io.DataDesc("softmax_label", (shape[0],))])
    mod.init_params(initializer=mx.init.Xavier())
    arg, aux = mod.get_params()
    params = ({f"arg:{k}": v for k, v in arg.items()}
              | {f"aux:{k}": v for k, v in aux.items()})
    cfg = ServingConfig(buckets=(2,), replicas=1, max_delay_ms=1.0)
    x = np.random.RandomState(0).rand(1, 28, 28).astype(np.float32)

    with pytest.raises(MXNetError):
        ModelServer(net, params, {"data": (1, 28, 28)}, config=cfg,
                    variant="int4")

    outs, stats = {}, {}
    for variant in ("f32", "int8"):
        srv = ModelServer(net, params, {"data": (1, 28, 28)}, config=cfg,
                          variant=variant)
        srv.start()
        try:
            outs[variant] = np.asarray(srv.predict({"data": x})[0],
                                       dtype=np.float32)
            stats[variant] = srv.stats()
            if variant == "int8":
                srv.reload(params)  # must re-quantize, not de-quantize
                after = np.asarray(srv.predict({"data": x})[0],
                                   dtype=np.float32)
                np.testing.assert_array_equal(after, outs["int8"])
        finally:
            srv.close()

    assert stats["f32"]["variant"] == "f32"
    assert stats["f32"]["int8_weights"] == {}
    assert stats["int8"]["variant"] == "int8"
    # conv1 (500 elems) stays exact under the min_size=1024 floor; the
    # big conv/dense weights are quantized
    q = set(stats["int8"]["int8_weights"])
    assert {"conv2_weight", "fc1_weight", "fc2_weight"} <= q
    assert "conv1_weight" not in q
    assert all(s > 0 for s in stats["int8"]["int8_weights"].values())
    # int8 parity tolerance: per-tensor symmetric 8-bit weights move the
    # lenet softmax by well under a percent
    assert not np.array_equal(outs["int8"], outs["f32"])  # really quantized
    np.testing.assert_allclose(outs["int8"], outs["f32"], atol=0.01)
