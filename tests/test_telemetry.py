"""Telemetry subsystem (ISSUE 2, ISSUE 24): registry semantics, spans that
nest and sit on the profiler's clock, hot-path instrumentation wiring."""

import json
import os
import sys

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import telemetry as tm  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_registry():
    tm.reset()
    spans = tm.spans_enabled()
    yield
    tm.enable_spans(spans)
    tm.reset()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------
def test_counter_gauge_histogram_basics():
    c = tm.counter("t.c")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert tm.counter("t.c") is c  # same handle on re-lookup

    g = tm.gauge("t.g")
    g.set(3)
    g.set(1)
    assert g.value == 1 and g.max == 3

    h = tm.histogram("t.h")
    for v in (10, 2, 8):
        h.observe(v)
    assert h.count == 3 and h.sum == 20 and h.min == 2 and h.max == 10


def test_kind_collision_raises():
    tm.counter("t.kind")
    with pytest.raises(TypeError):
        tm.gauge("t.kind")


def test_reset_keeps_handles_valid():
    c = tm.counter("t.reset")
    c.inc(7)
    tm.reset()
    assert c.value == 0
    c.inc()
    assert tm.counter("t.reset").value == 1


def test_snapshot_nests_on_dots():
    tm.counter("a.b.c").inc(2)
    tm.gauge("a.b.g").set(9)
    snap = tm.snapshot()
    assert snap["a"]["b"]["c"] == 2
    assert snap["a"]["b"]["g"]["value"] == 9


def test_snapshot_instrument_nested_under_instrument():
    # "n.h" (a histogram whose rendering is itself a dict) and "n.h.retries"
    # must come out as two distinct metrics, not merge into one dict
    tm.histogram("n.h").observe(3)
    tm.counter("n.h.retries").inc(2)
    snap = tm.snapshot()
    assert snap["n"]["h"][""]["count"] == 1
    assert snap["n"]["h"]["retries"] == 2


def test_enable_spans_mid_span_records_cleanly():
    tm.enable_spans(False)
    s = tm.span("mid.span")
    s.__enter__()
    tm.enable_spans(True)  # e.g. from a callback while fit spans are open
    s.__exit__(None, None, None)
    assert [e["name"] for e in tm.events()] == ["mid.span"]


def test_dump_writes_json_and_prometheus(tmp_path):
    tm.counter("d.count").inc(3)
    tm.histogram("d.hist").observe(5)
    json_path, prom_path = tm.dump(str(tmp_path / "snap.json"))
    with open(json_path) as f:
        snap = json.load(f)
    assert snap["d"]["count"] == 3
    prom = open(prom_path).read()
    assert "# TYPE mxnet_d_count counter" in prom
    assert "mxnet_d_count 3" in prom
    assert "mxnet_d_hist_count 1" in prom
    assert "mxnet_d_hist_sum 5" in prom


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
def test_span_histogram_always_on_events_gated():
    tm.enable_spans(False)
    with tm.span("t.phase"):
        pass
    assert tm.histogram("t.phase").count == 1
    assert tm.events() == []

    tm.enable_spans(True)
    with tm.span("t.phase", detail="x"):
        pass
    evts = tm.events()
    assert len(evts) == 1
    ev = evts[0]
    assert ev["name"] == "t.phase" and ev["ph"] == "X"
    assert ev["dur"] > 0 and "ts" in ev and "pid" in ev and "tid" in ev
    assert ev["id"] >= 1 and ev["parent"] is None
    assert ev["args"] == {"detail": "x"}
    assert tm.histogram("t.phase").count == 2


def test_nested_spans_record_id_and_parent():
    tm.enable_spans(True)
    with tm.span("t.outer"):
        with tm.span("t.inner"):
            pass
        with tm.span("t.inner"):
            pass
    with tm.span("t.outer"):
        pass
    by_id = {e["id"]: e for e in tm.events()}
    assert len(by_id) == 4  # every span has an id of its own
    outers = [e for e in by_id.values() if e["name"] == "t.outer"]
    inners = [e for e in by_id.values() if e["name"] == "t.inner"]
    assert all(e["parent"] is None for e in outers)
    first = min(outers, key=lambda e: e["ts"])
    assert [e["parent"] for e in inners] == [first["id"]] * 2
    for e in inners:  # one clock: a child lies inside its parent
        assert first["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= first["ts"] + first["dur"]


def test_parent_is_per_thread():
    import threading

    tm.enable_spans(True)
    inside, release = threading.Event(), threading.Event()

    def worker():
        with tm.span("t.worker"):
            inside.set()
            assert release.wait(30)
            with tm.span("t.worker_child"):
                pass

    th = threading.Thread(target=worker)
    with tm.span("t.main"):
        th.start()
        assert inside.wait(30)  # t.worker is open on the other thread
        with tm.span("t.main_child"):
            pass
        release.set()
        th.join(30)
        assert not th.is_alive()
    ev = {e["name"]: e for e in tm.events()}
    assert ev["t.main"]["parent"] is None and ev["t.worker"]["parent"] is None
    assert ev["t.main_child"]["parent"] == ev["t.main"]["id"]
    assert ev["t.worker_child"]["parent"] == ev["t.worker"]["id"]
    assert ev["t.main"]["tid"] != ev["t.worker"]["tid"]
    # the other thread's span is nobody's child here: all of t.main's time
    # outside t.main_child is its own
    h = tm.histogram
    assert h("t.main").self_sum >= h("t.main").sum - h("t.main_child").sum - 2


@pytest.mark.parametrize("children", [2, 0])
def test_self_sum_is_duration_less_children(children):
    import time

    with tm.span("t.parent"):
        time.sleep(0.002)
        for _ in range(children):
            with tm.span("t.child"):
                time.sleep(0.003)
    parent, child = tm.histogram("t.parent"), tm.histogram("t.child")
    assert child.count == children and child.self_sum == child.sum
    # microsecond floors: one per span
    assert abs(parent.self_sum - (parent.sum - child.sum)) <= children + 1
    assert parent.self_sum >= 2000
    if not children:
        assert parent.self_sum == parent.sum


def test_plain_observe_counts_as_self():
    h = tm.histogram("t.plain")
    h.observe(5)
    h.observe(7)
    assert (h.sum, h.self_sum) == (12, 12)


def test_exception_leaves_the_span_stack_clean():
    tm.enable_spans(True)
    with pytest.raises(ValueError):
        with tm.span("t.raises"):
            with tm.span("t.raises_inner"):
                raise ValueError("boom")
    assert tm._open.stack == []
    # a span entered by hand and never left goes with its parent
    with tm.span("t.tidy"):
        tm.span("t.leaked").__enter__()
    assert tm._open.stack == []
    with tm.span("t.after"):
        pass
    after = [e for e in tm.events() if e["name"] == "t.after"]
    assert after[0]["parent"] is None
    assert tm.histogram("t.raises").count == 1  # still timed


def test_snapshot_carries_self_sum():
    with tm.span("snap.outer"):
        with tm.span("snap.inner"):
            pass
    snap = tm.snapshot()["snap"]
    for leaf in (snap["outer"], snap["inner"]):
        assert {"count", "sum", "self_sum"} <= set(leaf)
    assert snap["inner"]["self_sum"] == snap["inner"]["sum"]
    assert snap["outer"]["self_sum"] <= snap["outer"]["sum"]


def test_span_as_decorator_times_every_call():
    @tm.span("t.decorated")
    def work(x):
        """doc"""
        return x + 1

    assert work(1) == 2 and work(2) == 3
    assert work.__name__ == "work" and work.__doc__ == "doc"
    assert tm.histogram("t.decorated").count == 2


def test_steps_share_an_identifier():
    tm.enable_spans(True)
    for n in (7, 8):
        with tm.span("t.step", step_num=n):
            with tm.span("t.phase"):
                with tm.span("t.leaf", k=1):
                    pass
    ev = tm.events()
    assert [e["args"] for e in ev if e["name"] == "t.step"] == [
        {"step_num": 7}, {"step_num": 8}]
    assert [e["args"] for e in ev if e["name"] == "t.phase"] == [
        {"step": 7}, {"step": 8}]
    assert [e["args"] for e in ev if e["name"] == "t.leaf"] == [
        {"k": 1, "step": 7}, {"k": 1, "step": 8}]


# ---------------------------------------------------------------------------
# the loop's spans: in the profiler's trace, and in the registry
# ---------------------------------------------------------------------------
def _mlp():
    d = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(d, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _mlp_iter(batches, batch_size=8):
    rng = np.random.RandomState(0)
    n = batches * batch_size
    return mx.io.NDArrayIter(
        rng.uniform(size=(n, 4)).astype(np.float32),
        rng.randint(0, 3, (n,)).astype(np.float32), batch_size=batch_size)


def _host_events(trace_dir, names):
    """[(name, start_ns, end_ns, stats)] of the events called one of
    ``names`` in the profiler's trace, read as benchmark/lib/trace.py does."""
    import glob

    from jax.profiler import ProfileData

    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    assert found, "the profiler wrote no .xplane.pb"
    out = []
    for plane in ProfileData.from_file(found[-1]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def test_profiler_trace_holds_the_loop_spans(tmp_path):
    import jax

    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    assert not tm.spans_enabled()  # the trace needs no MXNET_TELEMETRY
    jax.profiler.start_trace(str(tmp_path))
    try:
        mod.fit(_mlp_iter(2), num_epoch=1, optimizer="sgd")
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(str(tmp_path),
                      {"fit.step", "fit.dispatch", "executor.launch"})
    steps = [e for e in ev if e[0] == "fit.step"]
    dispatches = [e for e in ev if e[0] == "fit.dispatch"]
    launches = [e for e in ev if e[0] == "executor.launch"]
    assert len(steps) == 2 and len(dispatches) == 2 and len(launches) >= 2
    # emitted as StepTraceAnnotation emits a step: step_num, and _r
    assert sorted(s[3]["step_num"] for s in steps) == [0, 1]
    assert all(s[3]["_r"] == 1 for s in steps)
    for _, lo, hi, stats in dispatches:
        holder = [s for s in steps if s[1] <= lo and hi <= s[2]]
        assert len(holder) == 1
        assert stats["step"] == holder[0][3]["step_num"]
    for _, lo, hi, _ in launches:
        assert any(d[1] <= lo and hi <= d[2] for d in dispatches) or \
            any(s[1] <= lo and hi <= s[2] for s in steps)


def test_fit_step_count_equals_batches():
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(_mlp_iter(3), num_epoch=2, optimizer="sgd")
    assert tm.counter("fit.batches").value == 6
    assert tm.histogram("fit.step").count == 6
    # the phases are its children: what the iteration itself keeps is small
    step = tm.histogram("fit.step")
    assert step.self_sum < step.sum
    assert tm.histogram("fit.dispatch").count == 6
    assert tm.histogram("executor.launch").count >= 6
    assert tm.histogram("executor.stage_args").count == 6
    assert tm.histogram("module.bind").count == 1
    assert tm.histogram("module.init_params").count == 1
    assert tm.histogram("module.init_optimizer").count == 1
    assert tm.histogram("startup.import").count == 0  # reset by the fixture


def test_two_bucket_fit_counts_binds_and_programs():
    from mxnet_tpu.rnn import BucketSentenceIter

    rng = np.random.RandomState(3)
    sents = [list(rng.randint(1, 20, n)) for n in [3] * 8 + [6] * 8]
    it = BucketSentenceIter(sents, 4, buckets=[4, 8], invalid_label=0)
    assert tm.histogram("rnn.bucket_iter_build").count == 1

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        label = mx.sym.Variable("softmax_label")
        emb = mx.sym.Embedding(data, input_dim=20, output_dim=8, name="emb")
        pred = mx.sym.FullyConnected(
            mx.sym.Reshape(emb, shape=(-1, 8)), num_hidden=20, name="pred")
        out = mx.sym.SoftmaxOutput(pred, mx.sym.Reshape(label, shape=(-1,)),
                                   name="softmax")
        return out, ("data",), ("softmax_label",)

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=8,
                                 context=mx.cpu())
    tm.reset()
    mod.fit(it, num_epoch=2, optimizer="sgd",
            eval_metric=mx.metric.Perplexity(0))
    assert tm.histogram("module.bind").count == 2  # one per bucket
    assert tm.counter("bucketing.compile_on_switch").value == 1
    # every program built was traced and lowered once and compiled once:
    # the fused steps (one plan per bucket) and what went through AOTProgram
    built = (tm.counter("executor.fused_plan_compile").value
             + tm.counter("executor.jit_compile").value)
    assert tm.counter("executor.fused_plan_compile").value == 2
    assert tm.histogram("executor.trace_lower").count == built
    assert tm.histogram("executor.compile").count == built
    assert tm.histogram("fit.step").count == tm.counter("fit.batches").value


def test_steps_in_flight_stays_in_its_ring_and_lets_go(monkeypatch):
    from mxnet_tpu.module import base_module

    made = []

    class Watched(base_module._StepsInFlight):
        RING = 3

        def __init__(self):
            super().__init__()
            made.append(self)

    class NeverReady:
        def is_ready(self):
            return False

    monkeypatch.setattr(base_module, "_StepsInFlight", Watched)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    # steps that never finish: the worst a device can do to the ring
    monkeypatch.setattr(mx.mod.Module, "_step_token",
                        lambda self: NeverReady())
    mod.fit(_mlp_iter(6), num_epoch=1, optimizer="sgd")
    h = tm.histogram("fit.steps_in_flight")
    assert h.count == 6 and h.max == 3 and h.min == 1
    assert len(made) == 1 and len(made[0]._ring) == 0  # nothing kept


def test_steps_in_flight_reads_the_step_counter_the_program_returned():
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(_mlp_iter(4), num_epoch=1, optimizer="sgd")
    token = mod._step_token()
    assert token.shape == () and token.nbytes <= 8  # a scalar, not an output
    assert token.is_ready() in (True, False)
    h = tm.histogram("fit.steps_in_flight")
    assert h.count == 4 and 0 <= h.min and h.max <= 4


# ---------------------------------------------------------------------------
# hot-path wiring
# ---------------------------------------------------------------------------
def test_prefetch_iter_counters():
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(
        rng.uniform(size=(32, 4)).astype(np.float32),
        rng.randint(0, 3, (32,)).astype(np.float32),
        batch_size=8, last_batch_handle="discard")
    pf = mx.io.DevicePrefetchIter(it)
    n = sum(1 for _ in pf)
    pf.close()
    assert n == 4
    assert tm.counter("io.prefetch.batches").value == 4
    assert tm.histogram("io.prefetch.consumer_wait_us").count == 5  # +EOF


def test_metric_counters_device_vs_fallback():
    rng = np.random.RandomState(1)
    p = rng.uniform(0.05, 1.0, (16, 4)).astype(np.float32)
    labels = [mx.nd.array(rng.randint(0, 4, (16,)).astype(np.float32))]
    preds = [mx.nd.array(p / p.sum(axis=1, keepdims=True))]

    m = mx.metric.Accuracy()
    m.device_update(labels, preds)
    assert tm.counter("metric.device_update").value == 1
    assert tm.counter("metric.numpy_fallback").value == 0
    m.get()
    assert tm.counter("metric.drain_sync").value == 1

    class NoDevice(mx.metric.Accuracy):
        def _device_batch(self, label, pred):
            return None

    NoDevice().device_update(labels, preds)
    assert tm.counter("metric.numpy_fallback").value == 1


def test_kvstore_counters():
    kv = mx.kv.create("local")
    a = mx.nd.array(np.ones((4, 4), np.float32))
    kv.init("w", a)
    kv.push("w", mx.nd.array(np.full((4, 4), 2.0, np.float32)))
    out = mx.nd.array(np.zeros((4, 4), np.float32))
    kv.pull("w", out=out)
    assert tm.counter("kvstore.push").value == 1
    assert tm.counter("kvstore.push_bytes").value == 64
    assert tm.counter("kvstore.pull").value == 1
    assert tm.counter("kvstore.pull_bytes").value == 64


def test_executor_jit_compile_counts_compiles_not_launches():
    d = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(d, num_hidden=4, name="fc")
    exe = net.simple_bind(ctx=mx.cpu(), data=(2, 3), grad_req="null")
    tm.reset()
    # forward is lazy: reading an output materializes (and jit-builds) it
    exe.forward(is_train=False, data=mx.nd.array(np.ones((2, 3), np.float32)))
    _ = exe.outputs[0].shape
    compiles = tm.counter("executor.jit_compile").value
    assert compiles >= 1
    exe.forward(is_train=False, data=mx.nd.array(np.ones((2, 3), np.float32)))
    _ = exe.outputs[0].shape
    assert tm.counter("executor.jit_compile").value == compiles  # no recompile


def test_sync_counters_count_blocking_reads():
    a = mx.nd.array(np.ones((2, 2), np.float32))
    base = tm.counter("ndarray.asnumpy").value
    a.asnumpy()
    assert tm.counter("ndarray.asnumpy").value == base + 1
    a.wait_to_read()
    assert tm.counter("ndarray.wait_to_read").value == 1


def test_speedometer_phase_breakdown(caplog):
    import logging as _logging

    from mxnet_tpu.callback import Speedometer

    with tm.span("fit.dispatch"):
        sum(range(1000))

    class Param:
        epoch, nbatch = 0, 1
        eval_metric = None

    s = Speedometer(batch_size=8, frequent=1, phases=True)
    p = Param()
    with caplog.at_level(_logging.INFO):
        s(p)  # arms meter + phase window
        with tm.span("fit.dispatch"):
            sum(range(1000))
        p.nbatch = 2
        s(p)
    assert any("Phases:" in r.message and "dispatch=" in r.message
               for r in caplog.records)


def test_bucketing_switch_counters():
    """switch_bucket mirrors the executor.jit_compile invariant:
    bucketing.switch counts active-bucket changes, and
    bucketing.compile_on_switch counts only switches that had to BIND a
    new bucket — steady-state bucket misses must read as zero."""
    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        emb = mx.sym.Embedding(data, input_dim=10, output_dim=6, name="emb")
        pooled = mx.sym.sum(emb, axis=1)
        net = mx.sym.FullyConnected(pooled, num_hidden=4, name="fc")
        return mx.sym.SoftmaxOutput(net, name="softmax"), ("data",), \
            ("softmax_label",)

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=8)
    mod.bind(data_shapes=[("data", (4, 8))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    tm.reset()
    for key, dshape in [(8, (4, 8)), (4, (4, 4)), (8, (4, 8)), (4, (4, 4))]:
        batch = mx.io.DataBatch(
            data=[mx.nd.ones(dshape)], label=[mx.nd.zeros((4,))],
            bucket_key=key,
            provide_data=[mx.io.DataDesc("data", dshape)],
            provide_label=[mx.io.DataDesc("softmax_label", (4,))],
        )
        mod.forward(batch, is_train=False)
    # 8->4, 4->8, 8->4: three active-bucket changes, ONE new bucket bound
    assert tm.counter("bucketing.switch").value == 3
    assert tm.counter("bucketing.compile_on_switch").value == 1
    # steady state: revisiting bound buckets binds nothing new
    compile_before = tm.counter("bucketing.compile_on_switch").value
    for key, dshape in [(8, (4, 8)), (4, (4, 4))]:
        batch = mx.io.DataBatch(
            data=[mx.nd.ones(dshape)], label=[mx.nd.zeros((4,))],
            bucket_key=key,
            provide_data=[mx.io.DataDesc("data", dshape)],
            provide_label=[mx.io.DataDesc("softmax_label", (4,))],
        )
        mod.forward(batch, is_train=False)
    assert tm.counter("bucketing.compile_on_switch").value == compile_before


def test_stacked_wgrad_counter_counts_groups_per_launch():
    """executor.stacked_wgrad rises by the program's stacked groups at each
    launch of a train program, and a graph with no shared FullyConnected
    weight never moves it."""
    import stacked_wgrad_cases as swc

    tm.reset()
    sym, shapes, _loss, n_groups = swc.recurrent("lstm")
    exe = swc.bound(sym, shapes, swc.values(sym, shapes))
    for launches in (1, 2):
        exe.forward(is_train=True)
        exe.backward()
        exe.grad_dict["l0_i2h_weight"].asnumpy()
        assert tm.counter("executor.stacked_wgrad").value == \
            launches * n_groups
        # beside it, the nodes that read a weight some other node reads too:
        # an i2h and an h2h node a layer and time step (2 x 5 x 2)
        assert tm.counter("executor.shared_weight_reads").value == \
            launches * 20
    exe.forward(is_train=False)  # no gradient, no count
    exe.outputs[0].asnumpy()
    assert tm.counter("executor.stacked_wgrad").value == 2 * n_groups
    assert tm.counter("executor.shared_weight_reads").value == 2 * 20

    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                                name="fc")
    plain = mx.sym.MakeLoss(mx.sym.sum(mx.sym.square(net))).simple_bind(
        mx.cpu(), data=(2, 3))
    plain.forward(is_train=True)
    plain.backward()
    plain.grad_dict["fc_weight"].asnumpy()
    assert swc.n_stacked(plain) == 0
    assert tm.counter("executor.stacked_wgrad").value == 2 * n_groups
    assert tm.counter("executor.shared_weight_reads").value == 2 * 20
