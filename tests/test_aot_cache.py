"""AOT subsystem tests: persistent executable cache contract, warmup APIs,
and the adaptive train-window scheduler.

The cache contract is the PR's acceptance bar: populate the cache
(tools/aot_warm.py), spawn a FRESH process, and the reload must bind + run
the bench-model family with ``executor.jit_compile == 0`` — every
steady-state program deserializes instead of recompiling. Serialization
tests carry the ``aot_serialization`` marker; conftest skips them on
backends that cannot serialize executables.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import aot
import mxnet_tpu.telemetry as tm

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _subprocess_env(cache_dir):
    """JAX_PLATFORMS=cpu (a spawned jax-initialising child must not reach
    for an accelerator the parent may hold) + the AOT cache pointed at
    tmp."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [_ROOT, env.get("PYTHONPATH")]))
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_AOT_CACHE"] = "1"
    env["MXNET_AOT_CACHE_DIR"] = str(cache_dir)
    return env


@pytest.mark.aot_serialization
def test_persistent_cache_fresh_process_zero_compiles(tmp_path):
    """aot_warm populates the cache for the bench-model family; a fresh
    process then binds + runs forward/train-step/fused-update with
    executor.jit_compile == 0 and aot.cache_hit > 0."""
    env = _subprocess_env(tmp_path / "aot")
    warm = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "aot_warm.py"),
         "resnet", "--data-shape", "2,3,32,32",
         "--model-arg", "num_classes=10", "--model-arg", "num_layers=18",
         "--model-arg", "image_shape=3,32,32", "--step"],
        capture_output=True, text=True, env=env, timeout=600, cwd=_ROOT,
    )
    assert warm.returncode == 0, warm.stderr[-2000:]
    cache_files = os.listdir(tmp_path / "aot")
    assert len(cache_files) >= 3, cache_files  # fwd eval/train + step + fused

    reload = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tests", "aot_cache_worker.py")],
        capture_output=True, text=True, env=env, timeout=600, cwd=_ROOT,
    )
    assert reload.returncode == 0, reload.stderr[-2000:]
    rec = json.loads(reload.stdout.strip().splitlines()[-1])
    assert rec["jit_compile"] == 0, rec  # warm start: XLA never ran
    assert rec["cache_hit"] >= 3, rec   # train_step + fused + eval forward
    assert rec["deserialize_error"] == 0, rec
    assert rec["grad_norm"] > 0 and rec["out_shape"] == [2, 10], rec


@pytest.mark.aot_serialization
def test_aot_warm_cli_smoke(tmp_path):
    """The warm CLI runs standalone on a tiny zoo model, populates the
    cache dir, and a second invocation is all hits (idempotent)."""
    env = _subprocess_env(tmp_path / "aot")
    cmd = [sys.executable, os.path.join(_ROOT, "tools", "aot_warm.py"),
           "mlp", "--data-shape", "4,784", "--model-arg", "num_classes=10",
           "--step"]
    first = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           timeout=600, cwd=_ROOT)
    assert first.returncode == 0, first.stderr[-2000:]
    assert "stores=4" in first.stdout, first.stdout
    n_files = len(os.listdir(tmp_path / "aot"))
    assert n_files >= 4
    second = subprocess.run(cmd, capture_output=True, text=True, env=env,
                            timeout=600, cwd=_ROOT)
    assert second.returncode == 0, second.stderr[-2000:]
    assert "hits=4" in second.stdout, second.stdout
    assert len(os.listdir(tmp_path / "aot")) == n_files  # nothing re-stored


@pytest.mark.aot_serialization
def test_corrupt_cache_entry_recompiles(tmp_path, monkeypatch):
    """A corrupt cache file reads as a miss (deserialize_error counted,
    entry removed) and the program recompiles + re-persists."""
    monkeypatch.setenv("MXNET_AOT_CACHE", "1")
    monkeypatch.setenv("MXNET_AOT_CACHE_DIR", str(tmp_path))
    d = aot.digest("probe-corrupt")
    path = os.path.join(aot.cache_dir(), d + ".aotx")
    os.makedirs(aot.cache_dir(), exist_ok=True)
    with open(path, "wb") as f:
        f.write(b"not a pickle")
    errs = tm.counter("aot.deserialize_error").value
    assert aot.load(d) is None
    assert tm.counter("aot.deserialize_error").value == errs + 1
    assert not os.path.exists(path)  # poisoned entry evicted

    import jax
    import jax.numpy as jnp

    compiled = jax.jit(lambda x: x * 3).lower(jnp.ones((2,))).compile()
    assert aot.store(d, compiled)
    loaded = aot.load(d)
    assert loaded is not None
    np.testing.assert_allclose(np.asarray(loaded(jnp.ones((2,)))), 3.0)


def _mlp_module(batch=8):
    d = mx.sym.Variable("data")
    l = mx.sym.Variable("softmax_label")
    h = mx.sym.FullyConnected(d, num_hidden=16, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(h, num_hidden=10, name="fc2"), label=l,
        name="softmax")
    m = mx.mod.Module(net, context=mx.cpu())
    m.bind(data_shapes=[mx.io.DataDesc("data", (batch, 32))],
           label_shapes=[mx.io.DataDesc("softmax_label", (batch,))])
    m.init_params(initializer=mx.init.Xavier(), force_init=True)
    return m


def test_module_compile_warms_all_programs():
    """Module.compile pre-builds forward/forward_train/train_step; the
    subsequent first steps are all in-memory executable hits (no further
    XLA compiles)."""
    m = _mlp_module()
    tm.reset()
    kinds = m.compile()
    assert kinds == ["forward", "forward_train", "train_step"]
    compiles = tm.counter("executor.jit_compile").value
    assert compiles == 3
    rng = np.random.RandomState(0)
    b = mx.io.DataBatch(data=[mx.nd.array(rng.randn(8, 32))],
                        label=[mx.nd.array(rng.randint(0, 10, (8,)))])
    m.forward(b, is_train=True)
    m.backward()
    _ = m._exec_group._exec.grad_dict["fc1_weight"].asnumpy()
    m.forward(b, is_train=False)
    _ = m.get_outputs()[0].asnumpy()
    assert tm.counter("executor.jit_compile").value == compiles


def test_bucketing_compile_warms_buckets_in_parallel():
    """BucketingModule.compile binds + pre-compiles the given bucket set
    (thread pool; XLA compilation releases the GIL); running each bucket
    afterwards triggers no new jit compiles."""
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
    compiled = mod.compile(
        buckets=[(4, [("data", (4, 4))], [("softmax_label", (4,))])])
    assert set(compiled) == {8, 4}
    assert all("forward" in kinds for kinds in compiled.values())
    tm.reset()
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    for key, dshape in [(8, (4, 8)), (4, (4, 4))]:
        batch = mx.io.DataBatch(
            data=[mx.nd.ones(dshape)], label=[mx.nd.zeros((4,))],
            bucket_key=key,
            provide_data=[mx.io.DataDesc("data", dshape)],
            provide_label=[mx.io.DataDesc("softmax_label", (4,))],
        )
        mod.forward(batch, is_train=False)
        _ = mod.get_outputs()[0].asnumpy()
    assert tm.counter("executor.jit_compile").value == 0


# --- adaptive train-window scheduler ---------------------------------------

def test_choose_train_window_dispatch_bound_picks_deep_window():
    # synthetic dispatch-bound profile: 3 ms dispatch vs 0.5 ms residual
    k = aot.choose_train_window(3000.0, 500.0)
    assert k >= 2
    # fully dispatch-bound (no residual at all): cap at max_k
    assert aot.choose_train_window(3000.0, 0.0, max_k=32) == 32


def test_choose_train_window_device_bound_stays_serial():
    # device/data-bound: dispatch is a rounding error next to the residual
    assert aot.choose_train_window(100.0, 40000.0) == 1
    assert aot.choose_train_window(0.0, 1000.0) == 1


def test_scheduler_auto_decides_from_synthetic_telemetry():
    """TrainWindowScheduler('auto') probes single-step, then locks K from
    the fit.* histograms: dispatch-bound profiles get K >= 2,
    device-bound ones stay at 1."""
    def run(dispatch_us, data_wait_us):
        tm.reset()
        sched = aot.TrainWindowScheduler("auto")
        skip = sched.SKIP_BATCHES
        probe = sched.PROBE_BATCHES
        for _i in range(skip + probe):
            assert sched.next_k() == 1  # probing single-step
            tm.histogram("fit.dispatch").observe(dispatch_us)
            tm.histogram("fit.data_wait").observe(data_wait_us)
            sched.observe(1)
        return sched.next_k()

    assert run(dispatch_us=3000, data_wait_us=300) >= 2
    assert run(dispatch_us=100, data_wait_us=40000) == 1
    assert tm.gauge("fit.train_window_k").value == 1  # decision published


def test_scheduler_restarts_probe_on_partial_telemetry_reset():
    """A telemetry reset mid-probe (bench's compile-epoch reset) can leave
    the dispatch delta positive but a residual delta negative; the
    scheduler must restart the probe instead of reading residual<=0 as
    'fully dispatch-bound' and locking max_k on a device-bound loop."""
    tm.reset()
    sched = aot.TrainWindowScheduler("auto")
    for _ in range(sched.SKIP_BATCHES):
        sched.next_k()
        tm.histogram("fit.dispatch").observe(100)
        tm.histogram("fit.data_wait").observe(40000)
        sched.observe(1)
    sched.next_k()  # takes the rebase
    for _ in range(sched.PROBE_BATCHES):
        tm.histogram("fit.dispatch").observe(100)
        tm.histogram("fit.data_wait").observe(40000)
        sched.observe(1)
    # simulate the mid-probe reset: data_wait loses its accumulated sum
    tm.histogram("fit.data_wait")._zero()
    tm.histogram("fit.dispatch")._zero()
    for _ in range(3):  # dispatch count recovers past the base, sum low
        tm.histogram("fit.dispatch").observe(100)
    assert sched.next_k() == 1          # probe restarted, not K=max
    assert not sched._decided


def test_scheduler_fixed_setting_and_env_parse(monkeypatch):
    assert aot.TrainWindowScheduler(4).next_k() == 4
    monkeypatch.setenv("MXNET_TRAIN_WINDOW", "auto")
    assert aot.train_window_setting() == "auto"
    monkeypatch.setenv("MXNET_TRAIN_WINDOW", "8")
    assert aot.train_window_setting() == 8
    for off in ("", "0", "1", "none", "garbage"):
        monkeypatch.setenv("MXNET_TRAIN_WINDOW", off)
        assert aot.train_window_setting() is None


def test_choose_dispatch_depth_profiles():
    # double buffering is the baseline whenever windows engage
    assert aot.choose_dispatch_depth(500.0, 3000.0) == 2
    # dispatch-dominated host loop (per-execute host cost): one extra window
    # of slack absorbs host-time bursts
    assert aot.choose_dispatch_depth(3000.0, 500.0) == 3
    assert aot.choose_dispatch_depth(3000.0, 500.0, max_depth=2) == 2
    # no profile at all: still double-buffer
    assert aot.choose_dispatch_depth(0.0, 0.0) == 2


def test_dispatch_depth_env_parse(monkeypatch):
    monkeypatch.delenv("MXNET_DISPATCH_DEPTH", raising=False)
    assert aot.dispatch_depth_setting() == "auto"
    monkeypatch.setenv("MXNET_DISPATCH_DEPTH", "auto")
    assert aot.dispatch_depth_setting() == "auto"
    monkeypatch.setenv("MXNET_DISPATCH_DEPTH", "3")
    assert aot.dispatch_depth_setting() == 3
    monkeypatch.setenv("MXNET_DISPATCH_DEPTH", "0")
    assert aot.dispatch_depth_setting() == 1  # floor: a depth must exist
    monkeypatch.setenv("MXNET_DISPATCH_DEPTH", "junk")
    assert aot.dispatch_depth_setting() == "auto"


def test_scheduler_co_tunes_k_and_depth(monkeypatch):
    """Auto scheduling resolves (K, depth) together from the probe: a
    dispatch-bound profile gets deep-ish windows AND depth >= 2, with K
    SMALLER than the unpipelined choice (the in-flight overlap already
    hides the round trip); device-bound stays (1, 1). cap_depth forces a
    fenced pipeline and says why."""
    monkeypatch.delenv("MXNET_DISPATCH_DEPTH", raising=False)

    def run(dispatch_us, data_wait_us):
        tm.reset()
        sched = aot.TrainWindowScheduler("auto")
        for _i in range(sched.SKIP_BATCHES + sched.PROBE_BATCHES):
            sched.next_k()
            tm.histogram("fit.dispatch").observe(dispatch_us)
            tm.histogram("fit.data_wait").observe(data_wait_us)
            sched.observe(1)
        return sched.next_k(), sched

    k, sched = run(dispatch_us=3000, data_wait_us=300)
    assert k >= 2 and sched.depth >= 2
    assert tm.gauge("fit.dispatch_depth").value == sched.depth
    assert k <= aot.choose_train_window(3000, 300)  # co-tuned K relaxes
    k1, sched1 = run(dispatch_us=100, data_wait_us=40000)
    assert k1 == 1 and sched1.depth == 1
    # policy cap: depth 1, reason recorded, gauge says so
    k2, sched2 = run(dispatch_us=3000, data_wait_us=300)
    sched2.cap_depth("nonfinite-rollback")
    assert sched2.depth == 1
    assert sched2.depth_cap_reason == "nonfinite-rollback"
    assert tm.gauge("fit.dispatch_depth").value == 1
    # a fixed env depth is honored without a probe
    monkeypatch.setenv("MXNET_DISPATCH_DEPTH", "3")
    assert aot.TrainWindowScheduler(4).depth == 3
    # ...but K=1 means no windows: a fixed depth must not make the gauge
    # claim a pipeline the per-batch loop cannot deliver
    k3, sched3 = run(dispatch_us=100, data_wait_us=40000)
    assert k3 == 1 and sched3.depth == 1
    assert tm.gauge("fit.dispatch_depth").value == 1


def test_fit_with_fixed_window_matches_serial_trajectory(monkeypatch):
    """MXNET_TRAIN_WINDOW=K in fit dispatches train_window chunks and
    trains the same trajectory as the per-batch loop."""
    from mxnet_tpu.executor import Executor

    monkeypatch.delenv("MXNET_TRAIN_WINDOW", raising=False)
    rng = np.random.RandomState(3)
    data = rng.randn(32, 32).astype(np.float32)
    label = rng.randint(0, 10, (32,)).astype(np.float32)

    def fit_one():
        m = _mlp_module()
        it = mx.io.NDArrayIter(data, label, batch_size=8,
                               label_name="softmax_label")
        m.fit(it, num_epoch=2, eval_metric="acc",
              initializer=mx.init.Xavier(),
              optimizer_params={"learning_rate": 0.1})
        return m

    mx.random.seed(11)
    m_ref = fit_one()

    calls = []
    orig = Executor.fused_train_update

    def spy(exe, *a, **kw):
        calls.append(kw.get("n_steps", 1))
        return orig(exe, *a, **kw)

    monkeypatch.setattr(Executor, "fused_train_update", spy)
    monkeypatch.setenv("MXNET_TRAIN_WINDOW", "4")
    mx.random.seed(11)
    m_win = fit_one()
    assert 4 in calls, f"no window dispatch: {calls}"
    a_ref, x_ref = m_ref.get_params()
    a_win, x_win = m_win.get_params()
    for k in a_ref:
        np.testing.assert_allclose(a_ref[k].asnumpy(), a_win[k].asnumpy(),
                                   rtol=2e-5, atol=2e-5, err_msg=k)


def test_aot_program_falls_back_on_exec_mismatch():
    """An AOTProgram whose executable rejects the arguments permanently
    falls back to the jit path (never a user-visible failure)."""
    import jax
    import jax.numpy as jnp

    prog = aot.AOTProgram(jax.jit(lambda x: x + 1))
    np.testing.assert_allclose(np.asarray(prog(jnp.ones((2,)))), 2.0)
    assert prog.executable is not None
    base = tm.counter("aot.exec_fallback").value
    # different shape: the compiled executable rejects it, jit re-traces
    np.testing.assert_allclose(np.asarray(prog(jnp.ones((3, 3)))), 2.0)
    assert tm.counter("aot.exec_fallback").value == base + 1
    # and stays on the jit path from then on
    np.testing.assert_allclose(np.asarray(prog(jnp.ones((2,)))), 2.0)
