"""bench.py and __graft_entry__ must always run: the driver executes both
at round end, and a crash there loses the round's headline numbers."""

import json
import os
import subprocess
import sys
import tempfile

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_cpu_smoke():
    env = dict(os.environ)
    # tests must not touch a chip: the child runs on the host backend
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [_ROOT, env.get("PYTHONPATH")]))
    env["JAX_PLATFORMS"] = "cpu"
    env["BENCH_LAYERS"] = "18"
    env["BENCH_ITERS"] = "3"
    env["BENCH_WINDOWS"] = "2"
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py")],
        capture_output=True, text=True, env=env, timeout=900, cwd=_ROOT,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    line = r.stdout.strip().splitlines()[-1]
    rec = json.loads(line)
    assert rec["unit"] == "images/sec" and rec["value"] > 0
    assert "cpusmoke" in rec["metric"]
    # the non-finite guard's cost stays visible in every BENCH_*.json
    assert "nonfinite_guard_overhead" in rec
    assert rec["guard_on_img_per_sec"] > 0
    # guard overhead pin, pipelining enabled (windows dispatch with lazy
    # boundary publication): the chip bar is < 2% and is recorded by the
    # BENCH trajectory; this tiny-model CPU smoke measures the same loop
    # with +/-6% host noise (observed), so the pin here is the
    # noise-tolerant band that still catches a structural regression — a
    # guard that re-grew a per-batch sync or fence costs 2x, not 15%
    assert rec["nonfinite_guard_overhead"] < 0.15, rec


def test_bench_fit_mode_reaches_window_rate():
    """BENCH_MODE=fit (real NDArrayIter + Accuracy via Module.fit) must run
    at >=90% of the synthetic train_window throughput on the same config —
    the async-pipeline acceptance bar (device prefetch + device metrics
    leave no per-batch host sync on the fit path)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [_ROOT, env.get("PYTHONPATH")]))
    env["JAX_PLATFORMS"] = "cpu"
    env["BENCH_LAYERS"] = "18"
    env["BENCH_BATCH"] = "4"
    env["BENCH_ITERS"] = "4"
    # 3 timed windows/epochs per mode: the reported value is a median, so a
    # single host hiccup in one window can't sink the comparison
    env["BENCH_WINDOWS"] = "3"
    # the guard-overhead re-measure is test_bench_cpu_smoke's job; here it
    # would only stretch the train-mode run this comparison waits on
    env["BENCH_GUARD"] = "0"
    # kernel attribution is pinned by the guard-on test; the profiled
    # window would only stretch this throughput comparison
    env["BENCH_KERNELS"] = "0"

    def run(mode):
        e = dict(env)
        e["BENCH_MODE"] = mode
        r = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "bench.py")],
            capture_output=True, text=True, env=e, timeout=900, cwd=_ROOT,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    fit = run("fit")
    assert "fit" in fit["metric"]
    window = run("train")
    fit_rate = fit["value"]
    if fit_rate < 0.9 * window["value"]:
        # shared-host noise guard: one re-measure before declaring a
        # pipeline regression
        fit_rate = max(fit_rate, run("fit")["value"])
    assert fit_rate >= 0.9 * window["value"], (
        f"fit loop at {fit_rate} img/s vs train_window "
        f"{window['value']} img/s — async pipeline regressed")


def test_bench_fit_guard_on_keeps_no_sync_invariant():
    """With MXNET_NONFINITE_GUARD=skip AND pipelined window dispatch, the
    fit loop's steady-state telemetry (embedded in the bench record) must
    show ZERO host-blocking syncs — the guard's skip decision lives on
    device and never reads back per batch — and the guard must NOT cap
    the pipeline: dispatch depth stays >= 2 (the gauge) with >= 2 windows
    actually observed in flight. Only the rollback/raise policies may
    fence to depth 1 (documented boundary-fence classes)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [_ROOT, env.get("PYTHONPATH")]))
    env["JAX_PLATFORMS"] = "cpu"
    env["BENCH_LAYERS"] = "18"
    env["BENCH_BATCH"] = "4"
    env["BENCH_ITERS"] = "4"
    env["BENCH_WINDOWS"] = "2"
    env["BENCH_MODE"] = "fit"
    env["BENCH_WARM_START"] = "0"
    env["MXNET_NONFINITE_GUARD"] = "skip"
    env["MXNET_TRAIN_WINDOW"] = "2"
    env["MXNET_DISPATCH_DEPTH"] = "2"
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py")],
        capture_output=True, text=True, env=env, timeout=900, cwd=_ROOT,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    nd = rec["telemetry"].get("ndarray", {})
    assert nd.get("asnumpy", 0) == 0, rec["telemetry"]
    assert nd.get("wait_to_read", 0) == 0, rec["telemetry"]
    metric = rec["telemetry"].get("metric", {})
    assert metric.get("numpy_fallback", 0) == 0, rec["telemetry"]
    # pipelined dispatch pins (cpu-smoke fit mode): configured depth on
    # the gauge, achieved depth on the in-flight high-water mark, and the
    # JSON tail fields the trajectory reads
    fit = rec["telemetry"].get("fit", {})
    assert fit.get("dispatch_depth", {}).get("value", 0) >= 2, rec
    assert fit.get("windows_in_flight", {}).get("max", 0) >= 2, rec
    assert fit.get("window", {}).get("count", 0) >= 2, rec
    assert rec.get("dispatch_depth", 0) >= 2, rec
    assert rec.get("train_window_k", 0) == 2, rec
    assert 0 < rec.get("dispatch_span_share", 0) <= 1, rec
    # device-side attribution contract (ISSUE 18): every fit record names
    # its conv layout + precision recipe and embeds the top-10 per-kernel
    # device-time table (attributed AFTER the timed region)
    assert rec["layout"] in ("NCHW", "NHWC"), rec
    assert rec["recipe"] in ("f32", "bf16_master"), rec
    kernels = rec["kernels"]
    assert 0 < len(kernels) <= 10, kernels
    total_pct = 0.0
    for row in kernels:
        assert row["name"] and row["device_us"] > 0 and row["calls"] >= 1
        assert 0 <= row["pct"] <= 1
        total_pct += row["pct"]
    assert total_pct <= 1.0 + 1e-6, kernels
    # sorted by device time, heaviest first
    assert all(a["device_us"] >= b["device_us"]
               for a, b in zip(kernels, kernels[1:])), kernels


def test_bench_serve_mode_beats_sequential_and_never_compiles():
    """BENCH_MODE=serve: the dynamic batcher under concurrent synthetic
    load must (a) reach at least the batch-size-1 sequential predictor
    throughput — batching that loses to no batching is a regression —
    and (b) perform ZERO XLA compiles on the request path (the embedded
    telemetry snapshot's executor.jit_compile / aot counters cover the
    whole traffic window; every bucket executable was warmed up front)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [_ROOT, env.get("PYTHONPATH")]))
    env["JAX_PLATFORMS"] = "cpu"
    env["BENCH_MODE"] = "serve"
    env["BENCH_LAYERS"] = "18"
    env["BENCH_SERVE_CLIENTS"] = "6"
    env["BENCH_SERVE_REQUESTS"] = "8"
    env["BENCH_SERVE_SEQ_ITERS"] = "6"

    def run():
        r = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "bench.py")],
            capture_output=True, text=True, env=env, timeout=900,
            cwd=_ROOT,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    rec = run()
    assert "serving_throughput" in rec["metric"]
    assert rec["errors"] == 0
    assert rec["value"] > 0 and rec["p99_ms"] >= rec["p50_ms"] > 0
    # no-request-path-compile invariant: the snapshot covers traffic only
    ex = rec["telemetry"].get("executor", {})
    assert ex.get("jit_compile", 0) == 0, rec["telemetry"]
    aot = rec["telemetry"].get("aot", {})
    assert aot.get("trace_compile", 0) == 0, rec["telemetry"]
    assert rec["telemetry"]["serving"]["batches"] > 0
    rate = rec["value"]
    if rate < rec["sequential_img_per_sec"]:
        # shared-host noise guard: one re-measure before failing — the
        # retry stands on its own (its value vs its OWN sequential
        # baseline; mixing runs could pass when both individually failed)
        rec = run()
        rate = rec["value"]
    assert rate >= rec["sequential_img_per_sec"], (
        f"batcher at {rate} img/s lost to sequential batch-1 "
        f"{rec['sequential_img_per_sec']} img/s")


def test_bench_serve_sharded_legs_no_compile_and_curve():
    """BENCH_SERVE_SHARDED=1 on the virtual 8-device CPU mesh: every
    mesh leg (tp2 / pp2 / dp-of-tp2) serves with ZERO request-path
    compiles and zero errors, dp-of-tp2 actually fans out to 4 group
    replicas, and the tp2 scaling curve is reported at 1/2/4 groups.
    (The curve's SLOPE is the TPU round's acceptance — virtual CPU
    devices share host cores, so only structure is pinned here.)"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [_ROOT, env.get("PYTHONPATH")]))
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["BENCH_MODE"] = "serve"
    env["BENCH_LAYERS"] = "18"
    env["BENCH_SERVE_CLIENTS"] = "4"
    env["BENCH_SERVE_REQUESTS"] = "6"
    env["BENCH_SERVE_SEQ_ITERS"] = "2"
    env["BENCH_SERVE_SCALING"] = "0"
    env["BENCH_SERVE_SHARDED"] = "1"
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py")],
        capture_output=True, text=True, env=env, timeout=900, cwd=_ROOT,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    sharded = rec["sharded"]
    for name in ("tp2", "pp2", "dp-tp2"):
        leg = sharded[name]
        assert leg["errors"] == 0, (name, leg)
        assert leg["request_path_compiles"] == 0, (name, leg)
        assert leg["img_per_sec"] > 0, (name, leg)
        assert leg["p99_ms"] > 0, (name, leg)
    assert sharded["tp2"]["replicas"] == 1
    assert sharded["pp2"]["replicas"] == 1
    assert sharded["dp-tp2"]["replicas"] == 4
    curve = sharded["tp2_scaling_curve"]
    assert sorted(curve) == ["1", "2", "4"]
    assert all(v > 0 for v in curve.values()), curve
    assert sharded["group_scaling_4x"] > 0


def test_bench_serve_chaos_availability():
    """BENCH_CHAOS=1 serve leg: a replica killed under concurrent traffic
    and later revived must cost availability NOTHING (failover absorbs
    it) — pinned >= 0.99 per the serving SLO — with the fault window's
    p99 reported and at least one counted failover re-dispatch."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [_ROOT, env.get("PYTHONPATH")]))
    env["JAX_PLATFORMS"] = "cpu"
    # >= 2 virtual devices so the pool has a survivor to fail over to
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["BENCH_MODE"] = "serve"
    env["BENCH_CHAOS"] = "1"
    env["BENCH_LAYERS"] = "18"
    env["BENCH_SERVE_BUCKETS"] = "1,4"
    env["BENCH_SERVE_CLIENTS"] = "4"
    env["BENCH_SERVE_REQUESTS"] = "6"
    env["BENCH_SERVE_SEQ_ITERS"] = "2"
    env["BENCH_SERVE_SCALING"] = "0"  # scaling leg is the TPU round's job
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py")],
        capture_output=True, text=True, env=env, timeout=900, cwd=_ROOT,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["replicas"] == 2
    assert rec["errors"] == 0  # the clean measurement phase
    assert rec["availability"] >= 0.99, rec["chaos"]
    assert rec["chaos"]["failed"] == 0, rec["chaos"]
    assert rec["chaos"]["failover_count"] >= 1, (
        "replica kill never exercised failover")
    assert rec["p99_during_fault_ms"] > 0
    # both replicas actually served during the clean phase
    assert all(v > 0 for v in rec["per_replica_batches"].values()), rec


def _bench_env(**overrides):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [_ROOT, env.get("PYTHONPATH")]))
    env["JAX_PLATFORMS"] = "cpu"
    env.update(overrides)
    return env


def _run_bench(env):
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py")],
        capture_output=True, text=True, env=env, timeout=900, cwd=_ROOT,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


_SUITE_SMOKE_KNOBS = {
    "BENCH_MODE": "suite",
    # trimmed timed region: the smoke pins are structural (presence,
    # steady_compiles==0 counter-verified, finite outputs) — per-workload
    # compile time dominates this leg regardless of window count, and the
    # tier-1 wall budget pays for it once, here (the bf16-no-NaN pin
    # lives in test_whole_zoo_fastpath.py where it costs seconds, not a
    # second bf16 compile of every trunk)
    "BENCH_SUITE_WINDOWS": "2",
    "BENCH_SUITE_WARMUP": "1",
    "BENCH_SUITE_INFER_ITERS": "1",
}

_SUITE_WORKLOADS = ("mlp", "lenet", "resnet-50", "lstm-ptb", "ssd-vgg16",
                    "dcgan")


def test_bench_suite_whole_zoo_smoke():
    """BENCH_MODE=suite: EVERY BASELINE workload must appear in the one
    scoreboard record with the fast-path invariants intact — zero
    steady-state compiles (the counters bench embeds per workload), finite
    training outputs, per-symbol FLOPs populated — and the DCGAN fused
    window must beat the reference imperative loop."""
    rec = _run_bench(_bench_env(**_SUITE_SMOKE_KNOBS))
    assert "whole_zoo_suite" in rec["metric"]
    assert "cpusmoke" in rec["metric"]
    assert rec["unit"] == "geomean train samples/sec" and rec["value"] > 0
    assert set(rec["workloads"]) == set(_SUITE_WORKLOADS)
    for name, w in rec["workloads"].items():
        assert w["train_samples_per_sec"] > 0, (name, w)
        assert w["infer_samples_per_sec"] > 0, (name, w)
        # the zero-recompile invariant, counter-verified over the timed
        # region (executor.jit_compile + executor.fused_plan_compile)
        assert w["steady_compiles"] == 0, (name, w)
        assert w["train_outputs_finite"] is True, (name, w)
        assert w["gflops_per_sample_fwd"] > 0, (name, w)
        assert w["window_k"] >= 2 and w["dispatch_depth"] >= 2, (name, w)
        assert w["dtype"] in ("float32", "bfloat16"), (name, w)
    # device-side attribution (ISSUE 18): the suite record is stamped
    # with its layout + recipe, and the flagship resnet-50 leg embeds the
    # per-kernel device-time top-10 ("where did the step time go")
    assert rec["layout"] in ("NCHW", "NHWC"), rec
    assert rec["recipe"] in ("f32", "bf16_master"), rec
    kernels = rec["workloads"]["resnet-50"]["kernels"]
    assert 0 < len(kernels) <= 10, kernels
    for row in kernels:
        assert row["name"] and row["device_us"] > 0 and row["calls"] >= 1
        assert 0 <= row["pct"] <= 1
    dcgan = rec["workloads"]["dcgan"]
    assert dcgan["legacy_train_samples_per_sec"] > 0
    speedup = dcgan["fused_speedup"]
    if speedup < 1.0:
        # shared-host noise guard: one dcgan-only re-measure (with the
        # default deeper timed region) before declaring the fused window
        # lost to the imperative loop
        rec2 = _run_bench(_bench_env(BENCH_MODE="suite",
                                     BENCH_SUITE_WORKLOADS="dcgan"))
        speedup = max(speedup, rec2["workloads"]["dcgan"]["fused_speedup"])
    assert speedup >= 1.0, (
        f"fused G/D window at {speedup}x of the legacy loop — "
        f"the whole-zoo fast path regressed for dcgan")


def test_bench_score_sweep_smoke():
    """BENCH_MODE=score: the benchmark_score.py-parity sweep as one
    gateable record — a subset here (the full 14-symbol table is the TPU
    round's run; the registry itself is pinned in
    test_whole_zoo_fastpath.py)."""
    rec = _run_bench(_bench_env(BENCH_MODE="score",
                                BENCH_SCORE_NETS="mlp,lenet",
                                BENCH_ITERS="2", BENCH_SCORE_BATCH="2"))
    assert "zoo_score_sweep" in rec["metric"]
    assert "cpusmoke" in rec["metric"]
    assert rec["unit"] == "geomean images/sec" and rec["value"] > 0
    assert set(rec["networks"]) == {"mlp", "lenet"}
    for name, n in rec["networks"].items():
        assert n["samples_per_sec"] > 0, (name, n)
    assert rec["networks"]["lenet"]["gflops_per_sample_fwd"] > 0


def test_score_symbol_list_is_shared():
    """bench.py's score mode and examples/benchmark_score.py must sweep
    the SAME registry (models.SCORE_SYMBOLS) — two drifting symbol lists
    would make the scoreboard and the example disagree about 'the zoo'."""
    sys.path.insert(0, _ROOT)
    from mxnet_tpu import models

    assert len(models.SCORE_SYMBOLS) >= 14
    for fname in ("bench.py", os.path.join("examples",
                                           "benchmark_score.py")):
        with open(os.path.join(_ROOT, fname)) as f:
            assert "SCORE_SYMBOLS" in f.read(), (
                f"{fname} no longer reads the shared symbol registry")


def test_bench_io_mode_scaling_curve():
    """BENCH_MODE=io: the decode-plane record must carry the full
    worker-scaling curve, the serial baseline, the gated pool_speedup
    ratio and a flowing io.plane.* telemetry snapshot. The pool(>=4) >=
    2x serial pin applies only where parallel decode is physically
    possible (>= 4 host cores); on fewer cores no thread pool can beat
    serial decode, so — exactly like the sharded-serve smoke, whose
    curve slope is also the TPU round's acceptance — this box pins
    structure plus bounded pool overhead instead."""
    knobs = dict(BENCH_MODE="io", BENCH_IO_RECORDS="224",
                 BENCH_IO_WORKERS="1,2,4")
    rec = _run_bench(_bench_env(**knobs))
    assert "io_plane_decode" in rec["metric"]
    assert "cpusmoke" in rec["metric"]
    assert rec["unit"] == "images/sec" and rec["value"] > 0
    assert rec["serial_img_per_sec"] > 0
    assert sorted(rec["scaling"]) == ["1", "2", "4"]
    assert all(v > 0 for v in rec["scaling"].values()), rec["scaling"]
    plane = rec["telemetry"]["io"]["plane"]
    assert plane["batches"] > 0 and plane["records"] > 0
    # absent from the snapshot when never incremented — a clean run
    assert plane.get("worker_crash", 0) == 0
    assert plane.get("worker_stall", 0) == 0
    speedup = rec["pool_speedup"]
    # the bar the ISSUE states, applied where it is measurable; one
    # re-measure before failing (shared-host noise guard)
    floor = 2.0 if os.cpu_count() >= 4 else 0.6
    if speedup < floor:
        speedup = max(speedup, _run_bench(_bench_env(**knobs))["pool_speedup"])
    assert speedup >= floor, (
        f"decode pool at {speedup}x of serial on {os.cpu_count()} cores "
        f"(floor {floor}x) — the parallel plane regressed")


def test_bench_fit_recordio_leg():
    """BENCH_FIT_DATA=recordio: Module.fit trained from a generated
    RecordIO file through the full decode pool + prefetch stack must
    reach >= 70% of the synthetic (in-memory NDArrayIter) fit rate —
    the input plane keeps the chip fed."""
    knobs = dict(BENCH_MODE="fit", BENCH_LAYERS="18", BENCH_BATCH="4",
                 BENCH_ITERS="3", BENCH_WINDOWS="2", BENCH_GUARD="0",
                 BENCH_WARM_START="0", BENCH_KERNELS="0")
    syn = _run_bench(_bench_env(**knobs))
    rec = _run_bench(_bench_env(BENCH_FIT_DATA="recordio", **knobs))
    assert rec["fit_data"] == "recordio"
    assert "recordio" in rec["metric"]
    rate = rec["value"]
    if rate < 0.7 * syn["value"]:
        # shared-host noise guard: one re-measure before declaring the
        # decode plane unable to feed the training loop
        rate = max(rate, _run_bench(
            _bench_env(BENCH_FIT_DATA="recordio", **knobs))["value"])
    assert rate >= 0.7 * syn["value"], (
        f"recordio fit at {rate} img/s vs synthetic {syn['value']} "
        f"img/s — the decode plane starves the training loop")


@pytest.mark.slow
def test_bench_xla_flag_sweep_smoke():
    """BENCH_SWEEP=xla: the compiler-flag sweep must try every candidate
    from BENCH_SWEEP_XLA through MXNET_XLA_FLAGS (a rebuilt module per
    candidate — the flags feed compile options AND the AOT fingerprint),
    record the per-candidate table, and adopt a winner. slow-marked: a
    sweep is an extra fit compile per candidate on top of the headline
    run; the flag-threading itself is unit-pinned in test_executor.py."""
    rec = _run_bench(_bench_env(
        BENCH_MODE="fit", BENCH_LAYERS="18", BENCH_BATCH="4",
        BENCH_ITERS="2", BENCH_WINDOWS="1", BENCH_WARM_START="0",
        BENCH_KERNELS="0", BENCH_SWEEP="xla",
        BENCH_SWEEP_XLA="xla_cpu_enable_fast_math=true"))
    sweep = rec["sweep"]
    assert sweep and sweep[0]["xla_flags"] == "xla_cpu_enable_fast_math=true"
    assert sweep[0]["img_per_sec"] > 0, sweep
    assert "best_xla_flags" in rec, rec
    assert rec["value"] > 0


def test_hlo_audit_fused_window_clean():
    """tools/hlo_audit.py on the fused resnet-18 window program: every
    donated buffer must be aliased in the compiled executable (zero
    un-aliased donations, zero silently dropped marks) and the bf16
    recipe must show no stray f32 upcasts beyond the per-step gradient
    promotions the master-weight design requires."""
    env = _bench_env(MXNET_AOT_CACHE="0")
    out = os.path.join(tempfile.mkdtemp(prefix="hlo_audit_"), "verdict.json")
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "hlo_audit.py"),
         "--layers", "18", "--batch", "2", "--window", "2", "--json", out],
        capture_output=True, text=True, env=env, timeout=900, cwd=_ROOT,
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    with open(out) as f:
        verdict = json.load(f)
    assert verdict["ok"] is True, verdict
    assert verdict["unaliased_donations"] == [], verdict
    assert verdict["dropped_donations"] == 0, verdict
    assert verdict["donated_args"] > 0, verdict
    assert verdict["aliased_args"] + verdict["donor_args"] \
        == verdict["donated_args"], verdict
    assert verdict["stray_upcasts"] == {}, verdict


def test_graft_entry_single_chip_compiles():
    """entry() returns a jittable forward; eval_shape validates the trace
    without paying device compile time."""
    import jax

    sys.path.insert(0, _ROOT)
    import __graft_entry__ as g

    fn, (args, auxs) = g.entry()
    out = jax.eval_shape(fn, args, auxs)
    assert tuple(out.shape) == (8, 1000)
