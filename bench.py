"""Benchmark: ResNet-50 ImageNet training throughput on the local chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Baseline: the reference's published ResNet-50 train throughput on its best
single GPU (P100, 181.53 img/s @ bs32, docs/how_to/perf.md:179-188 — see
BASELINE.md). Methodology mirrors ``train_imagenet.py --benchmark 1``:
synthetic data, train-mode forward+backward+update, steady-state timing.

Steps are dispatched through ``Module.train_window`` (K fused steps per
XLA program, default K=20 on TPU; BENCH_FUSED_STEPS=1 restores per-step
dispatch) — the framework's intended steady-state training loop. Every
window iteration is a full fwd+bwd+update on the synthetic batch, exactly
like the reference's benchmark loop; the window only removes per-step
host dispatch, which the reference's threaded engine would likewise
pipeline away.

``BENCH_MODE=fit`` instead times the REAL training loop: ``Module.fit``
over an ``NDArrayIter`` with an ``Accuracy`` metric — device prefetch
staging each batch and device-resident metric accumulation keep the
epoch free of per-batch host syncs, so the fit loop must reach the
``train_window`` steady-state rate (the async-pipeline acceptance bar).
Epochs are timed at their epoch_end_callback boundaries; the first epoch
(compile) is discarded and the median of the rest is reported. On TPU,
fit mode defaults ``MXNET_TRAIN_WINDOW=auto`` so the loop runs the
framework's intended steady state: adaptive fused windows dispatched as
a PIPELINE (``MXNET_DISPATCH_DEPTH`` windows in flight, lazy boundary
publication); the JSON tail reports the operative ``train_window_k``,
``dispatch_depth``, ``peak_windows_in_flight`` and the steady-state
``dispatch_span_share`` (fit.dispatch's share of the host loop) so the
trajectory records why the number moved. ``BENCH_SWEEP=1`` grid-sweeps
K (``BENCH_SWEEP_K``) x depth (``BENCH_SWEEP_DEPTH``) with short fit
runs first, adopts the winner for the headline measurement, and embeds
the per-combo rates under ``"sweep"``.

Both window paths dispatch with ``publish_grads=False``: nothing in a
bench loop reads per-window gradients, so the boundary's f32 gradient
publication is dead-coded out of the fused program (the same lazy-
boundary contract the pipelined fit loop uses).

The result JSON always embeds a telemetry snapshot (``"telemetry"`` key)
so BENCH_* files carry the bound — data- vs dispatch- vs sync-bound — of
the measured run. With ``MXNET_TELEMETRY=1`` in fit mode, the run
additionally runs under jax's profiler, whose trace holds the program's
host spans beside the device operations, and writes that one
Perfetto-loadable timeline (``BENCH_TRACE_OUT``, default
bench_trace.json) plus the snapshot JSON/Prometheus pair
(``BENCH_TELEMETRY_OUT``, default bench_telemetry.json).

Compile-cost trajectory: both modes report ``cold_compile_s`` (the first
epoch / warmup duration — where XLA compilation lives) and
``warm_start_s`` (a FRESH module bound and stepped once after the timed
run), so the AOT executable cache win (``MXNET_AOT_CACHE=1`` — warm
start deserializes instead of recompiling) is tracked by the bench
trajectory, not just asserted in tests. ``BENCH_WARM_START=0`` skips the
extra measurement. ``MXNET_TRAIN_WINDOW=auto`` in fit mode engages the
adaptive window scheduler; the chosen K is reported as
``train_window_k``.

Robustness cost: train mode re-times the loop with the non-finite-
gradient sentinel on (``MXNET_NONFINITE_GUARD=skip``) and reports
``nonfinite_guard_overhead`` = 1 - guarded/unguarded img/s (expected
<2%: one all-finite reduce fused into the donated step, no host sync).
``BENCH_GUARD=0`` skips it.

``BENCH_MODE=serve`` times the INFERENCE serving path:
``serving.ModelServer`` (dynamic batcher over per-bucket pre-compiled
predictors, replicated across ``BENCH_SERVE_REPLICAS`` devices — 0 =
auto) under ``BENCH_SERVE_CLIENTS`` synthetic concurrent client
threads, reporting ``serving_throughput`` (img/s), request p50/p99
latency (from the server's log-bucket histogram),
``sequential_img_per_sec`` — the same model driven one request at a time
through the batch-1 predictor — plus ``replicas`` and
``per_replica_batches`` (the replication scaling evidence). With > 1
replica it also measures ``single_replica_img_per_sec`` under the same
concurrent load (``replica_scaling`` = the replication win;
``BENCH_SERVE_SCALING=0`` skips). The batcher must beat sequential
batch-1 (the smoke pin in tests/test_bench_smoke.py), and the embedded
telemetry snapshot must show ``executor.jit_compile == 0`` — the warmed
request path never compiles.

``BENCH_SERVE_SHARDED=1`` adds the MESH-NATIVE serving legs over a
tp-annotated MLP: one ``sharded`` sub-record per ``BENCH_SERVE_MESH_LEGS``
spec (default ``tp2,pp2,dp-tp2`` — single tp2 group, single GPipe pp2
group, and every tp2 group as a dp replica) with per-leg img/s, p99 and
``request_path_compiles`` (pinned 0), plus the ``tp2_scaling_curve``
(throughput at 1/2/4 two-device groups; ``group_scaling_4x`` is the
ratio the trajectory tracks). Needs >= 8 devices — real chips or
``--xla_force_host_platform_device_count=8``.

``BENCH_CHAOS=1`` adds the availability-under-chaos leg: one replica is
killed (env fault injection) under concurrent traffic, then revived;
the JSON tail reports ``availability`` (completed/total across
pre/fault/recover phases — pinned >= 0.99 in the cpu smoke),
``p99_during_fault_ms``, the failover count, and the killed replica's
final state (probe-recovered or still open).

``BENCH_MODE=suite`` emits the WHOLE-ZOO scoreboard: every BASELINE
workload — MLP, LeNet, ResNet-50, bucketed LSTM-PTB, SSD-VGG16, DCGAN —
through the modern stack (fused K-step train windows, ``BENCH_SUITE_K``;
pipelined dispatch, ``BENCH_SUITE_DEPTH`` windows in flight), one
sub-record per workload with train+infer samples/s, analytic
``gflops_per_sample_fwd`` (models.recipe.estimate_flops; MFU on TPU
bf16), dtype, window K, dispatch depth and ``steady_compiles`` — the
compile count over the timed region, pinned 0 by the cpu smoke. The
DCGAN leg also times the reference imperative loop
(``legacy_train_samples_per_sec``) so the fused-step win is a recorded
number, not a claim. ``BENCH_SUITE_WORKLOADS`` subsets by name; the
headline value is the geomean train rate. See docs/benchmarks.md.

``BENCH_MODE=score`` sweeps forward-only scoring over the 14 zoo symbols
of the published perf table, sharing the symbol list
(``models.SCORE_SYMBOLS``) and the scoring loop with
``examples/benchmark_score.py``. ``BENCH_SCORE_NETS`` subsets,
``BENCH_SCORE_BATCH`` sizes; per-net records carry samples/s + analytic
GFLOPs (+ MFU on TPU bf16); the headline is the geomean img/s.

``BENCH_MODE=ckpt`` times the CHECKPOINT save pause on the training
thread: two identical fit passes with per-epoch + mid-epoch v2 sharded
saves — synchronous, then ``MXNET_CKPT_ASYNC``-style async — reporting
per-save ``snapshot_us`` / ``write_us`` / ``write_async_us``, the
resulting ``pause_us`` each mode charges the training loop, and
``async_vs_sync_pause`` (the bounded-stall win; ``BENCH_CKPT_EPOCHS``
sizes the pass).

``BENCH_MODE=io`` measures the INPUT PLANE alone: ImageRecordIter
decode+augment img/s over a generated synthetic-JPEG ``.rec``, serial
baseline vs the supervised decode pool at each ``BENCH_IO_WORKERS``
count. The record carries the full ``scaling`` curve, the gated
``pool_speedup`` ratio, and the ``io.plane.*`` telemetry snapshot.
``BENCH_FIT_DATA=recordio`` makes the fit mode train ResNet from a
generated RecordIO file end-to-end (metric suffix ``_recordio``) — the
number that proves the plane feeds the chip at device rate. See
docs/io.md.
"""
# graftlint: allow=env-registry(bench drives the framework's declared MXNET_* knobs and chaos injection by writing/restoring os.environ by design — the sweep and chaos legs ARE env manipulation)

import json
import os
import sys
import time

import numpy as np

# reference P100 ResNet-50 train img/s @bs32 (BASELINE.md)
BASELINE_IMG_PER_SEC = 181.53


def _build_module(mx, models, batch_size, image, dtype, num_layers, on_tpu):
    sym = models.resnet(
        num_classes=1000, num_layers=num_layers,
        image_shape=",".join(map(str, image)),
    )
    ctx = mx.gpu() if on_tpu else mx.cpu()
    mod = mx.mod.Module(sym, context=ctx)
    mod.bind(
        data_shapes=[mx.io.DataDesc("data", (batch_size,) + image, dtype)],
        label_shapes=[mx.io.DataDesc("softmax_label", (batch_size,))],
    )
    mod.init_params(initializer=mx.init.Xavier(rnd_type="gaussian",
                                               factor_type="in", magnitude=2))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01, "momentum": 0.9})
    return mod


def _write_bench_rec(mx, path, n, image, seed=0):
    """Synthetic-JPEG RecordIO fixture for the io/recordio bench legs:
    ``n`` random images a shade larger than ``image`` (so rand_crop has
    room), labels = record id % 1000."""
    from mxnet_tpu import recordio

    rng = np.random.RandomState(seed)
    side = image[1] + max(8, image[1] // 8)
    rec = recordio.MXRecordIO(path, "w")
    for i in range(n):
        img = rng.randint(0, 255, (side, side, 3), np.uint8)
        rec.write(recordio.pack_img((0, float(i % 1000), i, 0), img))
    rec.close()
    return path


def _recordio_fit_iter(mx, batch_size, image, iters, windows):
    """BENCH_FIT_DATA=recordio: an ImageRecordIter over a generated .rec
    holding exactly the samples one epoch consumes — the leg that proves
    the decode plane feeds the chip at the synthetic-data rate."""
    import tempfile

    td = tempfile.mkdtemp(prefix="bench_recordio_")
    path = _write_bench_rec(mx, os.path.join(td, "train.rec"),
                            batch_size * iters, image)
    workers = int(os.environ.get("BENCH_IO_WORKERS_FIT", 4))
    return mx.io.ImageRecordIter(
        path_imgrec=path, data_shape=image, batch_size=batch_size,
        rand_crop=True, rand_mirror=True, shuffle=True, seed=0,
        preprocess_threads=workers)


def _run_fit_mode(mx, mod, batch_size, image, dtype, iters, windows,
                  fit_data="synthetic"):
    """Time Module.fit epochs over a real data iterator (+Accuracy
    metric): an in-memory NDArrayIter by default, or the RecordIO decode
    plane when ``fit_data == "recordio"``."""
    if fit_data == "recordio":
        train = _recordio_fit_iter(mx, batch_size, image, iters, windows)
    else:
        rng = np.random.RandomState(0)
        n = batch_size * iters
        # cast to the BOUND dtype up front (bfloat16 on TPU): the executor
        # was compiled for it, and staging f32 would double the H2D bytes
        data = rng.uniform(-1, 1, (n,) + image).astype(mx.base.np_dtype(dtype))
        label = rng.randint(0, 1000, (n,)).astype(np.float32)
        train = mx.io.NDArrayIter(data, label, batch_size=batch_size,
                                  last_batch_handle="discard")
    marks = []

    def epoch_mark(epoch, sym=None, arg=None, aux=None):
        marks.append(time.time())
        if epoch == 0:
            # the first (compile) epoch is discarded from the timing; drop
            # its telemetry too so the embedded snapshot reflects the
            # steady state (compile-epoch dispatch times would dwarf the
            # per-batch phase numbers the bound verdict reads)
            mx.telemetry.reset()

    metric = mx.metric.Accuracy()
    t0 = time.time()
    mod.fit(train, eval_metric=metric, num_epoch=windows + 1,
            optimizer_params={"learning_rate": 0.01, "momentum": 0.9},
            epoch_end_callback=epoch_mark)
    durations = np.diff([t0] + marks)
    steady = durations[1:] if len(durations) > 1 else durations
    rates = batch_size * iters / steady
    rate = float(np.median(rates))
    spread = float((rates.max() - rates.min()) / rate) if len(rates) > 1 else 0.0
    # the discarded first epoch is where XLA compilation lives — report it
    # so the compile-cache win shows up in the bench trajectory
    cold_compile_s = float(durations[0]) if len(durations) > 1 else 0.0
    return rate, spread, cold_compile_s


def _time_warm_start(mx, models, batch_size, image, dtype, num_layers,
                     on_tpu, fused=1):
    """Bind a FRESH module and run one dispatch (a `fused`-step window when
    fused>1, matching the timed loop's program shape): with the ambient
    MXNET_AOT_CACHE state this measures cache-deserialize vs recompile."""
    mod = _build_module(mx, models, batch_size, image, dtype, num_layers,
                        on_tpu)
    rng = np.random.RandomState(1)
    data = mx.nd.array(
        rng.uniform(-1, 1, (batch_size,) + image).astype(np.float32),
        dtype=dtype)
    label = mx.nd.array(rng.randint(0, 1000, (batch_size,)).astype(np.float32))
    batch = mx.io.DataBatch(data=[data], label=[label])
    tic = time.time()
    if fused > 1:
        # publish_grads=False matches the timed loop's program shape, so
        # the AOT cache entry the loop warmed serves this fresh module
        mod.train_window(batch, fused, publish_grads=False)
    else:
        mod.forward_backward(batch)
        mod.update()
    np.asarray(mod.get_outputs()[0]._data[0, :1])
    return round(time.time() - tic, 3)


def _maybe_mesh(record, mx):
    """Attach the operative GraftMesh layout (MXNET_MESH or an installed
    mesh) so a bench record is attributable to its parallelism config."""
    gm = mx.parallel.current_graft()
    if gm is not None:
        record["mesh"] = gm.spec


# bf16 peak per device kind; unknown kinds omit MFU rather than report
# against the wrong denominator
_PEAKS_TFLOPS_BF16 = {"TPU v5 lite": 197, "TPU v5e": 197,
                      "TPU v4": 275, "TPU v5p": 459,
                      "TPU v6 lite": 918, "TPU v6e": 918}


def _peak_tflops(jax):
    kind = getattr(jax.devices()[0], "device_kind", "")
    return next((v for k, v in _PEAKS_TFLOPS_BF16.items() if k in kind), None)


def _fwd_flops(models, sym, **shapes):
    """Analytic forward FLOPs/sample via models.recipe.estimate_flops
    (MAC convention: ResNet-50@224 ≈ 4.1e9). None when the symbol holds an
    op the estimator can't shape-infer — MFU is then omitted, not wrong."""
    try:
        return float(models.recipe.estimate_flops(sym, **shapes))
    except Exception:
        return None


def _maybe_mfu(record, samples_per_sec, jax, on_tpu, dtype, flops_per_sample,
               key="mfu"):
    """Attach model-FLOPs-utilization when the analytic per-sample FLOPs
    and the device-kind bf16 peak are both known. ``flops_per_sample`` is
    the full cost of what the rate counts — callers pass 3x the forward
    estimate for train rates (fwd + input-grad + weight-grad)."""
    if not (on_tpu and dtype == "bfloat16" and flops_per_sample):
        return
    peak = _peak_tflops(jax)
    if peak:
        record[key] = round(
            samples_per_sec * flops_per_sample / (peak * 1e12), 3)


def _stamp_device_recipe(record, mx, models, on_tpu, dtype):
    """Stamp the resolved conv-stack device layout (MXNET_CONV_LAYOUT,
    ops/layout.py) and the precision recipe on a headline record, so a
    rate move in the trajectory is attributable to the device-side config
    that caused it."""
    record["layout"] = models.recipe.conv_layout(
        mx.gpu() if on_tpu else mx.cpu())
    record["recipe"] = models.recipe.recipe_name(dtype)


def _kernel_rows(mx, top=10):
    """The traced device time as the ``kernels`` rows
    ``tools/bench_compare.py`` reads (``name``, ``device_us``, ``calls``,
    ``pct``, ``bytes``): ``profiler.device_table`` of the profile
    directory of the trace just dumped, one row per (operator, pass), and
    what carries no scope by its XLA kind."""
    table = mx.profiler.device_table()
    rows = [dict(r, name=f"{r['operator']} {r['pass']}")
            for r in table["by_operator"] if r["operator"] != "unscoped"]
    rows += table["unscoped"]
    out = []
    for r in sorted(rows, key=lambda r: -r["ms"])[:top]:
        row = {"name": r["name"], "device_us": round(r["ms"] * 1e3, 1),
               "calls": r["calls"], "pct": round(r["share"], 4)}
        if "bytes" in r:
            row["bytes"] = int(r["bytes"])
        out.append(row)
    return out


def _kernel_attribution(mx, mod, batch, k=2):
    """Top-10 per-kernel device-time table for one steady-state train
    window of ``mod``: traced AFTER the timed region (attribution never
    pollutes the measurement) with the jax device profiler and read back
    by ``_kernel_rows``. Returns [] when the profiler is
    unavailable; BENCH_KERNELS=0 skips the extra window entirely. The
    caller's timed loop just ran the same (shapes, K) program, so the
    traced window executes warm — no compile lands in the timeline."""
    if os.environ.get("BENCH_KERNELS", "1") == "0":
        return []
    import tempfile

    td = tempfile.mkdtemp(prefix="bench_kernels_")
    try:
        mx.profiler.profiler_set_config(
            filename=os.path.join(td, "kernels.json"))
        mx.profiler.profiler_set_state("run")
        mod.train_window(batch, k, publish_grads=False).wait()
        trace = mx.profiler.dump_profile()
        return _kernel_rows(mx) if trace else []
    except Exception as e:
        print(f"kernel attribution skipped: {e}", file=sys.stderr)
        return []


def _resnet_train_flops(models, num_layers, image, batch_size):
    """Train FLOPs/img for the train/fit headline records (3x forward; at
    50 layers @224 this reproduces the 12.3 GFLOP/img the MFU field has
    used since PR-3, now computed rather than hardcoded)."""
    sym = models.resnet(num_classes=1000, num_layers=num_layers,
                        image_shape=",".join(map(str, image)))
    fwd = _fwd_flops(models, sym, data=(batch_size,) + image)
    return 3.0 * fwd if fwd else None


def _sweep_fit(mx, models, batch_size, image, dtype, num_layers, on_tpu,
               iters):
    """BENCH_SWEEP=1: grid-sweep (train_window K) x (dispatch depth) with
    short fit runs, adopt the best combo in the environment for the
    headline measurement, and return the per-combo rates so the BENCH
    trajectory records WHY the number moved."""
    ks = [int(x) for x in os.environ.get(
        "BENCH_SWEEP_K", "10,20,32" if on_tpu else "2,3").split(",")]
    depths = [int(x) for x in os.environ.get(
        "BENCH_SWEEP_DEPTH", "1,2,3" if on_tpu else "1,2").split(",")]
    results = []
    best = None
    for k in ks:
        for d in depths:
            os.environ["MXNET_TRAIN_WINDOW"] = str(k)
            os.environ["MXNET_DISPATCH_DEPTH"] = str(d)
            mod = _build_module(mx, models, batch_size, image, dtype,
                                num_layers, on_tpu)
            mx.telemetry.reset()
            rate, _spread, _cold = _run_fit_mode(
                mx, mod, batch_size, image, dtype, iters, 1)
            results.append(
                {"k": k, "depth": d, "img_per_sec": round(rate, 2)})
            if best is None or rate > best[0]:
                best = (rate, k, d)
    os.environ["MXNET_TRAIN_WINDOW"] = str(best[1])
    os.environ["MXNET_DISPATCH_DEPTH"] = str(best[2])
    print(f"sweep winner: K={best[1]} depth={best[2]} "
          f"({best[0]:.1f} img/s)", file=sys.stderr)
    return results


def _sweep_xla(mx, models, batch_size, image, dtype, num_layers, on_tpu,
               iters):
    """BENCH_SWEEP=xla: sweep MXNET_XLA_FLAGS candidates with short fit
    runs, adopt the fastest in the environment for the headline
    measurement, and return per-candidate rates so the trajectory records
    the choice. Candidates come from BENCH_SWEEP_XLA as ;-separated flag
    strings (each a comma-separated MXNET_XLA_FLAGS value; the empty
    string = compiler defaults). The flags feed both executable digests
    and the AOT fingerprint, so every candidate really recompiles — a
    candidate XLA rejects is recorded as an error, not a crash."""
    cands = os.environ.get(
        "BENCH_SWEEP_XLA",
        ";xla_latency_hiding_scheduler=true" if on_tpu
        else ";xla_cpu_enable_fast_math=true"
        ";xla_llvm_disable_expensive_passes=true").split(";")
    results = []
    best = None
    for flags in cands:
        os.environ["MXNET_XLA_FLAGS"] = flags
        mod = _build_module(mx, models, batch_size, image, dtype,
                            num_layers, on_tpu)
        mx.telemetry.reset()
        entry = {"xla_flags": flags}
        try:
            rate, _spread, _cold = _run_fit_mode(
                mx, mod, batch_size, image, dtype, iters, 1)
            entry["img_per_sec"] = round(rate, 2)
            if best is None or rate > best[0]:
                best = (rate, flags)
        except Exception as e:
            entry["error"] = f"{type(e).__name__}: {e}"[:200]
        results.append(entry)
    os.environ["MXNET_XLA_FLAGS"] = best[1] if best else ""
    print(f"xla sweep winner: {best[1] or '<defaults>'} "
          f"({best[0]:.1f} img/s)" if best else "xla sweep: no candidate ran",
          file=sys.stderr)
    return results


def _fit_phase_fields(record, snapshot):
    """dispatch_depth + steady-state fit.dispatch span share from the
    embedded telemetry snapshot — the JSON-tail fields the trajectory
    reads alongside train_window_k."""
    fit = snapshot.get("fit", {})

    def hsum(name):
        return (fit.get(name) or {}).get("sum", 0)

    total = sum(hsum(n) for n in (
        "dispatch", "data_wait", "metric", "callback", "window_wait"))
    if total:
        record["dispatch_span_share"] = round(hsum("dispatch") / total, 4)
    depth = (fit.get("dispatch_depth") or {}).get("value", 0)
    if depth:
        record["dispatch_depth"] = depth
    in_flight = (fit.get("windows_in_flight") or {}).get("max", 0)
    if in_flight:
        record["peak_windows_in_flight"] = in_flight


def _random_inference_params(mx, sym, image):
    """Random weights straight from shape inference — binding a training
    executor just to initialize would compile the whole train graph."""
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=(1,) + image, softmax_label=(1,))
    rng = np.random.RandomState(0)
    params = {}
    for n, s in zip(sym.list_arguments(), arg_shapes):
        if n in ("data", "softmax_label"):
            continue
        fan_in = int(np.prod(s[1:])) if len(s) > 1 else int(s[0])
        params[f"arg:{n}"] = mx.nd.array(
            (rng.randn(*s) * np.sqrt(2.0 / max(fan_in, 1)))
            .astype(np.float32))
    for n, s in zip(sym.list_auxiliary_states(), aux_shapes):
        params[f"aux:{n}"] = (mx.nd.ones(s) if "var" in n or "gamma" in n
                              else mx.nd.zeros(s))
    return params


def _drive_serve_phase(server, samples, clients, per_client, phase):
    """One concurrent-client phase against ``server``; returns
    [(ok, latency_s)] per request (the chaos leg needs per-phase
    availability and latency, not just aggregates)."""
    import threading

    results = []
    lock = threading.Lock()

    def client(cid):
        for i in range(per_client):
            tic = time.time()
            try:
                server.predict(samples[(cid + i) % len(samples)],
                               timeout=120)
                ok = True
            except Exception:  # noqa: BLE001 — availability accounting
                ok = False
            with lock:
                results.append((ok, time.time() - tic))

    threads = [threading.Thread(target=client, args=(c,), name=f"{phase}{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results


def _tp_annotated_mlp(mx, in_dim=64, hidden=256, num_classes=16):
    """Two-layer MLP with explicit column/row tensor-parallel shard
    annotations — the sharded serving legs' model (resnet carries no
    ``__shard__`` attributes; this is the canonical Megatron split)."""
    data = mx.sym.Variable("data")
    with mx.AttrScope(__shard__="tp:0"):
        w1 = mx.sym.Variable("fc1_weight")
    with mx.AttrScope(__shard__="tp:1"):
        w2 = mx.sym.Variable("fc2_weight")
    h = mx.sym.FullyConnected(data, weight=w1, num_hidden=hidden,
                              no_bias=True, name="fc1")
    h = mx.sym.Activation(h, act_type="relu", name="relu1")
    return mx.sym.FullyConnected(h, weight=w2, num_hidden=num_classes,
                                 no_bias=True, name="fc2"), (in_dim,)


def _run_serve_sharded_legs(mx, clients, per_client):
    """``BENCH_SERVE_MESH_LEGS``: per-mesh-spec serving legs (``tp2``,
    ``pp2``, ``dp-tp2`` = every tp2 group as a dp replica) plus the
    group-replica scaling curve. Each leg reports throughput, p99 and the
    REQUEST-PATH compile count (must be 0 — the per-bucket sharded
    executables are all warmed up front)."""
    from mxnet_tpu.serving import ModelServer, ServingConfig

    sym, shape = _tp_annotated_mlp(mx)
    rng = np.random.RandomState(2)
    arg_shapes, _, _ = sym.infer_shape(data=(1,) + shape)
    params = {n: mx.nd.array(rng.uniform(-0.5, 0.5, s).astype(np.float32))
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n != "data"}
    samples = [rng.uniform(-1, 1, shape).astype(np.float32)
               for _ in range(16)]
    compile_ctr = mx.telemetry.counter("executor.jit_compile")

    def leg(mesh_spec, replicas):
        srv = ModelServer(
            sym, {k: v.copy() for k, v in params.items()},
            {"data": shape},
            config=ServingConfig(buckets="1,4", mesh=mesh_spec,
                                 replicas=replicas, fold_bn=False))
        srv.warmup()
        srv.start()
        srv.latency.reset()
        c0 = compile_ctr.value
        tic = time.time()
        results = _drive_serve_phase(srv, samples, clients, per_client,
                                     f"shard-{mesh_spec}-r{replicas}")
        wall = time.time() - tic
        out = {
            "img_per_sec": round(
                sum(1 for k, _ in results if k) / wall, 2),
            "errors": sum(1 for k, _ in results if not k),
            "replicas": len(srv.replicas),
            "p99_ms": round(srv.latency.percentile(99) / 1e3, 2),
            "request_path_compiles": compile_ctr.value - c0,
        }
        srv.close()
        return out

    legs_env = os.environ.get("BENCH_SERVE_MESH_LEGS", "tp2,pp2,dp-tp2")
    sharded = {}
    for name in [s.strip() for s in legs_env.split(",") if s.strip()]:
        if name.startswith("dp-"):
            # dp-of-<spec>: EVERY group serves (replicas=0 = all)
            sharded[name] = leg(name[3:], replicas=0)
        else:
            sharded[name] = leg(name, replicas=1)
    # group-replica scaling curve over the dp-of-tp2 layout: throughput
    # vs number of 2-device groups under the same concurrent load
    curve = {}
    for n in (1, 2, 4):
        curve[n] = leg("tp2", replicas=n)["img_per_sec"]
    sharded["tp2_scaling_curve"] = curve
    if curve[1] > 0:
        sharded["group_scaling_4x"] = round(curve[4] / curve[1], 3)
    return sharded


def _run_serve_chaos(mx, server, samples, clients, per_client):
    """BENCH_CHAOS=1: kill one replica under concurrent traffic (env
    fault injection, runtime-toggled), then revive it — report
    availability across pre/fault/recover phases and p99 DURING the
    fault. The serving availability SLO, measured, not asserted."""
    failover = mx.telemetry.counter("serving.replica.failover")
    f0 = failover.value
    pre = _drive_serve_phase(server, samples, clients, per_client, "pre")
    os.environ["MXNET_FI_SERVE_RAISE_REPLICA"] = "0"
    try:
        fault = _drive_serve_phase(server, samples, clients, per_client,
                                   "fault")
    finally:
        os.environ.pop("MXNET_FI_SERVE_RAISE_REPLICA", None)
    time.sleep(0.3)  # half-open probe backoff before the recovery phase
    recover = _drive_serve_phase(server, samples, clients, per_client,
                                 "recover")
    everything = pre + fault + recover
    ok = sum(1 for k, _ in everything if k)
    fault_lat = sorted(lat for _, lat in fault)
    p99_fault = fault_lat[max(0, int(len(fault_lat) * 0.99) - 1)] \
        if fault_lat else 0.0
    killed = next((r for r in server.stats()["replicas"] if r["id"] == 0),
                  {})
    return {
        "availability": round(ok / max(1, len(everything)), 4),
        "requests": len(everything),
        "failed": len(everything) - ok,
        "p99_during_fault_ms": round(p99_fault * 1e3, 2),
        "failover_count": failover.value - f0,
        "killed_replica_state": killed.get("state"),
    }


def _run_serve_mode(mx, models, image, num_layers, on_tpu):
    import threading

    from mxnet_tpu.serving import ModelServer, ServingConfig

    buckets = os.environ.get("BENCH_SERVE_BUCKETS",
                             "1,8,32" if on_tpu else "1,4,8")
    clients = int(os.environ.get("BENCH_SERVE_CLIENTS", 8))
    per_client = int(os.environ.get("BENCH_SERVE_REQUESTS",
                                    50 if on_tpu else 25))
    seq_iters = int(os.environ.get("BENCH_SERVE_SEQ_ITERS",
                                   30 if on_tpu else 12))
    chaos = os.environ.get("BENCH_CHAOS") == "1"
    replicas_cfg = int(os.environ.get("BENCH_SERVE_REPLICAS", "0") or 0)
    if chaos and replicas_cfg == 0:
        replicas_cfg = 2  # chaos needs a survivor to fail over to

    sym = models.resnet(num_classes=1000, num_layers=num_layers,
                        image_shape=",".join(map(str, image)))
    params = _random_inference_params(mx, sym, image)

    def make_server(n_replicas):
        return ModelServer(
            sym, params, {"data": image},
            config=ServingConfig(buckets=buckets, replicas=n_replicas),
            dev_type="gpu" if on_tpu else "cpu")

    server = make_server(replicas_cfg)
    server.warmup()
    server.start()

    rng = np.random.RandomState(1)
    samples = [rng.uniform(-1, 1, image).astype(np.float32)
               for _ in range(16)]

    # sequential one-request-at-a-time reference through the server's own
    # smallest-bucket predictor — the exact program the batcher amortizes,
    # so the ratio isolates the batching win from model/compile
    # differences (bucket 1 when configured; otherwise one real sample
    # padded into the smallest bucket, which is what a lone request costs)
    b0 = server.config.buckets[0]
    p0 = server.predictor(b0)
    seq_batch = np.zeros((b0,) + image, np.float32)
    for s in samples[:2]:
        seq_batch[0] = s
        p0.run(data=seq_batch)  # warm
    tic = time.time()
    for i in range(seq_iters):
        seq_batch[0] = samples[i % len(samples)]
        p0.run(data=seq_batch)
    sequential = seq_iters / (time.time() - tic)

    mx.telemetry.reset()
    server.latency.reset()
    errors = []
    completed = [0] * clients

    def client(cid):
        for i in range(per_client):
            try:
                server.predict(samples[(cid + i) % len(samples)],
                               timeout=120)
                completed[cid] += 1
            except Exception as e:  # noqa: BLE001 — recorded, not raised
                errors.append(repr(e))
                return

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    tic = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.time() - tic
    total = sum(completed)
    snapshot = mx.telemetry.snapshot()
    lat = server.latency
    n_replicas = len(server.replicas)
    record = {
        "metric": f"resnet{num_layers}_serving_throughput"
                  + ("" if on_tpu else "_cpusmoke"),
        "value": round(total / wall, 2),
        "unit": "images/sec",
        "vs_baseline": round(total / wall / BASELINE_IMG_PER_SEC, 3),
        "sequential_img_per_sec": round(sequential, 2),
        "batching_speedup": round(total / wall / sequential, 3),
        "clients": clients,
        "requests": total,
        "errors": len(errors),
        "p50_ms": round(lat.percentile(50) / 1e3, 2),
        "p99_ms": round(lat.percentile(99) / 1e3, 2),
        "replicas": n_replicas,
        # per-replica batch counts over the SAME wall window: the
        # replication scaling evidence (a starved replica shows up as a
        # near-zero share, not as an invisible average)
        "per_replica_batches": {r["id"]: r["batches"]
                                for r in server.stats()["replicas"]},
        "telemetry": snapshot,
    }
    if n_replicas > 1 and os.environ.get("BENCH_SERVE_SCALING", "1") != "0":
        # the single-replica baseline under the SAME concurrent load:
        # the ratio is the replication win the trajectory tracks
        single = make_server(1)
        single.warmup()
        single.start()
        tic = time.time()
        results = _drive_serve_phase(single, samples, clients, per_client,
                                     "single")
        single_wall = time.time() - tic
        single.close()
        ok = sum(1 for k, _ in results if k)
        record["single_replica_img_per_sec"] = round(ok / single_wall, 2)
        if ok:
            record["replica_scaling"] = round(
                record["value"] / record["single_replica_img_per_sec"], 3)
    if os.environ.get("BENCH_SERVE_SHARDED") == "1":
        # tp/pp group-replica legs + scaling curve (needs a multi-device
        # mesh: real chips, or --xla_force_host_platform_device_count)
        record["sharded"] = _run_serve_sharded_legs(mx, clients,
                                                    per_client)
    if chaos:
        record["chaos"] = _run_serve_chaos(mx, server, samples, clients,
                                           per_client)
        record["availability"] = record["chaos"]["availability"]
        record["p99_during_fault_ms"] = \
            record["chaos"]["p99_during_fault_ms"]
    server.close()
    print(json.dumps(record))


def _ckpt_pass(mx, models, batch_size, image, dtype, num_layers, on_tpu,
               epochs, ckpt_dir, async_write):
    """One fit pass with per-epoch + mid-epoch saves; returns the
    per-save training-thread pause decomposition from telemetry."""
    mod = _build_module(mx, models, batch_size, image, dtype, num_layers,
                        on_tpu)
    rng = np.random.RandomState(0)
    n = batch_size * 4
    data = rng.uniform(-1, 1, (n,) + image).astype(mx.base.np_dtype(dtype))
    label = rng.randint(0, 1000, (n,)).astype(np.float32)
    train = mx.io.NDArrayIter(data, label, batch_size=batch_size,
                              last_batch_handle="discard")
    cfg = mx.CheckpointConfig(ckpt_dir, period=1, batch_period=2,
                              keep_n=2, async_write=async_write)
    saves0 = mx.telemetry.counter("checkpoint.save").value
    bytes0 = mx.telemetry.counter("checkpoint.bytes").value
    marks = {}
    for h in ("checkpoint.snapshot", "checkpoint.write",
              "checkpoint.write_async"):
        hist = mx.telemetry.histogram(h)
        marks[h] = (hist.count, hist.sum)
    t0 = time.time()
    mod.fit(train, num_epoch=epochs,
            optimizer_params={"learning_rate": 0.01, "momentum": 0.9},
            checkpoint=cfg)
    wall_s = time.time() - t0
    saves = mx.telemetry.counter("checkpoint.save").value - saves0
    out = {"saves": saves, "wall_s": round(wall_s, 3),
           "bytes": mx.telemetry.counter("checkpoint.bytes").value - bytes0}
    for h, (c0, s0) in marks.items():
        hist = mx.telemetry.histogram(h)
        dc, ds = hist.count - c0, hist.sum - s0
        out[h.split(".", 1)[1] + "_us"] = round(ds / dc, 1) if dc else 0.0
    # the training thread stalls for snapshot always, plus the write only
    # when synchronous; async commits ride the writer thread
    out["pause_us"] = round(
        out["snapshot_us"] + (0.0 if async_write else out["write_us"]), 1)
    return out


def _run_ckpt_mode(mx, models, batch_size, image, dtype, num_layers,
                   on_tpu):
    """BENCH_MODE=ckpt: measure what a checkpoint save costs the training
    thread. Two identical fit passes with per-epoch + mid-epoch v2
    sharded saves — synchronous (pause = snapshot + write) then async
    (pause = snapshot only; the commit lands on the writer thread) — and
    report the per-save pause decomposition plus the async/sync ratio.
    The async pause bound is the elastic-checkpoint contract: growing
    model size moves write_us, not the training stall."""
    import shutil
    import tempfile

    epochs = int(os.environ.get("BENCH_CKPT_EPOCHS", 3))
    root = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        sync = _ckpt_pass(mx, models, batch_size, image, dtype, num_layers,
                          on_tpu, epochs, os.path.join(root, "sync"),
                          async_write=False)
        asy = _ckpt_pass(mx, models, batch_size, image, dtype, num_layers,
                         on_tpu, epochs, os.path.join(root, "async"),
                         async_write=True)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    record = {
        "metric": f"resnet{num_layers}_ckpt_pause"
                  + ("" if on_tpu else "_cpusmoke"),
        "value": asy["pause_us"],
        "unit": "us/save",
        "sync": sync,
        "async": asy,
        "async_vs_sync_pause": round(
            asy["pause_us"] / sync["pause_us"], 3) if sync["pause_us"]
        else None,
    }
    print(json.dumps(record))


# ---------------------------------------------------------------------------
# BENCH_MODE=suite — the whole-zoo scoreboard: every BASELINE workload
# (MLP/LeNet, ResNet-50, bucketed LSTM-PTB, SSD-VGG16, DCGAN) through the
# modern stack (fused K-step windows, pipelined dispatch, device metrics),
# each leg reporting train+infer samples/s, analytic GFLOPs/sample (MFU on
# TPU bf16), dtype, window K, dispatch depth and the STEADY-STATE compile
# count (executor.jit_compile + executor.fused_plan_compile over the timed
# region — the zero-recompile invariant, counter-verified).
#
# BENCH_MODE=score — the inference sweep over the 14 zoo symbols of the
# published perf table, sharing both the symbol list (models.SCORE_SYMBOLS)
# and the scoring loop with examples/benchmark_score.py.


def _suite_cfg(on_tpu):
    """(window K, dispatch depth, timed windows, warmup windows,
    infer iters) — BENCH_SUITE_* env-tunable, cpu-smoke-sized defaults."""
    return (
        max(1, int(os.environ.get("BENCH_SUITE_K", 16 if on_tpu else 2))),
        max(1, int(os.environ.get("BENCH_SUITE_DEPTH", 2))),
        max(1, int(os.environ.get("BENCH_SUITE_WINDOWS",
                                  8 if on_tpu else 2))),
        max(1, int(os.environ.get("BENCH_SUITE_WARMUP", 2))),
        max(1, int(os.environ.get("BENCH_SUITE_INFER_ITERS",
                                  20 if on_tpu else 3))),
    )


def _steady_compiles(mx):
    """Programs compiled since the last telemetry reset: AOTProgram builds
    (executor.jit_compile) + fused-window plan builds
    (executor.fused_plan_compile). The suite resets telemetry after warmup,
    so over a timed region this is the steady-state compile count — the
    acceptance invariant is that every workload pins it at 0."""
    tm = mx.telemetry
    return int(tm.counter("executor.jit_compile").value
               + tm.counter("executor.fused_plan_compile").value)


def _boundary_fence(boundary):
    """One-scalar device->host fetch off a WindowBoundary output: a
    barrier that holds on every backend (the value cannot arrive before
    the execution that produces it)."""
    if boundary is not None and boundary._outs:
        np.asarray(boundary._outs[0].ravel()[:1])


def _pipelined_windows(mx, dispatch, windows, depth, samples_per_window):
    """Time `windows` dispatches with `depth` windows in flight (the fit
    loop's backpressure discipline). Caller has already warmed up and
    fenced; telemetry is reset here so the compile count covers exactly
    the timed region. Returns (samples/sec, steady_compiles)."""
    from collections import deque

    mx.telemetry.reset()
    inflight = deque()
    last = None
    tic = time.time()
    for _ in range(windows):
        last = dispatch()
        inflight.append(last)
        while len(inflight) > depth:
            inflight.popleft().wait()
    while inflight:
        inflight.popleft().wait()
    _boundary_fence(last)
    dt = time.time() - tic
    # post-timing finiteness probe (one host fetch, outside the clock):
    # the bf16 recipes must train without NaN/Inf in the published outputs
    finite = True
    if last is not None and last._outs:
        finite = bool(np.all(np.isfinite(
            np.asarray(last._outs[0], dtype=np.float32))))
    return samples_per_window * windows / dt, _steady_compiles(mx), finite


def _forward_rate(mx, mod, batch, iters, warmup):
    """Forward-only samples/s with the benchmark_score dispatch/fence
    idiom (touch the output buffer to dispatch; fetch one scalar to
    fence). Returns (samples/sec, steady_compiles)."""
    def dispatch():
        mod.forward(batch, is_train=False)
        mod.get_outputs()[0]._data

    def fence():
        np.asarray(mod.get_outputs()[0]._data.ravel()[:1])

    for _ in range(max(1, warmup)):
        dispatch()
    fence()
    mx.telemetry.reset()
    tic = time.time()
    for _ in range(iters):
        dispatch()
    fence()
    rate = batch.data[0].shape[0] * iters / (time.time() - tic)
    return rate, _steady_compiles(mx)


def _workload_record(jax, on_tpu, train_rate, infer_rate, dtype, k, depth,
                     steady, fwd_flops, train_flops=None, finite=True):
    """One scoreboard row. ``steady`` is the train-leg steady-state compile
    count; ``train_flops`` defaults to 3x forward (fwd + input-grad +
    weight-grad), overridden by workloads whose step does more passes
    (DCGAN's three D passes)."""
    rec = {
        "train_samples_per_sec": round(train_rate, 2),
        "infer_samples_per_sec": round(infer_rate, 2),
        "dtype": dtype,
        "window_k": k,
        "dispatch_depth": depth,
        "steady_compiles": steady,
        "train_outputs_finite": finite,
    }
    if fwd_flops:
        # 6 decimals: the MLP head is ~1e-4 GFLOPs/sample and must not
        # round to a falsy 0.0
        rec["gflops_per_sample_fwd"] = round(fwd_flops / 1e9, 6)
        _maybe_mfu(rec, train_rate, jax, on_tpu, dtype,
                   train_flops or 3.0 * fwd_flops, key="mfu_train")
        _maybe_mfu(rec, infer_rate, jax, on_tpu, dtype, fwd_flops,
                   key="mfu_infer")
    return rec


def _train_leg(mx, mod, batch, k, depth, windows, warmup, samples_per_step):
    """Warm a Module's fused K-step window program, then time pipelined
    window dispatches. Returns (samples/sec, steady_compiles, finite)."""
    for _ in range(warmup):
        mod.train_window(batch, k, publish_grads=False).wait()
    _boundary_fence(mod.train_window(batch, k, publish_grads=False))
    return _pipelined_windows(
        mx, lambda: mod.train_window(batch, k, publish_grads=False),
        windows, depth, samples_per_step * k)


def _suite_classifier(mx, models, jax, on_tpu, sym, data_shape, num_classes,
                      dtype, cfg, init=None, optimizer_params=None,
                      kernels=False):
    """Shared train+infer legs for the single-input classifier-shaped
    workloads (MLP, LeNet, ResNet, SSD-train rides the same path with its
    own label plumbing — see _suite_ssd). ``kernels=True`` appends the
    top-10 per-kernel device-time table (one extra traced window after
    the timed legs)."""
    k, depth, windows, warmup, infer_iters = cfg
    bs = data_shape[0]
    ctx = mx.gpu() if on_tpu else mx.cpu()
    mod = mx.mod.Module(sym, context=ctx)
    mod.bind(data_shapes=[mx.io.DataDesc("data", data_shape, dtype)],
             label_shapes=[mx.io.DataDesc("softmax_label", (bs,))])
    mod.init_params(initializer=init or mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd", optimizer_params=optimizer_params or
                       {"learning_rate": 0.01, "momentum": 0.9})
    rng = np.random.RandomState(0)
    data = mx.nd.array(rng.uniform(-1, 1, data_shape).astype(np.float32),
                       dtype=dtype)
    label = mx.nd.array(rng.randint(0, num_classes, (bs,)).astype(np.float32))
    batch = mx.io.DataBatch(data=[data], label=[label])
    train_rate, steady, finite = _train_leg(mx, mod, batch, k, depth,
                                            windows, warmup, bs)

    imod = mx.mod.Module(sym, context=ctx)
    imod.bind(data_shapes=[mx.io.DataDesc("data", data_shape, dtype)],
              for_training=False)
    imod.init_params(initializer=init or mx.init.Xavier())
    infer_rate, _ = _forward_rate(mx, imod, batch, infer_iters, warmup)
    fwd = _fwd_flops(models, sym, data=data_shape)
    rec = _workload_record(jax, on_tpu, train_rate, infer_rate, dtype, k,
                           depth, steady, fwd, finite=finite)
    if kernels:
        rec["kernels"] = _kernel_attribution(mx, mod, batch, k)
    return rec


def _suite_mlp(mx, models, jax, on_tpu, dtype, cfg):
    bs = 1024 if on_tpu else 64
    return _suite_classifier(mx, models, jax, on_tpu,
                             models.mlp(num_classes=10, dtype=dtype),
                             (bs, 784), 10, dtype, cfg)


def _suite_lenet(mx, models, jax, on_tpu, dtype, cfg):
    bs = 512 if on_tpu else 64
    return _suite_classifier(mx, models, jax, on_tpu,
                             models.lenet(num_classes=10, dtype=dtype),
                             (bs, 1, 28, 28), 10, dtype, cfg)


def _suite_resnet50(mx, models, jax, on_tpu, dtype, cfg):
    bs = 128 if on_tpu else 4
    image = (3, 224, 224) if on_tpu else (3, 64, 64)
    sym = models.resnet(num_classes=1000, num_layers=50,
                        image_shape=",".join(map(str, image)))
    return _suite_classifier(
        mx, models, jax, on_tpu, sym, (bs,) + image, 1000, dtype, cfg,
        init=mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                            magnitude=2), kernels=True)


def _suite_ssd(mx, models, jax, on_tpu, dtype, cfg):
    """SSD-VGG16: the multi-loss Group trains through the same fused
    window machinery as the classifiers (MultiBoxTarget in-graph, f32
    anchor math under the bf16 trunk recipe); the infer leg scores the
    detection symbol (SoftmaxActivation + in-graph NMS)."""
    k, depth, windows, warmup, infer_iters = cfg
    bs = 16 if on_tpu else 2
    size = 300 if on_tpu else 64
    num_classes = 20 if on_tpu else 3
    max_obj, obj_w = 4, 5  # ImageDetRecordIter layout: [cls,x1,y1,x2,y2]
    ctx = mx.gpu() if on_tpu else mx.cpu()
    net = models.ssd.get_symbol_train(num_classes=num_classes,
                                      data_shape=size, dtype=dtype)
    mod = mx.mod.Module(net, data_names=("data",), label_names=("label",),
                        context=ctx)
    mod.bind(data_shapes=[mx.io.DataDesc("data", (bs, 3, size, size), dtype)],
             label_shapes=[mx.io.DataDesc("label", (bs, max_obj, obj_w))])
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.002,
                                         "momentum": 0.9, "wd": 5e-4})
    rng = np.random.RandomState(0)
    label = np.full((bs, max_obj, obj_w), -1.0, np.float32)
    for i in range(bs):
        for j in range(rng.randint(1, max_obj + 1)):
            x1, y1 = rng.uniform(0, 0.5, 2)
            w, h = rng.uniform(0.2, 0.5, 2)
            label[i, j] = [rng.randint(0, num_classes), x1, y1,
                           min(1.0, x1 + w), min(1.0, y1 + h)]
    data = mx.nd.array(
        rng.uniform(-1, 1, (bs, 3, size, size)).astype(np.float32),
        dtype=dtype)
    batch = mx.io.DataBatch(data=[data], label=[mx.nd.array(label)])
    train_rate, steady, finite = _train_leg(mx, mod, batch, k, depth,
                                            windows, warmup, bs)

    det = models.ssd.get_symbol(num_classes=num_classes, data_shape=size,
                                dtype=dtype)
    imod = mx.mod.Module(det, data_names=("data",), label_names=None,
                         context=ctx)
    imod.bind(data_shapes=[mx.io.DataDesc("data", (bs, 3, size, size),
                                          dtype)],
              for_training=False)
    imod.init_params(initializer=mx.init.Xavier())
    infer_rate, _ = _forward_rate(mx, imod, batch, infer_iters, warmup)
    fwd = _fwd_flops(models, net, data=(bs, 3, size, size),
                     label=(bs, max_obj, obj_w))
    return _workload_record(jax, on_tpu, train_rate, infer_rate, dtype, k,
                            depth, steady, fwd, finite=finite)


def _suite_lstm(mx, models, jax, on_tpu, dtype, cfg):
    """Bucketed LSTM-PTB: a materialized synthetic epoch chunks into
    K-batch windows through BucketingModule.train_window (grouped by
    bucket, one fused program per (bucket, group size) — after the warmup
    epoch every program is cached, so the timed epochs dispatch with zero
    compiles and zero per-batch host syncs). RNN legs run f32: the
    low-precision recipes cover the conv trunks, not the recurrent
    matmuls."""
    del dtype  # rnn leg is f32 by design; record says so explicitly
    k, depth, windows, warmup, _ = cfg
    bs = 32 if on_tpu else 8
    hidden = embed = 200 if on_tpu else 32
    vocab = 10000 if on_tpu else 100
    buckets = [16, 32] if on_tpu else [8, 16]
    rs = np.random.RandomState(0)
    sents = [[int(x) for x in rs.randint(1, vocab, int(rs.choice(buckets)))]
             for _ in range(bs * (8 if on_tpu else 4))]
    it = mx.rnn.BucketSentenceIter(sents, bs, buckets=buckets,
                                   invalid_label=0)
    sym_gen, state_names = models.lstm_lm_sym_gen(
        num_hidden=hidden, num_layers=2, num_embed=embed, vocab_size=vocab)
    ctx = mx.gpu() if on_tpu else mx.cpu()
    mod = mx.mod.BucketingModule(sym_gen=sym_gen,
                                 default_bucket_key=it.default_bucket_key,
                                 state_names=state_names, context=ctx)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(initializer=mx.init.Xavier(factor_type="in",
                                               magnitude=2.34))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    # one materialized epoch, reused verbatim every timed pass: identical
    # chunking -> identical (bucket, group size) pairs -> pure cache picks
    batches = list(it)
    chunks = [batches[i:i + k] for i in range(0, len(batches), k)]
    for _ in range(warmup):
        for ch in chunks:
            mod.train_window(None, batches=ch, publish_grads=False).wait()

    from collections import deque

    mx.telemetry.reset()
    inflight = deque()
    last = None
    tic = time.time()
    for _ in range(windows):
        for ch in chunks:
            last = mod.train_window(None, batches=ch, publish_grads=False)
            inflight.append(last)
            while len(inflight) > depth:
                inflight.popleft().wait()
    while inflight:
        inflight.popleft().wait()
    _boundary_fence(last)
    dt = time.time() - tic
    train_rate = windows * len(batches) * bs / dt
    steady = _steady_compiles(mx)
    finite = bool(last is not None and last._outs and np.all(
        np.isfinite(np.asarray(last._outs[0], dtype=np.float32))))

    # infer: forward-only through the bound bucket programs (samples are
    # sequences); flops = bucket-length-weighted forward estimate
    fb = next(b for b in batches if b.bucket_key == it.default_bucket_key)
    for _ in range(2):
        mod.forward(fb, is_train=False)
        mod.get_outputs()[0]._data
    np.asarray(mod.get_outputs()[0]._data.ravel()[:1])
    tic = time.time()
    iters = max(1, 2 * len(batches))
    for _ in range(iters):
        mod.forward(fb, is_train=False)
        mod.get_outputs()[0]._data
    np.asarray(mod.get_outputs()[0]._data.ravel()[:1])
    infer_rate = bs * iters / (time.time() - tic)

    counts = {}
    for b in batches:
        counts[b.bucket_key] = counts.get(b.bucket_key, 0) + 1
    fwd, tot = 0.0, 0
    for length, c in counts.items():
        shapes = {"data": (bs, length), "softmax_label": (bs, length)}
        for sn in state_names:
            shapes[sn] = (bs, hidden)
        f = _fwd_flops(models, sym_gen(length)[0], **shapes)
        if f:
            fwd, tot = fwd + f * c, tot + c
    return _workload_record(jax, on_tpu, train_rate, infer_rate, "float32",
                            k, depth, steady, fwd / tot if tot else None,
                            finite=finite)


def _suite_dcgan(mx, models, jax, on_tpu, dtype, cfg):
    """DCGAN: the alternating G/D step is one fused device-resident
    program (GANModule.train_window, in-graph latent sampling). The record
    carries the reference imperative loop's rate too
    (legacy_train_samples_per_sec) so the fused-vs-legacy win is pinned in
    the scoreboard. Train cost/sample ≈ 3 G passes + 9 D passes (three D
    forwards, two with full backward, one for input grads); infer is pure
    G generation."""
    del dtype  # GAN leg is f32 (reference recipe); record says so
    k, depth, windows, warmup, infer_iters = cfg
    bs = 64 if on_tpu else 4
    z_dim = 100 if on_tpu else 16
    nf = 64 if on_tpu else 8
    ctx = mx.gpu() if on_tpu else mx.cpu()
    mx.random.seed(0)
    g_sym = models.dcgan_generator(ngf=nf, nc=3)
    d_sym = models.dcgan_discriminator(ndf=nf)
    gan = mx.mod.GANModule(g_sym, d_sym, context=ctx, batch_size=bs,
                           code_shape=(z_dim, 1, 1), data_shape=(3, 64, 64))
    gan.bind()
    gan.init_params()
    gan.init_optimizer()
    rng = np.random.RandomState(0)
    real = mx.nd.array(rng.rand(bs, 3, 64, 64).astype(np.float32) * 2 - 1)
    for _ in range(warmup):
        gan.train_window(real, k).wait()
    _boundary_fence(gan.train_window(real, k))
    train_rate, steady, finite = _pipelined_windows(
        mx, lambda: gan.train_window(real, k), windows, depth, bs * k)

    # reference imperative loop on the same per-window step count — its
    # rate is the fused path's acceptance floor. The boundary's outputs
    # are the PRE-update real-pass reads, so fencing them would leave the
    # trailing G/D updates untimed (the fused program can't cheat that
    # way: any output fetch forces the whole XLA call) — fence on the
    # updated weights instead.
    def weight_fence():
        for m in (gan.mod_g, gan.mod_d):
            exe = m._exec_group._exec
            name = next(iter(exe.arg_dict))
            np.asarray(exe.arg_dict[name]._data.ravel()[:1])

    gan._serial_window([real] * k, None)  # warm the serial-path programs
    weight_fence()
    tic = time.time()
    legacy_windows = max(1, windows // 2) if on_tpu else windows
    for _ in range(legacy_windows):
        gan._serial_window([real] * k, None)
    weight_fence()
    legacy_rate = bs * k * legacy_windows / (time.time() - tic)

    imod = mx.mod.Module(g_sym, data_names=("rand",), label_names=None,
                         context=ctx)
    imod.bind(data_shapes=[mx.io.DataDesc("rand", (bs, z_dim, 1, 1))],
              for_training=False)
    imod.init_params(initializer=mx.init.Normal(0.02))
    noise = mx.nd.random_normal(loc=0, scale=1, shape=(bs, z_dim, 1, 1))
    infer_rate, _ = _forward_rate(
        mx, imod, mx.io.DataBatch(data=[noise], label=[]), infer_iters, 2)

    g_fwd = _fwd_flops(models, g_sym, rand=(bs, z_dim, 1, 1))
    d_fwd = _fwd_flops(models, d_sym, data=(bs, 3, 64, 64), label=(bs,))
    train_flops = 3.0 * (g_fwd + 3.0 * d_fwd) if g_fwd and d_fwd else None
    rec = _workload_record(jax, on_tpu, train_rate, infer_rate, "float32",
                           k, depth, steady, g_fwd, train_flops=train_flops,
                           finite=finite)
    rec["legacy_train_samples_per_sec"] = round(legacy_rate, 2)
    rec["fused_speedup"] = round(train_rate / legacy_rate, 3)
    return rec


_SUITE_RUNNERS = (
    ("mlp", _suite_mlp),
    ("lenet", _suite_lenet),
    ("resnet-50", _suite_resnet50),
    ("lstm-ptb", _suite_lstm),
    ("ssd-vgg16", _suite_ssd),
    ("dcgan", _suite_dcgan),
)


def _run_suite_mode(mx, models, jax, on_tpu):
    """BENCH_MODE=suite: one JSON scoreboard covering every BASELINE
    workload; headline value is the geomean train samples/s (unit-hostile
    across workloads, but stable under proportional regressions — the
    bench_compare gate diffs the per-workload fields)."""
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16" if on_tpu else "float32")
    cfg = _suite_cfg(on_tpu)
    subset = os.environ.get("BENCH_SUITE_WORKLOADS")
    wanted = ([n.strip() for n in subset.split(",") if n.strip()]
              if subset else [n for n, _ in _SUITE_RUNNERS])
    runners = dict(_SUITE_RUNNERS)
    unknown = [n for n in wanted if n not in runners]
    if unknown:
        raise SystemExit(f"BENCH_SUITE_WORKLOADS: unknown {unknown}; "
                         f"have {[n for n, _ in _SUITE_RUNNERS]}")
    workloads = {}
    for name in wanted:
        print(f"suite: {name} ...", file=sys.stderr)
        workloads[name] = runners[name](mx, models, jax, on_tpu, dtype, cfg)
    rates = [w["train_samples_per_sec"] for w in workloads.values()]
    record = {
        "metric": "whole_zoo_suite" + ("" if on_tpu else "_cpusmoke"),
        "value": round(float(np.exp(np.mean(np.log(rates)))), 2),
        "unit": "geomean train samples/sec",
        "window_k": cfg[0],
        "dispatch_depth": cfg[1],
        "workloads": workloads,
    }
    _maybe_mesh(record, mx)
    _stamp_device_recipe(record, mx, models, on_tpu, dtype)
    print(json.dumps(record))


def _run_score_mode(mx, models, jax, on_tpu):
    """BENCH_MODE=score: the published-table inference sweep. The symbol
    list AND the scoring loop live in one place each (models.SCORE_SYMBOLS,
    examples/benchmark_score.score) so this mode cannot drift from the
    example. BENCH_SCORE_NETS subsets for cpu smoke."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "examples"))
    import benchmark_score

    subset = os.environ.get("BENCH_SCORE_NETS")
    networks = ([n.strip() for n in subset.split(",") if n.strip()]
                if subset else list(models.SCORE_SYMBOLS))
    bs = int(os.environ.get("BENCH_SCORE_BATCH", 32 if on_tpu else 2))
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16" if on_tpu else "float32")
    iters = int(os.environ.get("BENCH_ITERS", 20 if on_tpu else 2))
    side = int(os.environ.get("BENCH_IMAGE", 224))
    image = (3, side, side)
    results = {}
    for net in networks:
        print(f"score: {net} ...", file=sys.stderr)
        rate = benchmark_score.score(net, bs, image, dtype, iters=iters,
                                     warmup=3 if on_tpu else 1)
        entry = {"samples_per_sec": round(rate, 2)}
        fwd = _fwd_flops(models, models.zoo.get_symbol(net),
                         data=(bs,) + image)
        if fwd:
            entry["gflops_per_sample_fwd"] = round(fwd / 1e9, 3)
            _maybe_mfu(entry, rate, jax, on_tpu, dtype, fwd)
        results[net] = entry
    rates = [e["samples_per_sec"] for e in results.values()]
    record = {
        "metric": "zoo_score_sweep" + ("" if on_tpu else "_cpusmoke"),
        "value": round(float(np.exp(np.mean(np.log(rates)))), 2),
        "unit": "geomean images/sec",
        "batch_size": bs,
        "dtype": dtype,
        "networks": results,
    }
    _maybe_mesh(record, mx)
    print(json.dumps(record))


# ---------------------------------------------------------------------------
# BENCH_MODE=io — the decode plane alone: img/s vs worker count. The
# scaling curve is the tentpole evidence that the parallel pool can feed
# the chip at device rate; serial (use_pool=0) is the baseline.
# ---------------------------------------------------------------------------
def _run_io_mode(mx, on_tpu):
    """BENCH_MODE=io: ImageRecordIter decode+augment throughput, serial
    vs pooled at 1/2/4/... workers, over a generated synthetic-JPEG .rec.
    Emits one JSON record: value = best pooled img/s, pool_speedup =
    best/serial (the gated ratio), scaling = the full curve."""
    import tempfile

    image = (3, 224, 224) if on_tpu else (3, 48, 48)
    batch_size = int(os.environ.get("BENCH_IO_BATCH", 32 if on_tpu else 16))
    records = int(os.environ.get("BENCH_IO_RECORDS",
                                 2048 if on_tpu else 320))
    passes = int(os.environ.get("BENCH_IO_PASSES", 2))
    workers = [int(w) for w in os.environ.get(
        "BENCH_IO_WORKERS", "1,2,4,8" if on_tpu else "1,2,4").split(",")]
    td = tempfile.mkdtemp(prefix="bench_io_")
    path = _write_bench_rec(mx, os.path.join(td, "bench.rec"), records, image)

    def rate(**kw):
        it = mx.io.ImageRecordIter(
            path_imgrec=path, data_shape=image, batch_size=batch_size,
            rand_crop=True, rand_mirror=True, shuffle=True, seed=0, **kw)
        for _ in it:       # warm epoch: readers, pool spin-up, page cache
            pass
        best = 0.0
        for _ in range(passes):
            it.reset()
            n, tic = 0, time.time()
            for _ in it:
                n += batch_size
            best = max(best, n / (time.time() - tic))
        it.close()
        return best

    mx.telemetry.reset()
    serial = rate(use_pool=False, preprocess_threads=1)
    scaling, best, best_workers = {}, 0.0, workers[0]
    for w in workers:
        r = rate(use_pool=True, preprocess_threads=w)
        scaling[str(w)] = round(r, 2)
        if r > best:
            best, best_workers = r, w
    from mxnet_tpu import native as _native

    record = {
        "metric": "io_plane_decode" + ("" if on_tpu else "_cpusmoke"),
        "value": round(best, 2),
        "unit": "images/sec",
        "serial_img_per_sec": round(serial, 2),
        "pool_speedup": round(best / serial, 3) if serial else 0.0,
        "workers_best": best_workers,
        "scaling": scaling,
        "records": records,
        "native_plane": bool(_native.available()),
        "cpu_count": os.cpu_count(),
        "telemetry": mx.telemetry.snapshot(),
    }
    print(json.dumps(record))


def main():
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import models

    on_tpu = jax.devices()[0].platform != "cpu"
    mode = os.environ.get("BENCH_MODE", "train")  # "train" | "fit"
    batch_size = int(os.environ.get("BENCH_BATCH", 128 if on_tpu else 8))
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16" if on_tpu else "float32")
    fused = max(1, int(os.environ.get("BENCH_FUSED_STEPS", 20 if on_tpu else 1)))
    warmup = 5 if on_tpu else 2
    iters = int(os.environ.get("BENCH_ITERS", 25 if on_tpu else 3))
    # iters counts STEPS; dispatches per timed window = ceil(iters/fused)
    windows = max(1, int(os.environ.get("BENCH_WINDOWS", 4 if on_tpu else 1)))
    num_layers = int(os.environ.get("BENCH_LAYERS", 50))
    image = (3, 224, 224) if on_tpu else (3, 64, 64)

    if mode == "suite":
        _run_suite_mode(mx, models, jax, on_tpu)
        return

    if mode == "score":
        _run_score_mode(mx, models, jax, on_tpu)
        return

    if mode == "serve":
        _run_serve_mode(mx, models, image, num_layers, on_tpu)
        return

    if mode == "ckpt":
        _run_ckpt_mode(mx, models, batch_size, image, dtype, num_layers,
                       on_tpu)
        return

    if mode == "io":
        _run_io_mode(mx, on_tpu)
        return

    sweep = None
    if mode == "fit":
        # the real training loop defaults to the framework's intended
        # steady state on the chip: adaptive fused windows + pipelined
        # dispatch (the scheduler co-tunes K and depth from the probe).
        # CPU smoke keeps the env-driven default (tests opt in explicitly).
        if on_tpu:
            os.environ.setdefault("MXNET_TRAIN_WINDOW", "auto")
        if os.environ.get("BENCH_SWEEP") == "1":
            sweep = _sweep_fit(mx, models, batch_size, image, dtype,
                               num_layers, on_tpu, max(iters, 2))
        elif os.environ.get("BENCH_SWEEP") == "xla":
            sweep = _sweep_xla(mx, models, batch_size, image, dtype,
                               num_layers, on_tpu, max(iters, 2))

    mod = _build_module(mx, models, batch_size, image, dtype, num_layers,
                        on_tpu)

    if mode == "fit":
        # MXNET_TELEMETRY=1: run the fit epochs under jax's profiler; the
        # program's spans are TraceMes on its host plane, so the trace it
        # writes is the one Chrome/Perfetto timeline
        tracing = mx.telemetry.spans_enabled()
        if tracing:
            trace_out = os.environ.get("BENCH_TRACE_OUT", "bench_trace.json")
            mx.profiler.profiler_set_config(filename=trace_out)
            mx.profiler.profiler_set_state("run")
        # _run_fit_mode resets telemetry again at the first epoch boundary
        # so the snapshot covers the steady-state epochs only
        mx.telemetry.reset()
        fit_data = os.environ.get("BENCH_FIT_DATA", "synthetic")
        img_per_sec, spread, cold_compile_s = _run_fit_mode(
            mx, mod, batch_size, image, dtype, max(iters, 2), max(windows, 2),
            fit_data=fit_data)
        snapshot = mx.telemetry.snapshot()
        record = {
            "metric": f"resnet{num_layers}_fit_throughput"
                      + ("_recordio" if fit_data == "recordio" else "")
                      + ("" if on_tpu else "_cpusmoke"),
            "fit_data": fit_data,
            "value": round(img_per_sec, 2),
            "unit": "images/sec",
            "vs_baseline": round(img_per_sec / BASELINE_IMG_PER_SEC, 3),
            "spread": round(spread, 4),
            "cold_compile_s": round(cold_compile_s, 3),
            "telemetry": snapshot,
        }
        _maybe_mfu(record, img_per_sec, jax, on_tpu, dtype,
                   _resnet_train_flops(models, num_layers, image, batch_size))
        _maybe_mesh(record, mx)
        _stamp_device_recipe(record, mx, models, on_tpu, dtype)
        window_k = mx.telemetry.gauge("fit.train_window_k").value
        if window_k:
            record["train_window_k"] = window_k
        _fit_phase_fields(record, snapshot)
        if sweep is not None:
            record["sweep"] = sweep
            if os.environ.get("BENCH_SWEEP") == "xla":
                # the adopted winner (what the headline number ran under)
                record["best_xla_flags"] = os.environ.get(
                    "MXNET_XLA_FLAGS", "")
        if tracing:
            trace = mx.profiler.dump_profile()  # stops the trace
            snap_path, prom_path = mx.telemetry.dump(
                os.environ.get("BENCH_TELEMETRY_OUT", "bench_telemetry.json"))
            record["trace"] = trace
            record["telemetry_snapshot"] = snap_path
            # attribute per-kernel device time straight off the timeline
            # the run already paid for
            record["kernels"] = _kernel_rows(mx) if trace else []
            print(f"trace: {trace}  snapshot: {snap_path} {prom_path}",
                  file=sys.stderr)
        if "kernels" not in record or not record["kernels"]:
            rng = np.random.RandomState(3)
            abatch = mx.io.DataBatch(
                data=[mx.nd.array(rng.uniform(-1, 1, (batch_size,) + image)
                                  .astype(np.float32), dtype=dtype)],
                label=[mx.nd.array(rng.randint(0, 1000, (batch_size,))
                                   .astype(np.float32))])
            record["kernels"] = _kernel_attribution(
                mx, mod, abatch, int(record.get("train_window_k") or 2))
        # AFTER the trace dump: the fresh module's recompile must not
        # pollute the steady-state timeline the trace documents
        if os.environ.get("BENCH_WARM_START", "1") != "0":
            record["warm_start_s"] = _time_warm_start(
                mx, models, batch_size, image, dtype, num_layers, on_tpu)
        print(json.dumps(record))
        return

    rng = np.random.RandomState(0)
    data = mx.nd.array(
        rng.uniform(-1, 1, (batch_size,) + image).astype(np.float32), dtype=dtype
    )
    label = mx.nd.array(rng.randint(0, 1000, (batch_size,)).astype(np.float32))
    batch = mx.io.DataBatch(data=[data], label=[label])

    def run_steps(n):
        # n train steps, dispatched as training windows of `fused` steps.
        # Windows run with lazy boundary publication (publish_grads=False):
        # nothing in this loop reads gradients, so the final step's f32
        # gradient materialization is dead-coded out of the program — the
        # same contract the pipelined fit loop uses. fence() still works:
        # outputs stay published.
        done = 0
        while done < n:
            k = min(fused, n - done)
            if k > 1:
                mod.train_window(batch, k, publish_grads=False)
            else:
                mod.forward_backward(batch)
                mod.update()
            done += k

    def fence():
        # a device->host fetch is a barrier that holds on every backend;
        # the last step's output depends on the whole step chain, so one
        # scalar fetch fences everything
        np.asarray(mod.get_outputs()[0]._data[0, :1])

    # warmup in whole windows too: a trailing partial window would compile
    # an extra program shape the timed region never uses; its duration is
    # where XLA compilation lives, reported as cold_compile_s
    tic = time.time()
    run_steps(((max(warmup, 2 * fused) + fused - 1) // fused) * fused)
    fence()
    cold_compile_s = round(time.time() - tic, 3)
    mx.telemetry.reset()  # snapshot covers the timed steady state only

    # several independently-timed windows: the reported value is the
    # median window, and the spread (max-min)/median is emitted so a
    # noisy host can't silently swing the headline number
    # round steps up to whole windows: a partial window would compile a
    # second program shape for no measurement benefit
    iters = ((max(iters, fused) + fused - 1) // fused) * fused
    rates = []
    for _ in range(windows):
        tic = time.time()
        run_steps(iters)
        fence()
        rates.append(batch_size * iters / (time.time() - tic))
    import statistics

    rates.sort()
    img_per_sec = statistics.median(rates)
    spread = (rates[-1] - rates[0]) / img_per_sec if windows > 1 else 0.0
    record = {
        "metric": f"resnet{num_layers}_train_throughput"
                  + ("" if on_tpu else "_cpusmoke"),
        "value": round(img_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": round(img_per_sec / BASELINE_IMG_PER_SEC, 3),
        "spread": round(spread, 4),
        "cold_compile_s": cold_compile_s,
        "telemetry": mx.telemetry.snapshot(),
    }
    if os.environ.get("BENCH_WARM_START", "1") != "0":
        record["warm_start_s"] = _time_warm_start(
            mx, models, batch_size, image, dtype, num_layers, on_tpu,
            fused=fused)
    if os.environ.get("BENCH_GUARD", "1") != "0" and \
            not os.environ.get("MXNET_NONFINITE_GUARD"):
        # the non-finite sentinel's cost must stay visible: re-time the
        # same steady-state loop with MXNET_NONFINITE_GUARD=skip (one
        # extra all-finite reduce folded into the fused step — read per
        # fused call, so flipping the env here compiles the guarded
        # program and nothing else changes). Expected <2% delta.
        os.environ["MXNET_NONFINITE_GUARD"] = "skip"
        try:
            run_steps(2 * fused)  # compile + warm the guarded program
            fence()
            g_rates = []
            for _ in range(windows):
                tic = time.time()
                run_steps(iters)
                fence()
                g_rates.append(batch_size * iters / (time.time() - tic))
            guard_rate = statistics.median(g_rates)
        finally:
            del os.environ["MXNET_NONFINITE_GUARD"]
        record["guard_on_img_per_sec"] = round(guard_rate, 2)
        record["nonfinite_guard_overhead"] = round(
            1.0 - guard_rate / img_per_sec, 4)
    _maybe_mfu(record, img_per_sec, jax, on_tpu, dtype,
               _resnet_train_flops(models, num_layers, image, batch_size))
    _maybe_mesh(record, mx)
    print(json.dumps(record))


if __name__ == "__main__":
    main()
