#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the framework's main path once, in ONE process, through the entry
points a user calls, at the full width of the north-star model (ResNet-50,
1000 classes, 224x224, batch 128, bfloat16; weights random from a seed):

* train leg — ``Module.fit`` over the resident synthetic iterator of
  ``examples/common/fit.py`` (the code path of ``train_imagenet.py
  --benchmark 1``): one epoch of 8 single-step batches, then one epoch under
  ``MXNET_TRAIN_WINDOW=4 MXNET_DISPATCH_DEPTH=2`` so the fused K-step window
  and pipelined dispatch both run;
* serve leg — a ``ModelServer`` on the same symbol and the weights just
  trained, one bucket of 8, three ``predict`` calls, answers compared with
  the trained module's own inference forward.

``--chips 4`` runs the train leg data-parallel over four chips (global batch
512) and checks that the batch really is sharded over four devices.

It needs a TPU: with any other default jax backend it prints one line and
exits non-zero at once (``JAX_PLATFORMS=cpu python chip_smoke.py`` fails in
seconds). Any failed check raises — nothing is caught and carried on from.
On success the last line of stdout is one JSON object naming the device.
Run it on the chip with ``chiprun -- python chip_smoke.py``.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

BATCH_PER_CHIP = 128
IMAGE = (3, 224, 224)
NUM_CLASSES = 1000
NUM_LAYERS = 50
EPOCH_BATCHES = 8
WINDOW_K = 4
DISPATCH_DEPTH = 2
SERVE_BUCKET = 8
SERVE_REQUESTS = 3
# |server - module| on softmax probabilities: both run the bfloat16 trunk,
# the server with its BatchNorms folded into the convolutions
SERVE_ATOL = 0.05

_SYNC_COUNTERS = ("ndarray.asnumpy", "ndarray.wait_to_read")
_COMPILE_COUNTERS = ("executor.jit_compile", "executor.fused_plan_compile")
_FALLBACK_COUNTERS = ("aot.exec_fallback", "aot.compile_fallback")


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def report(leg, **fields):
    print(json.dumps({"leg": leg, **fields}), flush=True)


class CompileClock:
    """Seconds jax spent in backend compiles, and how its persistent
    compilation cache answered, read from jax's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_writes = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_kw):
        if name.endswith("backend_compile_duration"):
            self.compile_s += secs

    def _on_event(self, name, **_kw):
        if name.endswith("/cache_hits"):
            self.cache_hits += 1
        elif name.endswith("/cache_misses"):  # recorded at entry write
            self.cache_writes += 1

    def snapshot(self):
        return {"compile_s": round(self.compile_s, 2),
                "cache_hits": self.cache_hits,
                "cache_writes": self.cache_writes}


def _counters(tm, names):
    return {n: tm.counter(n).value for n in names}


def _on_devices(arr, devices):
    return set(arr.devices()) == set(devices)


def _entries(cache_dir):
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def train_leg(mx, ctxs, batch, image, num_layers, num_classes, clock):
    """Two one-epoch ``Module.fit`` calls on one module: per-batch fused
    steps, then K-step windows at dispatch depth 2. Returns the trained
    module and the resident iterator."""
    sys.path.insert(0, os.path.join(ROOT, "examples"))
    from common.fit import SyntheticDataIter

    from mxnet_tpu import models
    from mxnet_tpu import telemetry as tm

    devices = [c.jax_device() for c in ctxs]
    layout = models.recipe.conv_layout(ctxs[0])
    report("train.config", network=f"resnet-{num_layers}",
           num_classes=num_classes, image=list(image), batch=batch,
           dtype="bfloat16", contexts=[str(c) for c in ctxs],
           conv_layout=layout, epoch_batches=EPOCH_BATCHES,
           window_k=WINDOW_K, dispatch_depth=DISPATCH_DEPTH)
    if devices[0].platform == "tpu":
        check(layout == "NHWC", f"conv layout on TPU is {layout}, not NHWC")

    np.random.seed(0)  # SyntheticDataIter draws from the global generator
    mx.random.seed(0)
    sym = models.resnet(num_classes=num_classes, num_layers=num_layers,
                        image_shape=",".join(map(str, image)))
    train = SyntheticDataIter(num_classes, (batch,) + tuple(image),
                              EPOCH_BATCHES, "bfloat16")
    labels = train.label.asnumpy().astype(np.int64)
    mod = mx.mod.Module(sym, context=ctxs)

    boundaries = []  # (wall time, compile counters, output future)

    def on_boundary(_param):
        # no host sync here: the output stays a device future until the
        # epoch is over
        boundaries.append((time.time(), _counters(tm, _COMPILE_COUNTERS),
                           mod.get_outputs()[0]._data))

    def one_epoch(epoch, window):
        boundaries.clear()
        if window:
            os.environ.update(MXNET_TRAIN_WINDOW=str(WINDOW_K),
                              MXNET_DISPATCH_DEPTH=str(DISPATCH_DEPTH))
        else:
            os.environ.pop("MXNET_TRAIN_WINDOW", None)
            os.environ.pop("MXNET_DISPATCH_DEPTH", None)
        metric = mx.metric.Accuracy()
        sync0 = _counters(tm, _SYNC_COUNTERS)
        compile0 = clock.compile_s
        tic = time.time()
        mod.fit(train, num_epoch=epoch + 1, begin_epoch=epoch,
                eval_metric=metric, kvstore="device", optimizer="sgd",
                optimizer_params={"learning_rate": 0.05, "momentum": 0.9,
                                  "wd": 1e-4},
                initializer=mx.init.Xavier(rnd_type="gaussian",
                                           factor_type="in", magnitude=2),
                batch_end_callback=on_boundary)
        wall = time.time() - tic
        sync = {n: tm.counter(n).value - sync0[n] for n in _SYNC_COUNTERS}
        want = EPOCH_BATCHES // WINDOW_K if window else EPOCH_BATCHES
        check(len(boundaries) == want,
              f"epoch {epoch}: {len(boundaries)} boundaries, wanted {want}")
        # steady state: nothing compiles after the first boundary
        first = boundaries[0][1]
        for _t, counts, _o in boundaries[1:]:
            check(counts == first,
                  f"epoch {epoch}: compile after the first batch: "
                  f"{first} -> {counts}")
        check(all(v == 0 for v in sync.values()),
              f"epoch {epoch}: host syncs inside the epoch: {sync}")
        losses, accs = [], []
        for _t, _c, out in boundaries:
            check(_on_devices(out, devices),
                  f"epoch {epoch}: outputs on {out.devices()}")
            p = np.asarray(out, dtype=np.float32)
            check(p.shape == (batch, num_classes), f"output shape {p.shape}")
            check(np.isfinite(p).all(), f"epoch {epoch}: non-finite output")
            losses.append(0.0 + float(-np.log(np.maximum(
                p[np.arange(batch), labels], 1e-30)).mean()))
            accs.append(float((p.argmax(axis=1) == labels).mean()))
        check(np.isfinite(losses).all(), f"epoch {epoch}: loss {losses}")
        report("train.epoch", epoch=epoch,
               mode=f"window K={WINDOW_K} depth={DISPATCH_DEPTH}"
               if window else "one fused step per batch",
               boundaries=len(boundaries),
               loss=[round(v, 4) for v in losses],
               accuracy=[round(v, 4) for v in accs],
               fit_accuracy=round(metric.get()[1], 4),
               wall_s=round(wall, 2),
               compile_s=round(clock.compile_s - compile0, 2),
               first_boundary_s=round(boundaries[0][0] - tic, 2),
               host_syncs=sync, compiles=first)
        return losses, accs, metric.get()[1]

    loss0, accs0, fit_acc0 = one_epoch(0, window=False)
    loss1, accs1, fit_acc1 = one_epoch(1, window=True)
    check(tm.gauge("fit.windows_in_flight").max >= DISPATCH_DEPTH,
          "pipelined dispatch never had "
          f"{DISPATCH_DEPTH} windows in flight")
    check(loss1[-1] < loss0[0],
          f"resident-batch loss did not fall: {loss0[0]} -> {loss1[-1]}")
    check(accs1[-1] > accs0[0] and fit_acc1 > fit_acc0,
          f"resident-batch accuracy did not rise: boundaries {accs0[0]} -> "
          f"{accs1[-1]}, fit metric {fit_acc0} -> {fit_acc1}")

    # where the state lives
    single = len(devices) == 1
    exe = mod._exec_group.execs[0]
    for name, arr in list(exe.arg_dict.items()) + list(exe.aux_dict.items()):
        check(_on_devices(arr._data, devices),
              f"{name} on {arr._data.devices()}, wanted {devices}")
    placement = {"params_and_aux": len(exe.arg_dict) + len(exe.aux_dict),
                 "devices": [str(d) for d in devices]}
    if not single:
        from jax.sharding import PartitionSpec as P

        data = exe.arg_dict["data"]._data
        check(data.sharding.spec == P("dp"),
              f"data sharding {data.sharding}")
        shard_devs = {s.device for s in data.addressable_shards}
        check(shard_devs == set(devices),
              f"data shards on {shard_devs}")
        check(all(s.data.shape[0] == batch // len(devices)
                  for s in data.addressable_shards), "uneven data shards")
        for name in mod._param_names:
            w = exe.arg_dict[name]._data
            check(w.sharding.is_fully_replicated
                  and len(w.addressable_shards) == len(devices),
                  f"{name} not replicated: {w.sharding}")
        in_use = [c.memory_stats()["bytes_in_use"] for c in ctxs]
        check(all(b > 0 for b in in_use), f"bytes_in_use {in_use}")
        placement.update(data_sharding=str(data.sharding.spec),
                         bytes_in_use=in_use)
    report("train.placement", **placement)
    return mod, train


def serve_leg(mx, mod, train, ctx, image, num_classes):
    """``ModelServer`` on the trained weights: three requests, answers
    checked against the module's own inference forward."""
    from mxnet_tpu import telemetry as tm
    from mxnet_tpu.serving import ModelServer, ServingConfig

    device = ctx.jax_device()
    arg_params, aux_params = mod.get_params()
    params = {f"arg:{k}": v for k, v in arg_params.items()}
    params.update({f"aux:{k}": v for k, v in aux_params.items()})

    # reference: the training module's inference forward on the resident
    # batch (moving BatchNorm statistics, unfolded graph)
    mod.forward(mx.io.DataBatch(data=[train.data], label=[train.label]),
                is_train=False)
    ref = mod.get_outputs()[0].asnumpy().astype(np.float32)
    samples = train.data.asnumpy().astype(np.float32)[:SERVE_REQUESTS]

    srv = ModelServer(mod.symbol, params, {"data": tuple(image)},
                      config=ServingConfig(buckets=(SERVE_BUCKET,),
                                           max_delay_ms=1.0),
                      dev_type=ctx.device_type, dev_id=ctx.device_id,
                      input_types={"data": "bfloat16"})
    try:
        srv.warmup()
        srv.start()
        check(len(srv.replicas) == 1, f"{len(srv.replicas)} replicas")
        check(srv.replicas[0].ctx.jax_device() == device,
              f"replica on {srv.replicas[0].device()}")
        pred = srv.predictor(SERVE_BUCKET)
        for name, arr in pred._exec.arg_dict.items():
            check(_on_devices(arr._data, [device]),
                  f"serving {name} on {arr._data.devices()}")
        compiles0 = _counters(tm, _COMPILE_COUNTERS)
        worst, top1 = 0.0, []
        for i in range(SERVE_REQUESTS):
            out = np.asarray(srv.predict({"data": samples[i]},
                                         timeout=300)[0])
            check(out.shape == (num_classes,), f"answer shape {out.shape}")
            check(np.isfinite(out).all(), "non-finite answer")
            check(abs(float(out.sum()) - 1.0) < 1e-2,
                  f"softmax sums to {out.sum()}")
            worst = max(worst, float(np.abs(out - ref[i]).max()))
            top1.append([int(out.argmax()), round(float(out.max()), 4),
                         int(ref[i].argmax()), round(float(ref[i].max()), 4)])
        check(_counters(tm, _COMPILE_COUNTERS) == compiles0,
              "a request compiled a program")
        check(worst <= SERVE_ATOL,
              f"server vs module inference: max |dp| {worst}")
        report("serve", bucket=SERVE_BUCKET, requests=SERVE_REQUESTS,
               replica_device=srv.replicas[0].device(),
               request_path_compiles=0,
               max_abs_diff_vs_module=round(worst, 5), atol=SERVE_ATOL,
               top1_class_prob_server_then_module=top1)
    finally:
        srv.close()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import mxnet_tpu as mx  # places the compile cache before any backend
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); jax found "
              f"{len(devs)} x {devs[0].platform} ({devs[0].device_kind})")
        return 1
    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="%(asctime)s %(message)s")
    import jaxlib

    from mxnet_tpu import telemetry as tm

    clock = CompileClock()
    cache_dir = jax.config.jax_compilation_cache_dir
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    report("start", device=device, jax=jax.__version__,
           jaxlib=jaxlib.__version__, compile_cache_dir=cache_dir,
           compile_cache_from_env=bool(
               os.environ.get("JAX_COMPILATION_CACHE_DIR")),
           cache_entries_at_start=_entries(cache_dir))

    tic = time.time()
    ctxs = [mx.tpu(i) for i in range(args.chips)]
    mod, train = train_leg(mx, ctxs, BATCH_PER_CHIP * args.chips, IMAGE,
                           NUM_LAYERS, NUM_CLASSES, clock)
    if args.chips == 1:
        serve_leg(mx, mod, train, ctxs[0], IMAGE, NUM_CLASSES)
    fallbacks = _counters(tm, _FALLBACK_COUNTERS)
    check(all(v == 0 for v in fallbacks.values()),
          f"AOT programs fell back to jit: {fallbacks}")
    report("done", wall_s=round(time.time() - tic, 2), **clock.snapshot(),
           **fallbacks, cache_entries_at_end=_entries(cache_dir))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
