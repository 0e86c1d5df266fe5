"""Inference benchmark (reference
``example/image-classification/benchmark_score.py``): forward-only scoring
throughput on synthetic data across networks and batch sizes.

Reference baselines (docs/how_to/perf.md:110-147): ResNet-50 score @bs32 —
713 img/s P100, 62 img/s 36-vCPU C4.8xlarge.

  python examples/benchmark_score.py                      # sweep
  python examples/benchmark_score.py --network resnet-50 --batch-size 32 --json
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import mxnet_tpu as mx
from mxnet_tpu import models


def get_symbol(network, **kwargs):
    # the registry: mxnet_tpu/models/zoo.py
    return models.zoo.get_symbol(network, num_classes=1000, **kwargs)


def score(network, batch_size, image_shape=(3, 224, 224), dtype="float32",
          iters=20, warmup=3, fold_bn=False):
    """img/s for forward-only inference, fenced by a device fetch.

    ``fold_bn`` applies the deployment-time BatchNorm fold
    (mx.contrib.fold_batchnorm) before scoring — ~+20% on ResNet-50/TPU.
    """
    sym = get_symbol(network)
    import jax

    on_accel = jax.devices()[0].platform != "cpu"
    ctx = mx.gpu() if on_accel else mx.cpu()
    data_shape = (batch_size,) + tuple(image_shape)
    mod = mx.mod.Module(sym, context=ctx)
    mod.bind(data_shapes=[mx.io.DataDesc("data", data_shape, dtype)],
             for_training=False)
    mod.init_params(initializer=mx.init.Xavier())
    if fold_bn:
        arg_p, aux_p = mod.get_params()
        sym, arg_p = mx.contrib.fold_batchnorm(sym, arg_p, aux_p)
        mod = mx.mod.Module(sym, context=ctx)
        mod.bind(data_shapes=[mx.io.DataDesc("data", data_shape, dtype)],
                 for_training=False)
        mod.set_params(arg_p, aux_p)
    rng = np.random.RandomState(0)
    data = mx.nd.array(
        rng.uniform(-1, 1, data_shape).astype(np.float32), dtype=dtype
    )
    batch = mx.io.DataBatch(data=[data], label=[])

    def dispatch():
        # forward() is lazy; touching the output's device buffer dispatches
        # the XLA execution WITHOUT a host round-trip, so iterations queue
        # back-to-back on the device (an unread forward would otherwise be
        # superseded by the next and never run)
        mod.forward(batch, is_train=False)
        mod.get_outputs()[0]._data

    def fence():
        np.asarray(mod.get_outputs()[0]._data[0, :1])

    for _ in range(warmup):
        dispatch()
    fence()
    tic = time.time()
    for _ in range(iters):
        dispatch()
    fence()
    return batch_size * iters / (time.time() - tic)


def main():
    parser = argparse.ArgumentParser(description="inference benchmark")
    parser.add_argument("--network", type=str, default=None,
                        help="one network instead of the sweep")
    parser.add_argument("--batch-size", type=int, default=0,
                        help="one batch size instead of the sweep")
    parser.add_argument("--dtype", type=str, default=None)
    parser.add_argument("--image-shape", type=str, default="3,224,224")
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--fold-bn", action="store_true",
                        help="fold BatchNorm into convs before scoring")
    parser.add_argument("--json", action="store_true",
                        help="print one JSON line (bench-driver format)")
    args = parser.parse_args()

    import jax

    on_accel = jax.devices()[0].platform != "cpu"
    dtype = args.dtype or ("bfloat16" if on_accel else "float32")
    image_shape = tuple(int(x) for x in args.image_shape.split(","))
    networks = [args.network] if args.network else \
        list(models.SCORE_SYMBOLS)
    batch_sizes = [args.batch_size] if args.batch_size else [1, 32]

    results = {}
    for net in networks:
        for bs in batch_sizes:
            speed = score(net, bs, image_shape, dtype, iters=args.iters,
                          fold_bn=args.fold_bn)
            results[(net, bs)] = speed
            if not args.json:
                print(f"network: {net:14s} batch size: {bs:4d} "
                      f"dtype: {dtype} image/sec: {speed:.2f}")
    if args.json:
        (net, bs), speed = max(results.items(), key=lambda kv: kv[1])
        record = {
            "metric": f"{net}_score_throughput_bs{bs}",
            "value": round(speed, 2),
            "unit": "images/sec",
        }
        if net == "resnet-50" and bs == 32:
            # the published baseline is resnet-50 @ bs32 only
            # (P100, docs/how_to/perf.md:138-147)
            record["vs_baseline"] = round(speed / 713.17, 3)
        print(json.dumps(record))


if __name__ == "__main__":
    main()
