#!/usr/bin/env python
"""Pre-populate the AOT executable cache for a model-zoo symbol + shapes.

Deployments warm the cache OUT OF BAND: run this once per (model, shape
set, backend) on the target host, and every later process that binds the
same signature starts with ``executor.jit_compile == 0`` — the forward,
train-step and (with ``--step``) fused train-update executables
deserialize from ``MXNET_AOT_CACHE_DIR`` instead of recompiling. See
``mxnet_tpu/aot.py`` and docs/architecture.md (AOT dispatch layer).

The cache is enabled for the run regardless of the ambient
``MXNET_AOT_CACHE`` value (populating it is the point); ``--cache-dir``
overrides ``MXNET_AOT_CACHE_DIR``.

Usage:
    python tools/aot_warm.py resnet --data-shape 128,3,224,224 \
        --model-arg num_layers=50 --dtype bfloat16
    python tools/aot_warm.py mlp --data-shape 32,784 --eval-only
    python tools/aot_warm.py lstm-bucketed ...   # not supported; use
        BucketingModule.compile(buckets=...) from python for bucketed models

Multiple ``--data-shape`` values warm one signature per shape (e.g. the
serving batch sizes). ``--step`` additionally runs one real optimizer step
per shape so the donated fused train program (the steady-state training
executable) lands in the cache too; ``--window K`` does the same for a
K-step training window.
"""

import argparse
import os
import sys

# runnable from a checkout without an installed package
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def _parse_shape(text):
    try:
        return tuple(int(x) for x in text.split(",") if x != "")
    except ValueError:
        raise argparse.ArgumentTypeError(f"bad shape {text!r}")


def _parse_model_arg(text):
    key, sep, val = text.partition("=")
    if not sep:
        raise argparse.ArgumentTypeError(f"--model-arg wants key=value, got {text!r}")
    for cast in (int, float):
        try:
            return key, cast(val)
        except ValueError:
            pass
    return key, val


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("model", help="model-zoo builder name (mxnet_tpu.models.<name>)")
    ap.add_argument("--data-shape", type=_parse_shape, action="append",
                    required=True, metavar="N,C,H,W",
                    help="full data shape incl. batch; repeatable")
    ap.add_argument("--label-name", default="softmax_label")
    ap.add_argument("--no-label", action="store_true",
                    help="symbol takes no label input")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--model-arg", type=_parse_model_arg, action="append",
                    default=[], metavar="KEY=VALUE",
                    help="forwarded to the model builder; repeatable")
    ap.add_argument("--eval-only", action="store_true",
                    help="warm the inference forward program only")
    ap.add_argument("--step", action="store_true",
                    help="also run one real optimizer step per shape so the "
                         "donated fused train executable is cached")
    ap.add_argument("--window", type=int, default=0, metavar="K",
                    help="with --step, also run a K-step training window in "
                         "both variants — repeat-batch (train_window(batch, "
                         "K)) and stacked-batches (Module.fit's "
                         "MXNET_TRAIN_WINDOW loop) — caching both window "
                         "executables")
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--cache-dir", default=None,
                    help="override MXNET_AOT_CACHE_DIR")
    args = ap.parse_args(argv)

    os.environ["MXNET_AOT_CACHE"] = "1"
    if args.cache_dir:
        os.environ["MXNET_AOT_CACHE_DIR"] = args.cache_dir

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import aot, models

    builder = getattr(models, args.model, None)
    if builder is None or not callable(builder):
        print(f"aot_warm: unknown model {args.model!r} "
              f"(see mxnet_tpu.models)", file=sys.stderr)
        return 2
    sym = builder(**dict(args.model_arg))

    on_tpu = mx.context.num_gpus() > 0
    ctx = mx.gpu() if on_tpu else mx.cpu()
    warmed = []
    for dshape in args.data_shape:
        label_names = () if args.no_label else (args.label_name,)
        mod = mx.mod.Module(sym, context=ctx, label_names=label_names)
        label_shapes = (None if args.no_label
                        else [mx.io.DataDesc(args.label_name, (dshape[0],))])
        mod.bind(
            data_shapes=[mx.io.DataDesc("data", dshape, args.dtype)],
            label_shapes=label_shapes,
            for_training=not args.eval_only,
        )
        mod.init_params(initializer=mx.init.Xavier())
        kinds = mod.compile()
        if args.step and not args.eval_only:
            mod.init_optimizer(optimizer=args.optimizer,
                               optimizer_params={"learning_rate": args.lr})
            rng = np.random.RandomState(0)
            batch = mx.io.DataBatch(
                data=[mx.nd.array(
                    rng.uniform(-1, 1, dshape).astype(np.float32),
                    dtype=args.dtype)],
                label=None if args.no_label else [mx.nd.array(
                    rng.randint(0, 2, (dshape[0],)).astype(np.float32))],
            )
            k = max(1, args.window)
            if k > 1:
                # both window program variants: repeat-batch
                # (train_window(batch, K)) AND stacked-batches
                # (what Module.fit's MXNET_TRAIN_WINDOW loop dispatches —
                # its data_stacks give the plan a different signature).
                # publish_grads=False matches the steady-state fit
                # pipeline: the publish flag is part of the plan
                # key AND the cache digest, so warming the publishing
                # variant would leave the real training loop compiling
                mod.train_window(batch, k, publish_grads=False)
                mod.train_window(None, batches=[batch] * k,
                                 publish_grads=False)
                kinds = kinds + [f"train_window(k={k})",
                                 f"train_window(k={k},stacked)"]
            else:
                mod.forward_backward(batch)
                mod.update()
                kinds = kinds + ["train_update(k=1)"]
            np.asarray(mod.get_outputs()[0]._data).ravel()[:1]
        warmed.append((dshape, kinds))

    cache = aot.cache_dir()
    n_files = len([f for f in os.listdir(cache)]) if os.path.isdir(cache) else 0
    for dshape, kinds in warmed:
        print(f"warmed {args.model}{list(dshape)}: {', '.join(kinds)}")
    print(f"cache: {cache} ({n_files} executables; "
          f"stores={mx.telemetry.counter('aot.cache_store').value}, "
          f"hits={mx.telemetry.counter('aot.cache_hit').value})")
    if not aot.supports_serialization():
        print("note: this backend cannot serialize executables — programs "
              "were compiled for this process only", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
