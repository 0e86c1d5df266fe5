#!/usr/bin/env python
"""Deployment-path benchmark: C client (amalgamated libmxtpu.so, MXPred*
ABI) vs the in-process Python Predictor, ResNet-50 folded, bs1 and bs32.

The reference's amalgamation exists for exactly this deployment story, so
the C path must not tax it: the expectation is C within ~10% of Python
(both run the same folded XLA program; the delta is marshalling —
MXPredSetInput/GetOutput cross the embedded-CPython boundary with raw
float buffers).

Usage: python tools/bench_deploy.py [--dev-type 2] [--iters-bs1 100]
Prints one line per (path, batch) plus a summary ratio.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import sysconfig
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)


def _py_leg(args, prefix):
    """Child process: write the random ResNet-50 checkpoint under ``prefix``
    and time the in-process Python ``Predictor`` on it. Everything that
    touches jax lives here — a chip belongs to one process at a time, and
    the C client legs that follow need it."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.predictor import Predictor

    sym = models.resnet(num_classes=1000, num_layers=50,
                        image_shape="3,224,224")
    # random params straight from shape inference — binding an executor
    # just to initialize would compile the whole graph on the host backend
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=(1, 3, 224, 224), softmax_label=(1,))
    rng = np.random.RandomState(0)
    arg_params, aux_params = {}, {}
    for n, s in zip(sym.list_arguments(), arg_shapes):
        if n in ("data", "softmax_label"):
            continue
        fan_in = int(np.prod(s[1:])) if len(s) > 1 else int(s[0])
        arg_params[n] = mx.nd.array(
            (rng.randn(*s) * np.sqrt(2.0 / max(fan_in, 1)))
            .astype(np.float32))
    for n, s in zip(sym.list_auxiliary_states(), aux_shapes):
        aux_params[n] = (mx.nd.ones(s) if "var" in n or "gamma" in n
                         else mx.nd.zeros(s))
    mx.model.save_checkpoint(prefix, 0, sym, arg_params, aux_params)
    sym_file, params_file = f"{prefix}-symbol.json", f"{prefix}-0000.params"

    for batch, iters in ((1, args.iters_bs1), (32, args.iters_bs32)):
        pred = Predictor(
            open(sym_file).read(), params_file,
            {"data": (batch, 3, 224, 224)},
            dev_type="gpu" if args.dev_type == 2 else "cpu")
        x = (np.arange(batch * 3 * 224 * 224, dtype=np.float32)
             % 255) / 255.0
        x = x.reshape(batch, 3, 224, 224)

        def once():
            pred.set_input("data", x)
            pred.forward()
            return pred.get_output(0)

        for _ in range(3):
            np.asarray(once())
        tic = time.time()
        for _ in range(iters):
            out = once()
        np.asarray(out)
        rate = batch * iters / (time.time() - tic)
        print(f"PY {batch} {rate:.2f}", flush=True)


def _rates(stdout, tag):
    """{batch: rate} from a leg's ``<tag> <batch> <rate>`` lines."""
    out = {}
    for line in stdout.splitlines():
        f = line.split()
        if len(f) == 3 and f[0] == tag:
            out[int(f[1])] = float(f[2])
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dev-type", type=int, default=2,
                    help="1=cpu 2=accelerator (TPU)")
    ap.add_argument("--iters-bs1", type=int, default=100)
    ap.add_argument("--iters-bs32", type=int, default=20)
    ap.add_argument("--amal-dir", default=None,
                    help="reuse an existing amalgamation build dir")
    ap.add_argument("--py-leg", metavar="PREFIX", default=None,
                    help=argparse.SUPPRESS)  # internal: run as the child
    args = ap.parse_args()
    if args.py_leg:
        _py_leg(args, args.py_leg)
        return

    # The parent never imports jax or mxnet_tpu: each leg below is a child
    # that owns the accelerator for its lifetime and releases it on exit.
    work = tempfile.mkdtemp(prefix="mxtpu_deploy_")
    prefix = os.path.join(work, "resnet50")
    sym_file, params_file = f"{prefix}-symbol.json", f"{prefix}-0000.params"
    results = {}

    # ---- python predictor ----
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--py-leg", prefix,
         "--dev-type", str(args.dev_type),
         "--iters-bs1", str(args.iters_bs1),
         "--iters-bs32", str(args.iters_bs32)],
        stdout=subprocess.PIPE, text=True, timeout=2400)
    if r.returncode != 0:
        sys.exit(f"python predictor leg failed (rc={r.returncode})")
    print(r.stdout, end="", flush=True)
    for batch, rate in _rates(r.stdout, "PY").items():
        results[("py", batch)] = rate

    # ---- C client over the amalgamated .so ----
    amal = args.amal_dir
    if not amal:
        amal = os.path.join(work, "amal")
        r = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "tools", "amalgamation.py"),
             "--out-dir", amal], capture_output=True, text=True)
        if r.returncode != 0:
            sys.exit(f"amalgamation failed:\n{r.stderr[-2000:]}")
    exe = os.path.join(work, "bench_predict")
    libdir = sysconfig.get_config_var("LIBDIR")
    r = subprocess.run(
        ["g++", "-std=c++17", "-O2",
         os.path.join(_ROOT, "cpp_package", "example", "bench_predict.cc"),
         "-o", exe, f"-I{amal}", os.path.join(amal, "libmxtpu.so"),
         f"-Wl,-rpath,{amal}", f"-Wl,-rpath,{libdir}"],
        capture_output=True, text=True)
    if r.returncode != 0:
        sys.exit(f"C build failed:\n{r.stderr[-2000:]}")
    env = dict(os.environ)
    env["PYTHONPATH"] = _ROOT + os.pathsep + env.get("PYTHONPATH", "")
    for batch, iters in ((1, args.iters_bs1), (32, args.iters_bs32)):
        r = subprocess.run(
            [exe, sym_file, params_file, str(batch), str(iters),
             str(args.dev_type)],
            capture_output=True, text=True, env=env, timeout=1200)
        if r.returncode != 0:
            sys.exit(f"C bench failed:\n{r.stderr[-2000:]}")
        print(r.stdout, end="", flush=True)
        results[("c", batch)] = _rates(r.stdout, "C")[batch]

    for batch in (1, 32):
        ratio = results[("c", batch)] / results[("py", batch)]
        print(f"SUMMARY bs{batch}: C/{'PY'} = {ratio:.3f} "
              f"(C {results[('c', batch)]:.1f} vs "
              f"PY {results[('py', batch)]:.1f} img/s)")


if __name__ == "__main__":
    main()
