"""Run a driver here, on the CPU, on a tiny configuration a test defines.

``run.py`` itself refuses anything but a TPU; the tests build the same
``run`` dict by hand with ``mx.cpu`` contexts. Nothing measured this way is
a device number: the tests look at control flow and counts only.
"""

from __future__ import annotations

import argparse
import time


def run_driver(config, traffic, *, builder_of, chips=1, seconds=0.5, seed=3,
               trace=0, bench=None):
    import jax

    import mxnet_tpu as mx
    from benchmark.lib import harness as hx

    run = hx.new_run(
        args=argparse.Namespace(seed=seed, seconds=seconds, trace=trace,
                                workload="test-cell"),
        seconds=seconds, bench=bench or {},
        cell={"name": "test-cell", "config": builder_of, "chips": chips,
              "traffic": traffic["name"]},
        config=config, traffic=traffic, devices=jax.devices()[:chips],
        peaks={"bf16_tflops": 1.0, "hbm_gb_per_s": 1.0, "hbm_gb": 1.0},
        mx=mx, jax=jax, ctx_of=mx.cpu, t_start=time.perf_counter())
    hx.load_driver(traffic["driver"]).run(run)
    return run
