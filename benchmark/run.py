#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration and traffic files by
name, runs the traffic file's driver on the TPU this machine holds, and
prints as its last line the JSON object the contract asks for: the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``. Earlier lines are for people: the slice series, the
whole-window mean, compiles inside the window. On any backend but a TPU
with enough chips it prints one line to stderr and exits 1.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

T_START = time.perf_counter()  # process start, for setup_s

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.lib import harness as hx

    try:
        bench, cell, entry, config, traffic = hx.find_cell(args.workload)
        seconds = (bench["run_seconds"] if args.seconds is None
                   else args.seconds)
        # the program's defaults: a cell never inherits a window or a depth
        # from the caller's shell, and never 'auto' (ISSUE 23, point 4)
        for var in ("MXNET_TRAIN_WINDOW", "MXNET_DISPATCH_DEPTH"):
            os.environ.pop(var, None)
        for var, val in (traffic.get("env") or {}).items():
            if str(val).lower() == "auto":
                raise hx.BenchError(f"{var}=auto in {cell['traffic']}: a "
                                    "cell sets a number, never a timing")
            os.environ[var] = str(val)

        import mxnet_tpu as mx  # places the compile cache before any backend
        import jax

        devices = hx.require_tpu(jax, cell["chips"])
        run = hx.new_run(
            args=args, seconds=seconds, bench=bench, cell=cell, config=config,
            traffic=traffic, devices=devices, mx=mx, jax=jax, ctx_of=mx.tpu,
            peaks=hx.peaks_of(devices[0].device_kind), t_start=T_START)
        driver = hx.load_driver(traffic["driver"])
        driver.run(run)
        line = result_line(hx, run)
    except hx.BenchError as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 1
    hx.emit(line)
    return 0


def result_line(hx, run):
    """The contract's object from what the driver left in ``run``."""
    bench, cell, trace_on = run["bench"], run["cell"], run["args"].trace
    device = dict(run["device_stamp"])
    metrics = {}
    if trace_on:
        reduced = run["tracer"].result
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        readers = hx.layer_readers()
        for name in hx.metrics_of(bench, cell["name"], "per_layer"):
            if name not in readers:
                raise hx.BenchError(f"no reader benchmark/layers/{name}.py")
            value = readers[name].read(run)
            if value is not None:
                metrics[name] = {"value": float(value),
                                 "unit": readers[name].UNIT}
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        for name in hx.metrics_of(bench, cell["name"], "end_to_end"):
            if name not in run["end_to_end"]:
                raise hx.BenchError(f"the driver gave no {name}")
            metrics[name] = {"value": float(run["end_to_end"][name]),
                             "unit": units[name]}
    line = {"correct": bool(run["correct"]), "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics, "device": device}
    if trace_on:
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    return line


if __name__ == "__main__":
    sys.exit(main())
