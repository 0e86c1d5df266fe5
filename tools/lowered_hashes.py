#!/usr/bin/env python3
"""The sha256 of each benchmark cell's lowered train programs, without
debug info: what a change that must not move a number is held to.

    python tools/lowered_hashes.py [--root TREE] [CELL ...]

Builds every cell of ``BENCHMARK.json`` (or the named ones) on the CPU from
the benchmark's own configuration and traffic files, at the cell's shapes,
and drives it for one warm-up cycle. Nothing is compiled or run: each
program the executor resolves is traced and lowered, its text
(``lowered.as_text()``: locations and name stacks left out, and the numbers
jax gives private functions as it meets them, ``@argsort_22``, which follow
what else the process traced) is hashed, and the program answers zeros.
Prints one JSON object, ``{cell: [[sha256[:12], characters of text],
...]}``, the fused train programs in the order they were built (one a
bucket). Two trees that print the same object lower the
same train programs; scopes, spans and comments do not show. ``--root``
hashes another checkout (the parent's), one process a cell either way.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import subprocess
import sys
import time

FUSED = "executor.fused_plan_compile"


class _Done(Exception):
    pass


def lowered_programs(drive, launches):
    """``[(compile counter, sha256[:12], len(text))]`` of every program
    ``drive()`` makes the executor resolve until the fused train programs
    were launched ``launches`` times between them."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import aot

    seen, calls = [], [0]

    def resolve(self, args):
        lowered = self.jit_fn.lower(*args)
        text = re.sub(r"(@\w+?)_\d+\b", r"\1", lowered.as_text())
        fused = self._counter == FUSED
        seen.append((self._counter,
                     hashlib.sha256(text.encode()).hexdigest()[:12],
                     len(text)))
        outs = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            lowered.out_info)

        def answer(*_args):
            calls[0] += fused
            if calls[0] > launches:
                raise _Done()
            return outs

        self.executable = answer
        return answer

    saved = aot.AOTProgram._resolve, aot.load
    aot.AOTProgram._resolve, aot.load = resolve, lambda *a, **k: None
    try:
        drive()
    except _Done:
        pass
    except aot.DonatedCallError as e:
        if not isinstance(e.__cause__, _Done):
            raise
    finally:
        aot.AOTProgram._resolve, aot.load = saved
    return seen


def hash_cell(root, name):
    """The fused train programs of one cell of the checkout at ``root``;
    call in a fresh process (it sets the backend up)."""
    os.chdir(root)
    sys.path.insert(0, root)
    os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmark.lib import harness as hx

    bench, cell, _entry, config, traffic = hx.find_cell(name)
    if cell["chips"] > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={cell['chips']}")
    for var in ("MXNET_TRAIN_WINDOW", "MXNET_DISPATCH_DEPTH"):
        os.environ.pop(var, None)
    for var, val in (traffic.get("env") or {}).items():
        os.environ[var] = str(val)
    import jax
    import mxnet_tpu as mx

    jax.config.update("jax_enable_compilation_cache", False)
    run = hx.new_run(
        args=argparse.Namespace(seed=3, seconds=0.5, trace=0, workload=name),
        seconds=0.5, bench=bench, cell=cell, config=config, traffic=traffic,
        devices=jax.devices()[:cell["chips"]],
        peaks={"bf16_tflops": 1.0, "hbm_gb_per_s": 1.0, "hbm_gb": 1.0},
        mx=mx, jax=jax, ctx_of=mx.cpu, t_start=time.perf_counter())
    cycle = traffic.get("batches_per_cycle") or traffic["warmup_cycle_steps"]
    seen = lowered_programs(
        lambda: hx.load_driver(traffic["driver"]).run(run), cycle)
    return [[sha, size] for counter, sha, size in seen if counter == FUSED]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--one", help=argparse.SUPPRESS)  # the child's cell
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    if args.one:
        print("HASHES " + json.dumps(hash_cell(root, args.one)))
        return 0
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        cells = args.cells or [w["name"] for w in json.load(f)["workloads"]]
    out = {}
    for name in cells:
        done = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--root", root,
             "--one", name], capture_output=True, text=True)
        lines = [ln for ln in done.stdout.splitlines()
                 if ln.startswith("HASHES ")]
        if done.returncode or not lines:
            sys.stderr.write(done.stderr[-2000:])
            return 1
        out[name] = json.loads(lines[-1][len("HASHES "):])
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
