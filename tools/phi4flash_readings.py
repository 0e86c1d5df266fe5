#!/usr/bin/env python3
"""The upper readings behind ``TOLERANCES`` of ``benchmark/reference/
phi-4-mini-flash.py``: the reference against itself with a precision lowered
or a piece changed, at the cell's size (1 x 4096 seeded tokens, published
widths), weights and ids from the seed as a run of ``phi4-mini-flash-train-1c``
makes them.

    python3 tools/phi4flash_readings.py SEED [SEED ...] [--only NAME,NAME]

One JSON line a control: the relative distance of ``loss`` and ``grad_norm``
from the plain float32 reference's, beside ``TOLERANCES`` and whether the
control is not correct by them. ``float8``: weights and projection inputs
rounded to float8_e4m3fn, the precision below the bfloat16 the configuration
states, which has to come out as not correct. The others are what ISSUE 65
names, and the recurrence dropped (``y = D x`` alone): a bfloat16 scan
state, the ``D`` skip dropped, the sub-norm dropped, ``lam_init`` by the
place in the cut (a cross layer reading another layer's
keys is held on the CPU, ``tests/test_phi4flash.py``: the chain a layer at a
time has no place for it). The lower readings (what a sound program is off by) are the
traced runs' ``compared``. The reference runs on the host's CPU device
whatever the machine holds: ``chiprun -- python3 tools/phi4flash_readings.py
...`` (about 90 s a control on the chip machine's 13 cores). ``--tiny``
rehearses at hidden 64, T 64."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "phi4-mini-flash-train-1c"
TINY = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
            num_key_value_heads=2, sliding_window=8, mamba_dt_rank=4,
            vocab_size=64, buckets=[64])


def _float8(ref):
    import jax.numpy as jnp

    plain = ref.project

    def low(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    ref.project = lambda x, w, b=None: plain(low(x), low(w), b)


def _bfloat16_state(ref):
    ref.SCAN_STATE_DTYPE = "bfloat16"


def _no_recurrence(ref):
    """``y = D x`` alone: the state never read."""
    ref.selective_scan = lambda xs, delta, a, b, c: 0.0 * xs


def _no_skip(ref):
    ref.skip = lambda d, xs: 0.0 * xs


def _no_sub_norm(ref):
    ref.sub_norm = lambda x, gain, eps: x


def _lam_init_by_cut_index(ref):
    plain = ref.layers_of
    ref.layers_of = lambda cfg: [(kind, i) for i, (kind, _) in
                                 enumerate(plain(cfg))]


CONTROLS = {"float8": _float8, "bfloat16_state": _bfloat16_state,
            "no_recurrence": _no_recurrence, "no_skip": _no_skip, "no_sub_norm": _no_sub_norm,
            "lam_init_by_cut_index": _lam_init_by_cut_index}


def fresh_reference(name):
    """The reference's module executed anew: a control patches its own."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "phi4flash_reference", os.path.join(ROOT, "benchmark", "reference",
                                            name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--only", default="")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from benchmark.drivers import _train
    from benchmark.lib import gen
    from benchmark.lib import harness as hx

    cfg = hx.find_cell(CELL)[3]
    if args.tiny:
        cfg.update(TINY, compute_dtype="float32")
    builder = hx.config_module("configs", cfg["name"])
    t = max(cfg["buckets"])
    sym = builder.sym_gen(cfg, mx)[0](t)[0]
    shapes = builder.input_shapes(cfg, 1, t)
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    cpu = jax.devices("cpu")[0]
    names = args.only.split(",") if args.only else list(CONTROLS)
    for seed in args.seeds:
        with jax.default_device(cpu):
            leaves = gen.make_leaves(
                jax, seed, _train.param_specs(params, builder.init_rule))
            ids = gen.make_leaves(
                jax, seed + 1, [("data", shapes["data"], "float32",
                                 "randint", float(cfg["vocab_size"] - 1),
                                 1.0)])["data"]
            label = jnp.concatenate([ids[:, 1:], jnp.zeros((1, 1))], axis=1)
            plain = fresh_reference(cfg["name"])
            t0 = time.perf_counter()
            want = plain.first_step(jax, cfg, leaves, ids, label)
            print(json.dumps({"seed": seed, "control": "plain", **want,
                              "s": time.perf_counter() - t0}), flush=True)
            for name in names:
                ref = fresh_reference(cfg["name"])
                CONTROLS[name](ref)
                t0 = time.perf_counter()
                got = ref.first_step(jax, cfg, leaves, ids, label)
                rel = {k: abs(got[k] - want[k]) / abs(want[k]) for k in want}
                print(json.dumps({
                    "seed": seed, "control": name, "rel_err": rel,
                    "tolerances": plain.TOLERANCES,
                    "not_correct_by": [k for k in rel
                                       if rel[k] > plain.TOLERANCES[k]],
                    "s": time.perf_counter() - t0}), flush=True)


if __name__ == "__main__":
    main()
