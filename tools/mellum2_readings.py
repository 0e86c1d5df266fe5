#!/usr/bin/env python3
"""The readings behind ``TOLERANCES`` of ``benchmark/reference/
mellum2-12b-a2.5b.py``, and behind its builder's unit embedding.

    python3 tools/mellum2_readings.py checks SEED [SEED ...] [--fault NAME]
    python3 tools/mellum2_readings.py controls SEED [--only NAME,NAME]
    python3 tools/mellum2_readings.py routing SEED [SEED ...] [--seq-len T]

``checks``: the driver's own reference check of ``mellum2-12b-train-1c``
(``benchmark/drivers/bucketing_fit.reference_check``: the program's first
training step at 1 x 16 384 against the plain float32 reference, weights and
ids from the seed as a run makes them), alone and seed after seed in one
process: the lower readings, what a sound program is off by. ``--fault``
puts a fault in the PROGRAM's place (its symbol is built from a changed
configuration, the reference from the published one), so that the verdict
printed is the harness's own: ``no_amplitude`` (``attention_factor`` 1),
``geometric_frequencies`` (the full layers turned by the window layers'
frequencies, the amplitude kept).

``controls``: the reference against itself with a piece changed or a
precision lowered, each pair handed to the harness's own comparison
(``_train.check_against_reference`` under ``TOLERANCES``): the upper
readings, what a limit has to fail. :func:`controls_of` lists them; float8
weights and projection inputs are the precision below the bfloat16 the
configuration states.

``routing`` (the CPU; published widths, a short row): of a layer's routed
assignments the share that reaches the 8 held experts, over the balanced
share (``top_k x held / published`` a row), with the embedding drawn at
0.02 and at the builder's 1.0: why ``init_rule`` departs from ISSUE 62.

On the chip: ``chiprun -- python3 tools/mellum2_readings.py checks ...``
(about a minute a seed after the first). ``--tiny`` is the CPU rehearsal of
the first two modes: hidden 64, T 32, float32. One JSON line a reading; all
of a call's readings also in ``<--out>/<mode>.json`` (``chiprun_out/
mellum2_readings``).
"""

from __future__ import annotations

import argparse
import contextlib
import copy
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "mellum2-12b-train-1c"
OUT = os.path.join(ROOT, "chiprun_out", "mellum2_readings")
TINY = dict(
    vocab_size=64, hidden_size=64, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, sliding_window=8, num_experts=4,
    num_experts_published=16, expert_offset=4, moe_intermediate_size=32,
    num_experts_per_tok=2, compute_dtype="float32", buckets=[32],
    rope_parameters={
        "sliding_attention": {"rope_type": "default", "rope_theta": 100},
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 100, "factor": 4,
            "original_max_position_embeddings": 16, "beta_fast": 1,
            "beta_slow": 0.1, "attention_factor": 1.25}})


def faulted(cfg, fault):
    """The configuration a faulted PROGRAM is built from."""
    cfg = copy.deepcopy(cfg)
    rope = cfg["rope_parameters"]
    if fault == "no_amplitude":
        rope["full_attention"]["attention_factor"] = 1.0
    elif fault == "geometric_frequencies":
        rope["full_attention"] = dict(
            rope["sliding_attention"], rope_type="yarn", factor=1.0,
            original_max_position_embeddings=rope["full_attention"][
                "original_max_position_embeddings"], beta_fast=32,
            beta_slow=1,
            attention_factor=rope["full_attention"]["attention_factor"])
    else:
        raise SystemExit(f"no fault {fault!r}")
    return cfg


def setting(args):
    """(run, traffic, reference module): what ``run.py`` hands the driver,
    on the chip, or with ``--tiny`` on the CPU at :data:`TINY`."""
    from benchmark.lib import harness as hx

    bench, cell, _, cfg, traffic = hx.find_cell(CELL)
    traffic = copy.deepcopy(traffic)
    if args.tiny:
        cfg.update(copy.deepcopy(TINY))
        traffic["reference_check"] = {"batch": 1, "seq_len": 32}
    hx.apply_env(traffic)

    import mxnet_tpu as mx
    import jax

    if args.tiny:
        devices, ctx_of, peaks = jax.devices()[:1], mx.cpu, None
    else:
        devices, ctx_of = hx.require_tpu(jax, cell["chips"]), mx.tpu
        peaks = hx.peaks_of(devices[0].device_kind)

    def run_of(seed):
        ns = argparse.Namespace(workload=CELL, seed=seed, seconds=20.0,
                                trace=0)
        run = hx.new_run(args=ns, seconds=20.0, bench=bench, cell=cell,
                         config=cfg, traffic=traffic, devices=devices,
                         mx=mx, jax=jax, ctx_of=ctx_of, peaks=peaks,
                         t_start=time.perf_counter())
        run["compared"], run["correct"] = {}, True
        return run

    return run_of, traffic, hx.config_module("reference", cfg["name"])


def record(args, mode, lines):
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, mode + ".json"), "w") as f:
        json.dump(lines, f, indent=1)


def checks(args):
    from benchmark.drivers import bucketing_fit

    run_of, traffic, _ = setting(args)
    lines = []
    for seed in args.seeds:
        run = run_of(seed)
        if args.fault:
            plain = run["builder"]
            bent = faulted(run["config"], args.fault)
            run["builder"] = types.SimpleNamespace(**dict(
                vars(plain), sym_gen=lambda cfg, mx, dropout=None:
                plain.sym_gen(bent, mx, dropout)))
        t0 = time.time()
        bucketing_fit.reference_check(run, run["ctx_of"](0),
                                      **traffic["reference_check"])
        lines.append({"mode": "checks", "program_fault": args.fault or None,
                      "seed": seed, "correct": run["correct"],
                      "compared": run["compared"],
                      "seconds": round(time.time() - t0, 1)})
        print(json.dumps(lines[-1]), flush=True)
        record(args, "checks" + ("_" + args.fault if args.fault else ""),
               lines)
    return 0


def _b16(x):
    import jax.numpy as jnp

    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _f8(x):
    import jax.numpy as jnp

    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def controls_of(ref, jax):
    """{name: (patches {attribute of the reference: value}, leaves ->
    leaves, (ids, label) -> (ids, label))}."""
    plain = types.SimpleNamespace(**vars(ref))
    other = {"sliding_attention": "full_attention",
             "full_attention": "sliding_attention"}

    def same(x):
        return x

    def low_leaves(leaves):
        return {n: a if n.endswith("_gamma") else _f8(a)
                for n, a in leaves.items()}

    def wider(cfg, kind):
        return plain.band(cfg, kind) and plain.band(cfg, kind) + 1

    return {
        "float8_e4m3fn_weights_and_projection_inputs": (
            {"project": lambda x, w: plain.project(_f8(x), w)},
            low_leaves, same),
        "no_amplitude": ({"amplitude": lambda rope: 1.0}, same, same),
        "geometric_frequencies_on_the_full_layer": (
            {"inv_freq": lambda rope, half: plain.inv_freq(
                dict(rope, rope_type="default"), half)}, same, same),
        "bfloat16_router": (
            {"router_probs": lambda t, router: jax.nn.softmax(
                _b16(_b16(t) @ _b16(router).T), -1)}, same, same),
        "bfloat16_attention_softmax": (
            {"softmax": lambda s: _b16(jax.nn.softmax(_b16(s), -1))},
            same, same),
        "no_band": ({"band": lambda cfg, kind: 0}, same, same),
        "schedules_swapped": (
            {"rope_of": lambda cfg, kind:
             cfg["rope_parameters"][other[kind]]}, same, same),
        "no_head_norms": ({"head_norm": lambda z, gain, eps: z}, same, same),
        "no_renormalisation": (
            {"route": lambda probs, k, norm: plain.route(probs, k, False)},
            same, same),
        "a_band_one_key_wider": ({"band": wider}, same, same),
        "bfloat16_projection_operands": (
            {"project": lambda x, w: plain.project(_b16(x), _b16(w))},
            same, same),
        # the label of position t is token t, not token t + 1
        "labels_not_shifted": ({}, same, lambda batch: (batch[0], batch[0])),
    }


@contextlib.contextmanager
def patched(module, patches):
    plain = {name: getattr(module, name) for name in patches}
    for name, value in patches.items():
        setattr(module, name, value)
    try:
        yield
    finally:
        for name, value in plain.items():
            setattr(module, name, value)


def seeded(run, traffic):
    """(leaves, ids, label) of the run's seed, as ``reference_check`` makes
    them."""
    import jax.numpy as jnp
    from benchmark.drivers import _train
    from benchmark.lib import gen

    jax, mx, cfg, builder = (run[k] for k in ("jax", "mx", "config",
                                              "builder"))
    batch, seq_len = (traffic["reference_check"][k]
                      for k in ("batch", "seq_len"))
    shapes = builder.input_shapes(cfg, batch, seq_len)
    sym = builder.sym_gen(cfg, mx)[0](seq_len)[0]
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    leaves = gen.make_leaves(jax, run["args"].seed,
                             _train.param_specs(params, builder.init_rule))
    ids = gen.make_leaves(
        jax, run["args"].seed + 1,
        [("data", shapes["data"], "float32", "randint",
          float(cfg["vocab_size"] - 1), 1.0)])["data"]
    label = jnp.concatenate([ids[:, 1:], jnp.zeros((batch, 1))], axis=1)
    return leaves, ids, label


def controls(args):
    from benchmark.drivers import _train

    run_of, traffic, ref = setting(args)
    (seed,) = args.seeds
    run = run_of(seed)
    jax, cfg = run["jax"], run["config"]
    leaves, ids, label = seeded(run, traffic)
    want = ref.first_step(jax, cfg, leaves, ids, label)
    table = controls_of(ref, jax)
    lines = []
    for name in args.only or table:
        patches, of_leaves, of_batch = table[name]
        t0 = time.time()
        with patched(ref, patches):
            got = ref.first_step(jax, cfg, of_leaves(leaves),
                                 *of_batch((ids, label)))
        verdict = dict(run, compared={}, correct=True)
        _train.check_against_reference(
            verdict, cfg["name"] + ".control." + name, got, want,
            ref.TOLERANCES, _train.CheckMemory(run))
        lines.append({"mode": "controls", "control": name, "seed": seed,
                      "correct": verdict["correct"],
                      "compared": verdict["compared"],
                      "seconds": round(time.time() - t0, 1)})
        print(json.dumps(lines[-1]), flush=True)
        record(args, "controls", lines)
    return 0


def routing(args):
    """Each layer's assignments to the held experts over the balanced
    count, the reference's forward op by op on the CPU."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmark.lib import harness as hx

    _, _, _, cfg, traffic = hx.find_cell(CELL)
    import mxnet_tpu as mx
    import jax
    import jax.numpy as jnp
    from benchmark.drivers import _train
    from benchmark.lib import gen

    builder = hx.config_module("configs", cfg["name"])
    ref = hx.config_module("reference", cfg["name"])
    t = args.seq_len
    shapes = builder.input_shapes(cfg, 1, t)
    sym = builder.sym_gen(cfg, mx)[0](t)[0]
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    first, held = cfg["expert_offset"], cfg["num_experts"]
    balanced = t * cfg["num_experts_per_tok"] * held \
        / cfg["num_experts_published"]
    seen = []
    plain = ref.route

    def spy(probs, k, norm):
        weights = plain(probs, k, norm)
        seen.append(float(jnp.sum(weights[:, first:first + held] > 0))
                    / balanced)
        return weights

    lines = []
    for seed in args.seeds:
        ids = gen.make_leaves(
            jax, seed + 1, [("data", shapes["data"], "float32", "randint",
                             float(cfg["vocab_size"] - 1), 1.0)])["data"]
        for std in (0.02, 1.0):
            def rule(name, shape):
                kind, scale, offset = builder.init_rule(name, shape)
                return (kind, std if name == "embed_weight" else scale,
                        offset)

            leaves = gen.make_leaves(jax, seed,
                                     _train.param_specs(params, rule))
            del seen[:]
            with patched(ref, {"route": spy}), \
                    jax.default_matmul_precision("highest"):
                ref.forward(cfg, leaves, ids)
            lines.append({"mode": "routing", "seed": seed, "seq_len": t,
                          "embedding_std": std,
                          "held_over_balanced_by_layer":
                              [round(x, 3) for x in seen]})
            print(json.dumps(lines[-1]), flush=True)
    record(args, "routing", lines)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("checks", "controls", "routing"))
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--fault", default="")
    ap.add_argument("--only", type=lambda s: s.split(","), default=None)
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=OUT)
    args = ap.parse_args(argv)
    return {"checks": checks, "controls": controls,
            "routing": routing}[args.mode](args)


if __name__ == "__main__":
    sys.exit(main())
