#!/usr/bin/env python3
"""What the benchmark's reference check cannot see of the looped model: the
exit gate by name.

    python3 tools/ouro_gate_check.py [--seed N] [--seq-len T] [--tiny]

The check of ``ouro-2.6b-train-1c`` compares the loss and the norm of the
gradient over every parameter; the gate's 2049 parameters do not move a norm
over 400 M. This script binds the cell's first training step as the driver
does (the configuration and traffic of ``BENCHMARK.json``, weights and ids
from ``--seed``, the traffic file's ``env``), runs one forward/backward and
prints, against the plain float32 reference ``benchmark/reference/
ouro-2.6b.py``, as ``max |a - b| / max |b|`` a tensor: the gradients of
``early_exit_gate_weight``, ``early_exit_gate_bias``, ``pred_weight`` and
``l0_q_weight``, the mean share ``p_t`` of each of the four exits, and the
check's own two numbers (``first_step``) with the six parameters whose
gradient's norm is furthest from the reference's. One JSON line, last. On the chip: ``chiprun -- python3 tools/ouro_gate_check.py``
(2-4 minutes, most of it the reference on the host). ``--tiny`` is the CPU
rehearsal: hidden 64, T 32, float32.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "ouro-2.6b-train-1c"
NAMED = ("early_exit_gate_weight", "early_exit_gate_bias", "pred_weight",
         "l0_q_weight")


def rel(a, b):
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    from benchmark.drivers import _train
    from benchmark.lib import gen
    from benchmark.lib import harness as hx

    _, _, _, cfg, traffic = hx.find_cell(CELL)
    seq_len = args.seq_len or traffic["reference_check"]["seq_len"]
    if args.tiny:
        cfg.update(hidden_size=64, num_attention_heads=4,
                   num_key_value_heads=4, head_dim=16, intermediate_size=96,
                   vocab_size=64, num_hidden_layers=2,
                   compute_dtype="float32")
        seq_len = args.seq_len or 32
    hx.apply_env(traffic)

    import mxnet_tpu as mx
    import jax
    import jax.numpy as jnp

    builder = hx.config_module("configs", cfg["name"])
    ref = hx.config_module("reference", cfg["name"])
    ctx = mx.cpu() if args.tiny else mx.tpu(0)
    sym = builder.sym_gen(cfg, mx)[0](seq_len)[0]
    shapes = builder.input_shapes(cfg, 1, seq_len)
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    params = {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in shapes}
    leaves = gen.make_leaves(jax, args.seed,
                             _train.param_specs(params, builder.init_rule))
    ids = gen.make_leaves(
        jax, args.seed + 1,
        [("data", shapes["data"], "float32", "randint",
          float(cfg["vocab_size"] - 1), 1.0)])["data"]
    label = jnp.concatenate([ids[:, 1:], jnp.zeros((1, 1))], axis=1)

    passes = cfg["total_ut_steps"]
    inner = sym.get_internals()
    exe = mx.sym.Group([sym] + [
        inner[f"u{t}_early_exit_gate_output"] for t in range(1, passes + 1)
    ]).simple_bind(ctx, **shapes)
    for n, a in leaves.items():
        exe.arg_dict[n][:] = mx.nd.NDArray(a)
    exe.arg_dict["data"][:] = mx.nd.NDArray(ids)
    exe.arg_dict["softmax_label"][:] = mx.nd.NDArray(label)
    # the reference's copy lives on the host: the chip holds one set
    host = jax.devices("cpu")[0]
    lab = label.reshape(-1).astype(jnp.int32)
    leaves, ids, label = jax.device_put((leaves, ids, label), host)
    outs = exe.forward(is_train=True)
    exe.backward()
    rows = seq_len
    grads = {n: exe.grad_dict[n].asnumpy() / rows for n in NAMED}
    # and what the driver's check compares, with each parameter's share of it
    norms = {n: float(jnp.sqrt(jnp.sum(jnp.square(
        exe.grad_dict[n]._data.astype(jnp.float32))))) / rows for n in leaves}
    picked = jnp.take_along_axis(outs[0]._data, lab[:, None], 1)
    loss = float(-jnp.mean(jnp.log(jnp.maximum(picked, 1e-30))))
    scores = [o.asnumpy().astype("float32")[:, 0] for o in outs[1:]]
    shares = ref.exit_distribution(
        [jax.nn.sigmoid(jnp.asarray(s)) for s in scores])
    del exe, outs

    ce, want, want_shares = ref.value_and_grads(jax, cfg, leaves, ids, label)
    want_norms = {n: float(jnp.sqrt(jnp.sum(g ** 2))) for n, g in want.items()}
    total, want_total = (sum(v * v for v in d.values()) ** 0.5
                         for d in (norms, want_norms))
    # the parameters whose gradient's norm is furthest from the reference's,
    # weighed by their share of the whole norm's square
    off = sorted(norms, key=lambda n: -abs(
        norms[n] ** 2 - want_norms[n] ** 2))[:6]
    line = {"cell": CELL, "seed": args.seed, "seq_len": seq_len,
            "layers": cfg["num_hidden_layers"], "dtype": cfg["compute_dtype"],
            "device": jax.devices()[0].device_kind,
            "grad_rel_err": {n: rel(grads[n], want[n]) for n in NAMED},
            "mean_share": {"program": [float(x) for x in shares.mean(0)],
                           "reference": [float(x)
                                         for x in want_shares.mean(0)]}}
    line["first_step"] = {
        "loss_rel_err": abs(loss - float(ce)) / abs(float(ce)),
        "grad_norm_rel_err": abs(total - want_total) / want_total,
        "grad_norm": [total, want_total],
        "furthest": {n: [norms[n], want_norms[n]] for n in off}}
    line["mean_share_rel_err"] = [
        abs(a - b) / b for a, b in zip(line["mean_share"]["program"],
                                       line["mean_share"]["reference"])]
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
