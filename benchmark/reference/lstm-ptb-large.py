"""Plain reference of ``lstm-ptb-large``: the two-layer LSTM language model
of Zaremba et al. 2014 (arXiv:1409.2329) in ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, one python loop over time.

It shares only parameter names with ``mxnet_tpu``. Gates are stacked in the
order input, forget, cell, output along the first axis of ``i2h_weight``
``(4H, in)`` and ``h2h_weight`` ``(4H, H)``; the forget gate's
pre-activation gets ``forget_bias`` added; states start at zero. The loss is
the cross-entropy of EVERY position, pads included with label 0: that is
what the program's ``SoftmaxOutput`` without ``use_ignore`` trains on (the
MXNet example does the same and only its Perplexity metric ignores pads).

Departures from the paper: no dropout here (the check binds the program
with dropout 0, because a reference cannot draw the program's mask; the
measured cell trains with 0.65); sentences in length buckets and not a
continuous stream with carried state.

Tolerances (relative) of the first training step on 32 seeded sentences of
20 tokens, with their reasons:

* loss 1e-4, global gradient norm 1e-3 -- both sides compute in float32,
  so they differ by summation order only: on the v5e 3e-6 and 9e-8 (my
  chip run, PR 23). The same step with bfloat16
  matmuls (jax's default precision on a TPU) is off by about 1e-2 in the
  gradient norm, so a program that computes the f32 LSTM in bf16 fails.

The check rests on the gradient norm. With the seeded weights the scores are
near zero, so the loss reads ln 10000 = 9.2103 on both sides whatever the
recurrence computes: it holds the program to the softmax, the label shift
and the count of positions, and to nothing else. The gradient reaches the
embedding only through every gate of both layers at every time step, so its
norm, which agrees to 1e-7, is what a wrong gate order, a missing forget
bias or a bf16 matmul moves.
"""

from __future__ import annotations

TOLERANCES = {"loss": 1e-4, "grad_norm": 1e-3}


def logits(jax, cfg, p, tokens):
    """Scores ``(batch * time, vocab)``, batch-major, of ``tokens`` (B, T)."""
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    hidden = cfg["num_hidden"]
    b, t = tokens.shape
    x = p["embed_weight"][tokens.astype(jnp.int32)]          # (B, T, E)
    for layer in range(cfg["num_layers"]):
        pre = f"lstm_l{layer}_"
        h = jnp.zeros((b, hidden), jnp.float32)
        c = jnp.zeros((b, hidden), jnp.float32)
        outs = []
        for step in range(t):
            gates = (jnp.dot(x[:, step], p[pre + "i2h_weight"].T, precision=hi)
                     + p[pre + "i2h_bias"]
                     + jnp.dot(h, p[pre + "h2h_weight"].T, precision=hi)
                     + p[pre + "h2h_bias"])
            i, f, g, o = jnp.split(gates, 4, axis=1)
            c = jax.nn.sigmoid(f + cfg["forget_bias"]) * c \
                + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            outs.append(h)
        x = jnp.stack(outs, axis=1)                            # (B, T, H)
    flat = x.reshape(b * t, hidden)
    return jnp.dot(flat, p["pred_weight"].T, precision=hi) + p["pred_bias"]


def first_step(jax, cfg, params, data, label):
    """{"loss": mean cross-entropy over all positions, "grad_norm": norm of
    d(mean loss)/dW over every leaf}."""
    import jax.numpy as jnp

    lab = label.reshape(-1).astype(jnp.int32)

    def loss_fn(w):
        logp = jax.nn.log_softmax(logits(jax, cfg, w, data), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, lab[:, None], 1))

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(dict(params))
    norm = jnp.sqrt(sum(jnp.sum(g ** 2) for g in grads.values()))
    return {"loss": float(loss), "grad_norm": float(norm)}
