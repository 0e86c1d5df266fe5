"""Plain reference of ``resnet50-v2``: pre-activation bottleneck ResNet-50
written out from He et al. 2016 (arXiv:1603.05027) in ``jax.numpy``,
float32, ``jax.default_matmul_precision("highest")``, NCHW, no kernels.

It shares nothing with ``mxnet_tpu``: only the parameter names, so that the
same seeded leaves feed both. Layout of a leaf: convolution ``(out, in, kh,
kw)``, dense ``(out, in)``.

Departures from the paper, which the program shares: the stride of a
bottleneck sits on its 3x3 (as in the MXNet reference symbol, not on the
first 1x1); BatchNorm eps 2e-5.

Tolerances (relative), with their reasons, for the first training step on
32 seeded images:

* loss 1e-2 -- the program runs a bfloat16 trunk (8 bits of mantissa,
  about 4e-3 a rounding) through 53 convolutions with float32 statistics;
  on the v5e it read 1.2e-3 off this reference (7.0657 against 7.0574; my
  chip run, PR 23). The loss of seeded weights sits near ln 1000 whatever
  the trunk does, so the loss is the weaker check.
* global gradient norm 3e-2 -- rounding adds up over the backward pass
  as well, and how much depends on the drawn batch: three seeds read
  3.9e-4, 1.5e-3 and 6.3e-3 on the v5e (34.352 against 34.136 the worst; my
  chip runs, PR 23), so about five times the worst reading. A BatchNorm
  left out, or gamma and beta not applied, changes every gradient
  downstream by tens of percent with the drawn gamma (1 +- 0.1) and beta
  (+- 0.1). A float32 program would read under 1e-4: this tolerance checks
  the mathematics, and the LSTM's checks the precision.
"""

from __future__ import annotations

TOLERANCES = {"loss": 1e-2, "grad_norm": 3e-2}


def _conv(jax, x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=jax.lax.Precision.HIGHEST)


def _bn(jnp, x, p, name, eps, train):
    if train:
        mean = x.mean(axis=(0, 2, 3))
        var = ((x - mean[None, :, None, None]) ** 2).mean(axis=(0, 2, 3))
    else:
        mean, var = p[name + "_moving_mean"], p[name + "_moving_var"]
    inv = p[name + "_gamma"] / jnp.sqrt(var + eps)
    return (x - mean[None, :, None, None]) * inv[None, :, None, None] \
        + p[name + "_beta"][None, :, None, None]


def logits(jax, cfg, p, x, train):
    """Pre-softmax scores ``(batch, classes)`` of images ``x`` (NCHW)."""
    import jax.numpy as jnp

    eps = cfg["bn_eps"]
    relu = jax.nn.relu
    x = x.astype(jnp.float32)
    x = _conv(jax, x, p["conv0_weight"], 2, 3)
    x = relu(_bn(jnp, x, p, "bn0", eps, train))
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
        [(0, 0), (0, 0), (1, 1), (1, 1)])
    for stage, units in enumerate(cfg["units"]):
        for unit in range(units):
            name = f"stage{stage + 1}_unit{unit + 1}"
            stride = 2 if (unit == 0 and stage > 0) else 1
            a1 = relu(_bn(jnp, x, p, name + "_bn1", eps, train))
            y = _conv(jax, a1, p[name + "_conv1_weight"], 1, 0)
            y = relu(_bn(jnp, y, p, name + "_bn2", eps, train))
            y = _conv(jax, y, p[name + "_conv2_weight"], stride, 1)
            y = relu(_bn(jnp, y, p, name + "_bn3", eps, train))
            y = _conv(jax, y, p[name + "_conv3_weight"], 1, 0)
            if unit == 0:
                x = _conv(jax, a1, p[name + "_sc_weight"], stride, 0)
            x = y + x
    x = relu(_bn(jnp, x, p, "bn1", eps, train))
    x = x.mean(axis=(2, 3))
    return jnp.dot(x, p["fc1_weight"].T,
                   precision=jax.lax.Precision.HIGHEST) + p["fc1_bias"]


def first_step(jax, cfg, params, data, label):
    """{"loss": mean cross-entropy, "grad_norm": norm of d(mean loss)/dW
    over every trained leaf} of one training-mode step."""
    import jax.numpy as jnp

    trained = {k: v for k, v in params.items()
               if not k.endswith(("_moving_mean", "_moving_var"))}
    rest = {k: v for k, v in params.items() if k not in trained}
    lab = label.astype(jnp.int32)

    def loss_fn(w):
        z = logits(jax, cfg, {**w, **rest}, data, True)
        logp = jax.nn.log_softmax(z, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, lab[:, None], 1))

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(trained)
    norm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                        for g in grads.values()))
    return {"loss": float(loss), "grad_norm": float(norm)}
