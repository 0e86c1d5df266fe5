"""Plain reference of ``olmoe-1b-7b``: the OLMoE decoder of Muennighoff et
al. 2024 (arXiv:2409.02060; HF ``modeling_olmoe.py``) in ``jax.numpy``,
float32, ``jax.default_matmul_precision("highest")``, on the host's CPU
device. Dense masked experts (every expert on every token, times the top-k
mask), the full ``(T, T)`` score matrix, no kernel, no sort, no block.

It shares only parameter names and layouts with ``mxnet_tpu``: ``*_weight``
of a projection is ``(out, in)``; ``l<i>_moe_router_weight`` ``(E, H)``,
``gate_weight`` / ``up_weight`` ``(E, H, F)``, ``down_weight`` ``(E, F, H)``.

One layer, ``x`` the ``(B, T, H)`` residual stream::

    u = rms(x; input_norm)
    q = rms(Wq u; q_norm), k = rms(Wk u; k_norm), v = Wv u   (norms over all
        H projected features, before the split into heads)
    h = x + Wo . softmax(rot(q) rot(k)^T / sqrt(D) + causal) v
    t = rms(h; post_norm); p = softmax(Wr t) over all E
    y = h + sum over the k largest p_e of
            p_e * Wdown_e(silu(Wgate_e t) * Wup_e t)

with the k weights NOT renormalised (``norm_topk_prob`` false), then a final
norm and an untied head. The training loss is the paper's: cross-entropy
plus, per layer, ``lb * E * sum_e f_e P_e`` (``f_e`` the share of tokens
routed to expert e, a constant, the ``f_e`` sum to k; ``P_e`` the mean of
``p_e``) and ``z * mean_t logsumexp(Wr t)^2``.

Departures from the paper, each because the program does the same:

* the cross-entropy that is differentiated is summed over the rows whose
  label is not the pad (0) and divided by ALL rows (``SoftmaxOutput`` with
  ``use_ignore`` and no normalisation, then the optimizer's rescale); the
  paper averages over the non-pad rows. On packed rows the two differ by
  the pad share, under 0.1%. ``first_step``'s ``loss`` is the plain mean
  over all rows, pads as label 0: that is what the driver reads back;
* ``f_e`` and ``P_e`` are taken over every row of the batch, pads too;
* Adam is MXNet's (``adam_steps``): ``lr_t = lr sqrt(1-b2^t)/(1-b1^t)``,
  ``w -= lr_t m / (sqrt(v) + eps)``, eps outside the bias correction; no
  decoupled weight decay and no global-norm clip (the framework has
  neither; the configuration sets wd 0 and no clip and says so).

Tolerances (relative), with their reasons (readings: my chip runs, PR 26,
1 x 4096 seeded tokens at published widths; PERF.md section 6 has them all):

``TOLERANCES`` hold the program's bfloat16 trunk against this float32
reference on the driver's first training step. The loss of seeded weights
sits near ln(vocab) whatever the layer computes (every mutation below moves
it by under 8e-4), so it holds the program to the softmax, the label shift
and the row count; the check rests on ``grad_norm``, the norm of the
gradient of the TOTAL loss over every parameter. The limits lie between two
readings. The largest the bfloat16 trunk gave over fourteen seeds: loss
1.3e-4, grad_norm 6.4e-4 (signed, rms 2.9e-4: about 4% of the tokens change
their eighth expert when the router reads a bfloat16 input, as the
published model's does). And this reference computed in the precision
below, float8_e4m3fn weights and matmul inputs: loss 5.9e-4 to 2.1e-3,
grad_norm 0.64-0.68, which comes out as not correct. 2e-3 is seven of the
trunk's rms. One thing these two scalars cannot tell apart, said plainly:
this reference wholly in bfloat16 (router, norms, softmaxes and loss too)
reads 7.7e-4, 2.4e-5 and 5.5e-4, the trunk's own range; what the float32
islands buy does not show in a one-layer gradient norm. What a wrong layer
moves ``grad_norm`` by: dropping each token's least probable expert of the
eight 7.9e-3, its most probable 5.1e-2, renormalising the eight weights
1.4e-1, no q/k norm 7.2e-2, no rotary embedding 1.8e-2: all fail.

``F32_TOLERANCES`` hold a float32 trunk (the CPU tests, and the float32
comparison on the chip): both sides compute in float32 and differ by the
order of sums (blocks of queries, sorted experts); measured 8.5e-8 and
2.4e-7 on the chip, 2e-7 on the CPU at the tiny size. The bfloat16 trunk's
SMALLEST readings over the seeds, 2.5e-6 and 1.3e-5, fail them.
``F32_TENSOR_TOLERANCE`` is for probabilities and each parameter's
gradient, as ``max |a - b| / max |b|`` a tensor: measured at most 3.3e-5
(the q, k, v projections and their norms; 3e-6 elsewhere) against about
1e-2 in bfloat16.
"""

from __future__ import annotations

import math

TOLERANCES = {"loss": 4e-4, "grad_norm": 2e-3}
F32_TOLERANCES = {"loss": 1e-6, "grad_norm": 3e-6}
F32_TENSOR_TOLERANCE = 3e-4


def rms_norm(x, gain, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) \
        * gain


def normed_projection(u, weight, gain, eps):
    """A query or key projection: the norm runs over all the projected
    features, before the split into heads."""
    return rms_norm(u @ weight.T, gain, eps)


def rotary(x, theta):
    """Rotate-half over the last axis of ``x`` (B, heads, T, D)."""
    import jax.numpy as jnp

    t, d = x.shape[-2:]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], -1)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(emb) + half * jnp.sin(emb)


def attention(q, k, v):
    """Causal softmax attention of (B, heads, T, D), the whole matrix."""
    import jax
    import jax.numpy as jnp

    t, d = q.shape[-2:]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def route(probs, k):
    """(N, E): a token's probability at its k most probable experts, 0
    elsewhere; not renormalised."""
    import jax
    import jax.numpy as jnp

    kth = jax.lax.top_k(probs, k)[0][:, -1:]
    return jnp.where(probs >= kth, probs, 0.0)


def moe(t, w_router, w_gate, w_up, w_down, k, lb_coef, z_coef):
    """(output, router penalty) of the rows ``t`` (N, H): every expert on
    every row, weighted by :func:`route`."""
    import jax
    import jax.numpy as jnp

    n_experts = w_router.shape[0]
    scores = t @ w_router.T
    probs = jax.nn.softmax(scores, -1)
    weights = route(probs, k)                                  # (N, E)
    hidden = jax.nn.silu(jnp.einsum("nh,ehf->enf", t, w_gate)) \
        * jnp.einsum("nh,ehf->enf", t, w_up)
    out = jnp.einsum("ne,enh->nh", weights,
                     jnp.einsum("enf,efh->enh", hidden, w_down))
    share = jax.lax.stop_gradient(jnp.mean(weights > 0, 0))    # f_e
    penalty = lb_coef * n_experts * jnp.sum(share * jnp.mean(probs, 0)) \
        + z_coef * jnp.mean(jax.nn.logsumexp(scores, -1) ** 2)
    return out, penalty


def forward(cfg, p, tokens):
    """(scores (B*T, vocab), sum of the layers' router penalties)."""
    import jax.numpy as jnp

    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    b, t = tokens.shape
    x = p["embed_weight"][tokens.astype(jnp.int32)]            # (B, T, H)
    hidden = x.shape[-1]
    penalty = 0.0

    def split(z):
        return z.reshape(b, t, heads, hidden // heads).transpose(0, 2, 1, 3)

    for i in range(cfg["num_hidden_layers"]):
        w = {n[len(f"l{i}_"):]: a for n, a in p.items()
             if n.startswith(f"l{i}_")}
        u = rms_norm(x, w["input_norm_gamma"], eps)
        q = normed_projection(u, w["q_weight"], w["q_norm_gamma"], eps)
        k = normed_projection(u, w["k_weight"], w["k_norm_gamma"], eps)
        v = u @ w["v_weight"].T
        a = attention(rotary(split(q), cfg["rope_theta"]),
                      rotary(split(k), cfg["rope_theta"]), split(v))
        x = x + a.transpose(0, 2, 1, 3).reshape(b, t, hidden) \
            @ w["o_weight"].T
        out, pen = moe(
            rms_norm(x, w["post_norm_gamma"], eps).reshape(b * t, hidden),
            w["moe_router_weight"], w["moe_gate_weight"], w["moe_up_weight"],
            w["moe_down_weight"], cfg["num_experts_per_tok"],
            cfg["router_aux_loss_coef"], cfg["router_z_loss_coef"])
        x = x + out.reshape(b, t, hidden)
        penalty = penalty + pen
    x = rms_norm(x, p["final_norm_gamma"], eps).reshape(b * t, hidden)
    return x @ p["pred_weight"].T, penalty


def _on_host(jax, *trees):
    """The arguments on the host's CPU device, where there is one: the
    reference runs there. True float32 at any precision setting, 40 GiB for
    the dense experts (every expert on every token is 6 GB of temporaries
    at published widths), and nothing taken from a chip that the job under
    test has filled."""
    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError:
        return trees
    return jax.device_put(trees, cpu)


def logits(jax, cfg, params, tokens):
    """Scores ``(batch * time, vocab)``, batch-major, of ``tokens`` (B, T)."""
    params, tokens = _on_host(jax, dict(params), tokens)
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda w, t: forward(cfg, w, t)[0])(params, tokens)


def losses(jax, cfg, p, tokens, label):
    """(total loss that is differentiated, mean cross-entropy of all rows
    with the pads as label 0)."""
    import jax.numpy as jnp

    lab = label.reshape(-1).astype(jnp.int32)
    scores, penalty = forward(cfg, p, tokens)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(scores, -1),
                               lab[:, None], 1)[:, 0]
    trained = jnp.sum(jnp.where(lab != 0, nll, 0.0)) / lab.shape[0]
    return trained + penalty, jnp.mean(nll)


def value_and_grads(jax, cfg, params, tokens, label):
    """(mean cross-entropy, {name: d(total loss)/d(parameter)})."""
    params, tokens, label = _on_host(jax, dict(params), tokens, label)
    with jax.default_matmul_precision("highest"):
        (_, ce), grads = jax.jit(jax.value_and_grad(
            lambda w, t, l: losses(jax, cfg, w, t, l), has_aux=True))(
                params, tokens, label)
    return ce, grads


def first_step(jax, cfg, params, data, label):
    """{"loss": mean cross-entropy over all rows, "grad_norm": norm of
    d(total loss)/dW over every leaf}."""
    import jax.numpy as jnp

    ce, grads = value_and_grads(jax, cfg, params, data, label)
    norm = jnp.sqrt(sum(jnp.sum(g ** 2) for g in grads.values()))
    return {"loss": float(ce), "grad_norm": float(norm)}


def adam_steps(jax, cfg, params, batches, lr, beta1, beta2, eps,
               grad_scale=1.0):
    """Mean cross-entropy before each of MXNet's Adam steps on ``batches``
    = [(tokens, label), ...]. ``grad_scale`` is what the program's gradient
    is of the total loss's: rows (the summed cross-entropy) over the
    batch's rows (``rescale_grad``), so the sequence length."""
    import jax.numpy as jnp

    params = dict(params)
    mean = {n: jnp.zeros_like(a) for n, a in params.items()}
    var = dict(mean)
    seen = []
    for t, (tokens, label) in enumerate(batches, 1):
        ce, grads = value_and_grads(jax, cfg, params, tokens, label)
        seen.append(float(ce))
        lr_t = lr * math.sqrt(1.0 - beta2 ** t) / (1.0 - beta1 ** t)
        for n, g in grads.items():
            g = g * grad_scale
            mean[n] = beta1 * mean[n] + (1.0 - beta1) * g
            var[n] = beta2 * var[n] + (1.0 - beta2) * g * g
            params[n] = params[n] - lr_t * mean[n] / (jnp.sqrt(var[n]) + eps)
    return seen
