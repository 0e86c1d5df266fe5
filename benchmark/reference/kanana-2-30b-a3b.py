"""Plain reference of ``kanana-2-30b-a3b``: the DeepSeek-V3 decoder layer
as kakaocorp's kanana-2-30b-a3b-instruct-2601 configures it (``config.json``
named in the configuration's ``source``, ``model_type: deepseek_v3``; what
``config.json`` has no key for follows the family's public
``modeling_deepseek_v3.py`` and is marked (+) here and listed under
``assumed`` in the configuration) in ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, on the host's CPU device. Every
head gets its own copy of the shared rotated key, whole score rows against a
mask, dense masked experts (every held expert on every token, times the
routing weights); no kernel, no sort, no block plan, no absorbed latent.
Attention and the head run a block of rows at a time under
``jax.checkpoint`` (attention's blocks one after another in a
``lax.map``) only so that 8192 positions fit in the host's memory: every
block scores ALL the keys against the mask.

It shares only parameter names and layouts with ``mxnet_tpu``: ``*_weight``
of a projection is ``(out, in)``; ``q_weight`` rows are head-major, a head's
``[128 without positions | 64 rotated]``; ``kv_a_weight`` rows ``[512 latent |
64 rotated key]`` (``kv_a_proj_with_mqa``); ``kv_b_weight`` rows head-major,
a head's ``[128 key | 128 value]``; ``l<i>_moe_router_weight`` ``(E, H)`` over
all E published experts, ``l<i>_moe_expert_bias`` ``(E,)``
(``e_score_correction_bias``), and of the L experts held here
``gate_weight`` / ``up_weight`` ``(L, H, F)``, ``down_weight`` ``(L, F, H)``;
the two shared experts are one SwiGLU of their summed width (the family's own
``DeepseekV3MLP(intermediate_size = moe_intermediate_size *
n_shared_experts)``).

The model, ``h`` the ``(B, T, 2048)`` residual stream, ``rms(x; w) = x /
sqrt(mean(x^2) + 1e-6) * w``::

    h0 = embed[ids]
    u  = rms(h; input_norm)
    q  = Wq u -> 32 heads of [q_nope 128 | q_rope 64]      (q_lora_rank null)
    [c | k_rope] = Wkva u              512 latent dims | ONE rotated key of 64
    [k_nope | v] = Wkvb rms(c; kv_a_norm) -> 32 heads of [128 | 128]
    q_rope, k_rope <- rotary: pair (2i, 2i + 1) of position t turns by
         t * 1e6^(-2i/64)   (rope_interleave; no scaling)
    k_n = [k_nope_n | k_rope]    the same k_rope in every head n
    a_n = softmax(q_n k_n^T / sqrt(192) + causal mask) v_n        v: 128 wide
    h  = h + Wo [a_1 .. a_32]
    u  = rms(h; post_attn_norm)
    h  = h + down(silu(gate u) * up u), width 6144   layer 0 (first_k_dense)
       | h + shared(u) + sum_{e in top6} w_e expert_e(u)   layers 1 and on
    router, float32: s = sigmoid(Wr u) over all E; sel = top6(s + b)
         (n_group = topk_group = 1: no group-limited choice);
         w = s[sel] / (sum s[sel] + 1e-20) * 2.448; b has no gradient
    logits = W_head rms(h; final_norm)

The family's code turns interleaved pairs by first sorting a head's evens
before its odds and then rotating halves; the scores ``q . k`` are those of
the pairwise rotation above, which is what is written here.

**The share.** The configuration holds ``n_routed_experts`` of the
``n_routed_experts_published`` experts, ids ``[expert_offset, expert_offset
+ n_routed_experts)``: the router scores and chooses over all of them and
normalises over the six it chose, the experts held here add their part, and
what the absent ones would have added is left out. The vocabulary is a
slice: a smaller vocabulary.

No auxiliary router loss (``noaux_tc``: the family balances through the
selection bias, moved outside the gradient by a training loop: not done here
nor in the program, a departure the configuration lists; the bias is 0).
Loss, Adam and their departures are those of ``olmoe-1b-7b.py``: the
cross-entropy that is differentiated is summed over the rows whose label is
not the pad (0) and divided by ALL rows; ``first_step``'s ``loss`` is the
plain mean over all rows; Adam is MXNet's.

Tolerances (relative), with their reasons (readings: PERF.md section 6,
PR 41; 1 x 8192 seeded tokens at published widths).

``TOLERANCES`` hold the program's bfloat16 trunk against this float32
reference on the driver's first training step. The loss of seeded weights
sits near ln(vocabulary) whatever the layers compute, so it holds the
program to the softmax, the label shift and the row count (limit 4e-4, the
accepted cells': 9 times the largest reading); the check rests on
``grad_norm``, the norm of the gradient over every parameter. Its limit lies
between two readings. The largest the bfloat16 trunk gave on the chip over
the builder's three traced seeds: loss 4.3e-5, grad_norm 2.2e-4 (PERF.md
section 6 keeps every reading). And this reference computed in the precision
below, float8_e4m3fn weights and projection inputs (``project``'s ``x``),
against itself in float32 at published widths, 1 x 8192 tokens, on the
host: loss 1.2e-4, grad_norm 0.78, which comes out as not correct, by
grad_norm and not by the loss (``tests/test_kanana2.py``,
``test_tolerances_fail_the_reference_in_float8``, asserts the same at the
tiny size). 2e-3 is 9 times the trunk's largest and 1/390 of the float8
reading. What a left-out mechanism moves at published widths was not
measured; the CPU tests hold nine of them at the small size, where each
fails these limits (``test_tolerances_fail_a_wrong_layer``).

``F32_TOLERANCES`` hold a float32 trunk (the CPU tests): both sides compute
in float32 and differ by the order of their sums (blocks of queries and
keys, experts' rows sorted, a scatter-add combine).
``F32_TENSOR_TOLERANCE`` is for probabilities and each parameter's
gradient, as ``max |a - b| / max |b|`` a tensor.
"""

from __future__ import annotations

import math

TOLERANCES = {"loss": 4e-4, "grad_norm": 2e-3}
F32_TOLERANCES = {"loss": 1e-6, "grad_norm": 1e-5}
F32_TENSOR_TOLERANCE = 3e-4

ATTENTION_BLOCK = 512   # queries a checkpointed block (memory only)
HEAD_BLOCK = 2048       # rows of the head a checkpointed block


def project(x, w):
    """A bias-free projection of the last axis, ``w`` (out, in)."""
    return x @ w.T


def rms_norm(x, gain, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) \
        * gain


def latent_norm(c, gain, eps):
    """The norm of the 512 latent dims before they are projected up."""
    return rms_norm(c, gain, eps)


def rotary(x, theta):
    """Interleaved pairs over the last axis of ``x`` (B, heads, T, D): dims
    (2i, 2i + 1) of position t turn by ``t * theta^(-2i/D)``."""
    import jax.numpy as jnp

    t, d = x.shape[-2:]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def keys(k_nope, k_rope):
    """(B, heads, T, 128 + 64): every head's own ``k_nope`` beside the ONE
    rotated key ``k_rope`` (B, 1, T, 64), the same in every head."""
    import jax.numpy as jnp

    return jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:3]
                                  + k_rope.shape[3:])], -1)


def score_scale(cfg):
    """1 / sqrt(qk_head_dim): of the whole 192, not of the 128."""
    return 1.0 / math.sqrt(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])


def attention(q, k, v, scale):
    """Causal softmax attention of q, k (B, H, T, Dk) over v (B, H, T, Dv):
    the output is Dv wide."""
    import jax
    import jax.numpy as jnp

    b, heads, t, _ = q.shape
    block = min(ATTENTION_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions in blocks of {block}")

    @jax.checkpoint
    def rows(first, qb):
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, k) * scale
        seen = jnp.arange(t)[None, :] <= first + jnp.arange(block)[:, None]
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    blocks = q.reshape(b, heads, t // block, block, -1).transpose(
        2, 0, 1, 3, 4)
    out = jax.lax.map(lambda a: rows(*a),
                      (jnp.arange(0, t, block), blocks))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, heads, t, v.shape[-1])


def latent_attention(cfg, u, w):
    """The mixer on the normed stream ``u`` (B, T, H)."""
    import jax.numpy as jnp

    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    theta = float(cfg["rope_theta"])
    b, t, _ = u.shape

    def split(z, width):
        return z.reshape(b, t, heads, width).transpose(0, 2, 1, 3)

    q = split(project(u, w["q_weight"]), nope + rope)
    kv_a = project(u, w["kv_a_weight"])
    c, k_rope = kv_a[..., :rank], kv_a[..., None, :, rank:]
    kv = split(project(latent_norm(c, w["kv_a_norm_gamma"], eps),
                       w["kv_b_weight"]), nope + dv)
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], theta)], -1)
    k = keys(kv[..., :nope], rotary(k_rope, theta))
    a = attention(q, k, kv[..., nope:], score_scale(cfg))
    return project(a.transpose(0, 2, 1, 3).reshape(b, t, heads * dv),
                   w["o_weight"])


def swiglu(u, w_gate, w_up, w_down):
    """``down(silu(gate u) * up u)``, weights ``(out, in)``."""
    import jax

    return project(jax.nn.silu(project(u, w_gate)) * project(u, w_up),
                   w_down)


def route(scores, bias, k, norm, scale):
    """(N, E) routing weights: the score of a token's k experts of largest
    ``score + bias`` (no gradient through the bias or the choice), divided
    by their sum if ``norm``, times ``scale``; 0 elsewhere."""
    import jax
    import jax.numpy as jnp

    biased = jax.lax.stop_gradient(scores + bias)
    kth = jax.lax.top_k(biased, k)[0][:, -1:]
    kept = jnp.where(biased >= kth, scores, 0.0)
    if norm:
        kept = kept / (jnp.sum(kept, -1, keepdims=True) + 1e-20)
    return kept * scale


def router_scores(t, w_router):
    import jax

    return jax.nn.sigmoid(project(t, w_router))


def experts(t, weights, w_gate, w_up, w_down):
    """Every held expert on every row of ``t`` (N, H), weighted by
    ``weights`` (N, L)."""
    import jax
    import jax.numpy as jnp

    hidden = jax.nn.silu(jnp.einsum("nh,ehf->enf", t, w_gate)) \
        * jnp.einsum("nh,ehf->enf", t, w_up)
    return jnp.einsum("ne,enh->nh", weights,
                      jnp.einsum("enf,efh->enh", hidden, w_down))


def moe(cfg, t, w):
    """The routed part of an expert layer on the rows ``t`` (N, H): what
    the experts held here add."""
    held = w["moe_gate_weight"].shape[0]
    first = cfg.get("expert_offset", 0)
    weights = route(router_scores(t, w["moe_router_weight"]),
                    w["moe_expert_bias"], cfg["num_experts_per_tok"],
                    cfg["norm_topk_prob"], cfg["routed_scaling_factor"])
    return experts(t, weights[:, first:first + held], w["moe_gate_weight"],
                   w["moe_up_weight"], w["moe_down_weight"])


def mlp(cfg, t, w, dense):
    """The feed-forward half of a layer on the rows ``t`` (N, H)."""
    if dense:
        return swiglu(t, w["mlp_gate_weight"], w["mlp_up_weight"],
                      w["mlp_down_weight"])
    return swiglu(t, w["shared_gate_weight"], w["shared_up_weight"],
                  w["shared_down_weight"]) + moe(cfg, t, w)


def layer(cfg, h, w, dense):
    b, t, hidden = h.shape
    eps = cfg["rms_norm_eps"]
    h = h + latent_attention(cfg, rms_norm(h, w["input_norm_gamma"], eps), w)
    u = rms_norm(h, w["post_attn_norm_gamma"], eps)
    return h + mlp(cfg, u.reshape(b * t, hidden), w, dense).reshape(
        b, t, hidden)


def embed(table, tokens):
    import jax.numpy as jnp

    return table[tokens.astype(jnp.int32)]


def layer_weights(p, i):
    """Layer i's parameters, their ``l<i>_`` prefix taken off."""
    return {n[len(f"l{i}_"):]: a for n, a in p.items()
            if n.startswith(f"l{i}_")}


def is_dense(cfg, i):
    return i < cfg["first_k_dense_replace"]


def forward(cfg, p, tokens):
    """Scores (B*T, vocabulary)."""
    h = embed(p["embed_weight"], tokens)
    for i in range(cfg["num_hidden_layers"]):
        h = layer(cfg, h, layer_weights(p, i), is_dense(cfg, i))
    h = rms_norm(h, p["final_norm_gamma"], cfg["rms_norm_eps"])
    return project(h.reshape(-1, h.shape[-1]), p["pred_weight"])


def _on_host(jax, *trees):
    """The arguments on the host's CPU device, where there is one: the
    reference runs there, in true float32 and in the host's memory, and
    takes nothing from a chip that the job under test has filled."""
    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError:
        return trees
    return jax.device_put(trees, cpu)


def logits(jax, cfg, params, tokens):
    """Scores ``(batch * time, vocab)``, batch-major, of ``tokens`` (B, T)."""
    params, tokens = _on_host(jax, dict(params), tokens)
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda w, t: forward(cfg, w, t))(params, tokens)


def head_losses(jax, cfg, x, gain, w_head, label):
    """(loss that is differentiated, mean cross-entropy of all rows with
    the pads as label 0) of the last layer's output ``x`` (B, T, H); the
    head a block of rows at a time."""
    import jax.numpy as jnp

    lab = label.reshape(-1).astype(jnp.int32)
    x = rms_norm(x, gain, cfg["rms_norm_eps"]).reshape(-1, x.shape[-1])

    @jax.checkpoint
    def nll(x, lab, w_head):
        return -jnp.take_along_axis(
            jax.nn.log_softmax(project(x, w_head), -1), lab[:, None], 1)[:, 0]

    nll = jnp.concatenate(
        [nll(x[a:a + HEAD_BLOCK], lab[a:a + HEAD_BLOCK], w_head)
         for a in range(0, lab.shape[0], HEAD_BLOCK)])
    trained = jnp.sum(jnp.where(lab != 0, nll, 0.0)) / lab.shape[0]
    return trained, jnp.mean(nll)


def losses(jax, cfg, p, tokens, label):
    """The model's loss as one function of its parameters: what
    ``value_and_grads`` differentiates, a layer at a time."""
    h = embed(p["embed_weight"], tokens)
    for i in range(cfg["num_hidden_layers"]):
        h = layer(cfg, h, layer_weights(p, i), is_dense(cfg, i))
    return head_losses(jax, cfg, h, p["final_norm_gamma"], p["pred_weight"],
                       label)


def value_and_grads(jax, cfg, params, tokens, label):
    """(mean cross-entropy, {name: d(loss)/d(parameter)}): the chain rule
    over :func:`losses` written out a layer at a time, each layer's forward
    and each layer's vector-Jacobian product a call of its own, so that the
    host holds one layer's intermediates at a time (the CPU test holds it
    equal to autodiff of the whole)."""
    params, tokens, label = _on_host(jax, dict(params), tokens, label)
    kinds = [is_dense(cfg, i) for i in range(cfg["num_hidden_layers"])]
    forward = {k: jax.jit(lambda h, w, k=k: layer(cfg, h, w, k))
               for k in set(kinds)}
    backward = {k: jax.jit(lambda h, w, g, k=k: jax.vjp(
        lambda h, w: layer(cfg, h, w, k), h, w)[1](g)) for k in set(kinds)}
    with jax.default_matmul_precision("highest"):
        h = jax.jit(embed)(params["embed_weight"], tokens)
        inputs = []
        for i, k in enumerate(kinds):
            inputs.append(h)
            h = forward[k](h, layer_weights(params, i))
        (_, ce), back = jax.jit(jax.value_and_grad(
            lambda x, g, w, l: head_losses(jax, cfg, x, g, w, l),
            argnums=(0, 1, 2), has_aux=True))(
                h, params["final_norm_gamma"], params["pred_weight"], label)
        g, grads = back[0], {"final_norm_gamma": back[1],
                             "pred_weight": back[2]}
        for i in reversed(range(len(kinds))):
            g, dw = backward[kinds[i]](inputs[i], layer_weights(params, i), g)
            grads.update({f"l{i}_{n}": a for n, a in dw.items()})
        grads["embed_weight"] = jax.jit(lambda e, t, g: jax.vjp(
            lambda e: embed(e, t), e)[1](g)[0])(
                params["embed_weight"], tokens, g)
    return ce, grads


def first_step(jax, cfg, params, data, label):
    """{"loss": mean cross-entropy over all rows, "grad_norm": norm of
    d(loss)/dW over every leaf}."""
    import jax.numpy as jnp

    ce, grads = value_and_grads(jax, cfg, params, data, label)
    norm = jnp.sqrt(sum(jnp.sum(g ** 2) for g in grads.values()))
    return {"loss": float(ce), "grad_norm": float(norm)}


def adam_steps(jax, cfg, params, batches, lr, beta1, beta2, eps,
               grad_scale=1.0):
    """Mean cross-entropy before each of MXNet's Adam steps on ``batches``
    = [(tokens, label), ...]: ``lr_t = lr sqrt(1-b2^t)/(1-b1^t)``, ``w -=
    lr_t m / (sqrt(v) + eps)``. ``grad_scale`` is what the program's
    gradient is of the loss's: rows (the summed cross-entropy) over the
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
