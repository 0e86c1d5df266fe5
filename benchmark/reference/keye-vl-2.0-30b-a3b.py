"""Plain reference of ``keye-vl-2.0-30b-a3b``: the language model of Kwai-Keye's
Keye-VL-2.0-30B-A3B (``config.json`` named in the configuration's ``source``;
what it has no key for follows the family's public code and is marked (+)
here and listed under ``assumed`` in the configuration: the Qwen3-MoE decoder,
``modeling_qwen3_moe.py``, for the block, and DeepSeek-V3.2-Exp's report and
the ``Indexer`` of its ``inference/model.py`` for the sparse attention) in
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``, on the
device its arguments live on: the CPU in the tests, and in the benchmark's
check THE CHIP, which the check has to itself (``benchmark/README.md``, step
6): at "highest" a float32 product on the TPU is six bfloat16 passes, float32
to the last bits, and a first step at 1 x 16 384 takes 60-90 s there where
the host's 13 cores took about 500 (the check 173 s against 616; my chip
runs, PR 51; the accepted references run on the host, at a quarter to a half
of these positions). Written from the
equations: the indexer's scores of a block
of queries against every key, ``lax.top_k`` INDICES, a gather of the chosen
keys and values, a softmax over the gathered ones; dense masked experts (every
held expert on every token, times the routing weights); no kernel, no sort,
no mask of a threshold. Attention and the head run a block of rows at a time
under ``jax.checkpoint`` only so that 16 384 positions fit in memory.

It shares only parameter names and layouts with ``mxnet_tpu``: ``*_weight``
of a projection is ``(out, in)``; ``l<i>_moe_router_weight`` ``(E, H)`` over
all E published experts, and of the L experts held here ``gate_weight`` /
``up_weight`` ``(L, H, F)``, ``down_weight`` ``(L, F, H)``.

One layer, ``h`` the ``(B, T, H)`` residual stream, ``u = rms(h; input_norm)``::

    q  = rms_head(Wq u) (32 x 128), k = rms_head(Wk u), v = Wv u (4 x 128):
         the norm over the 128 of each head, one gain of 128 each        (+)
    q, k rotated over all 128 dims in halves, theta 1e7 (a text token's three
         M-RoPE positions are equal: the plain rotation)                   (+)
    the indexer, on stop_gradient(u):
    qI = W_qI u (16 x 64); kI = LayerNorm(W_kI u) (ONE key of 64, eps 1e-6,
         gain and bias) (+); both rotated over their 64 dims                (+)
    w  = (W_w u) * 16^-0.5 * 64^-0.5                                       (+)
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])       s <= t, float32
    S_t = the min(2048, t + 1) positions s <= t of largest I[t, s]
         (lax.top_k: equal scores to the lower s)
    a[t, n] = sum_{s in S_t} softmax_{S_t}(q[t, n] . k[s, n // 8] / sqrt(128))
              v[s, n // 8]
    h  = h + Wo a
    t  = rms(h; post_norm); p = softmax(Wr t) over all 128, float32
    h  = h + sum over the 8 largest p_e that are held here of
             p_e / (sum of the 8) * down_e(silu(gate_e t) * up_e t)

then a final norm and an untied head. The loss that is differentiated::

    cross-entropy + sum over layers of
        lb * E * sum_e f_e P_e                  (the router's balance term, (+))
      + c * (1 / rows) sum_t KL(P[t] || softmax_{S_t}(I[t]))   (the indexer's)

``P[t, s]`` the mean over the 32 heads of the probabilities above, a constant.
Because ``u`` and ``P`` are constants to the indexer and the choice passes no
gradient, the language model's parameters see the first two terms alone and
the indexer's five leaves a layer the third alone: ``c`` is the indexer's
learning rate and nothing else.

**The share.** The configuration holds ``num_experts`` of the
``num_experts_published`` experts, ids ``[expert_offset, expert_offset +
num_experts)``: the router scores and chooses over all of them and normalises
over the eight it chose, the experts held here add their part, and what the
absent ones would have added is left out. The vocabulary is a slice: a
smaller vocabulary.

Departures, each because the program does the same: no vision tower (rows are
text); the serving code's Hadamard rotation of the indexer's q and k and its
FP8 scores are a quantisation and are left out; cross-entropy, Adam and their
departures are those of ``olmoe-1b-7b.py`` (summed over the rows whose label
is not the pad (0), divided by ALL rows; ``first_step``'s ``loss`` is the plain
mean over all rows; the balance term's ``f_e`` and ``P_e`` and the indexer's
term are over every row, pads too).

Tolerances (relative), with their reasons (readings: my chip runs, PR 51,
third session, under the unit embedding of the configuration's ``init_rule``;
PERF.md section 6 has them all, and those of the 0.02 embedding before it;
1 x 16 384 seeded tokens at published widths).

``TOLERANCES`` hold the program's bfloat16 trunk against this float32
reference on the driver's first training step.

``grad_norm``, the norm of the gradient of the TOTAL loss over every
parameter: the trunk read 1.6e-5 and 7.8e-5 on two traced seeds (the accepted
cells' range; under the 0.02 embedding it read 1.9e-4 to 2.5e-3 over eight,
because every token of layers 1-3 then looked alike, a row's index scores lay
close together and the trunk's bfloat16 scores kept other keys near each
threshold than the float32 ones); this reference computed in the precision
below, float8_e4m3fn weights and projection inputs, reads 0.047, not correct.
5e-3 is 64 times the trunk's largest, the more room on that side because
fresh seeds read higher, and 9 times under the float8 reading. The indexer's
five leaves a layer carry 0.047 of ``grad_norm`` squared at the coefficient
1.0 (0.249 under the 0.02 embedding, where the head's gradient all but
cancelled over a row of like tokens), so the scalar sees the indexer, by
less: what a left-out mechanism moves ``loss`` / ``grad_norm`` by at
published widths (reference against reference): on the chip at 1 x 16 384, no
selection (dense causal) 3.9e-5 / 0.056 and the KL term left out 0 / 0.024;
on the host at 1 x 2048 keeping 256, in that order and then the indexer's
ReLU left out, its weights unscaled, ``P`` from one head instead of 32, the
indexer's input not detached: 1.6e-4 / 0.015, 0 / 0.0098, 4.3e-4 / 0.039,
0 / 61, 0 / 0.0092, 0 / 0.026 (float8 there 1.1e-4 / 0.21): all over 5e-3.

``loss``: the trunk read 1.9e-7 and 2.8e-6. The float8 reference reads
9.3e-8 at 1 x 16 384: the LOSS CANNOT SEE THE PRECISION here (under the 0.02
embedding it read 1.4e-4, and on the host at 1 x 2048 1.1e-4; a loss of seeded
weights sits near ln(vocabulary) whatever the layers compute, and what float8
moves of it is second order and of either sign), so the float8 reference is
not correct by ``grad_norm`` alone, and the limit stays where the second
session set it, 6e-5: 21 times the trunk's largest. It holds the program to
the softmax, the label shift and the row count; the selection left out moves
it by 3.9e-5, under it.

``F32_TOLERANCES`` hold a float32 trunk (the CPU tests): both sides compute
in float32 and differ by the order of their sums (a mask against a gather,
experts' rows sorted); measured ~1e-7 at the tiny size.
``F32_TENSOR_TOLERANCE`` is for probabilities and each parameter's gradient,
as ``max |a - b| / max |b|`` a tensor.
"""

from __future__ import annotations

import math

TOLERANCES = {"loss": 6e-5, "grad_norm": 5e-3}
F32_TOLERANCES = {"loss": 1e-6, "grad_norm": 1e-5}
F32_TENSOR_TOLERANCE = 3e-4

ATTENTION_BLOCK = 128   # queries a checkpointed block (memory only)
HEAD_BLOCK = 2048       # rows of the head a checkpointed block


def rms_norm(x, gain, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) \
        * gain


def layer_norm(x, gain, bias, eps):
    import jax.numpy as jnp

    centred = x - jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(centred * centred, -1, keepdims=True)
    return centred / jnp.sqrt(var + eps) * gain + bias


def project(x, weight):
    """A bias-free projection, ``weight`` (out, in)."""
    return x @ weight.T


def rotary(x, theta):
    """Rotate-half over the last axis of ``x`` (B, heads, T, D)."""
    import jax.numpy as jnp

    t, d = x.shape[-2:]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], -1)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(emb) + half * jnp.sin(emb)


# --- the indexer -------------------------------------------------------------

def index_input(u):
    """The indexer reads the layer's input as a constant."""
    import jax

    return jax.lax.stop_gradient(u)


def index_activation(s):
    import jax

    return jax.nn.relu(s)


def index_weight_scale(cfg):
    sa = cfg["sa_config"]
    return (sa["indexer_num_heads"] * sa["indexer_head_dim"]) ** -0.5


def index_top_k(cfg):
    return cfg["sa_config"]["topk"]


def index_loss_coef(cfg):
    return cfg["index_loss_coef"]


def indexer(cfg, u, w):
    """(qI (B, J, T, Di), kI (B, T, Di), wI (B, J, T)) of the normed input
    ``u`` (B, T, H)."""
    sa = cfg["sa_config"]
    heads, d = sa["indexer_num_heads"], sa["indexer_head_dim"]
    b, t, _ = u.shape
    u = index_input(u)
    q = project(u, w["index_q_weight"]).reshape(b, t, heads, d)
    q = rotary(q.transpose(0, 2, 1, 3), cfg["rope_theta"])
    k = layer_norm(project(u, w["index_k_weight"]), w["index_k_norm_gamma"],
                   w["index_k_norm_beta"], cfg["index_norm_eps"])
    k = rotary(k[:, None], cfg["rope_theta"])[:, 0]
    weight = project(u, w["index_w_weight"]) * index_weight_scale(cfg)
    return q, k, weight.transpose(0, 2, 1)


def index_scores(q, k, weight):
    """I (B, Tq, T): every query of the block against every key."""
    import jax.numpy as jnp

    s = index_activation(jnp.einsum("bjqd,bkd->bjqk", q, k))
    return jnp.sum(s * weight[..., None], axis=1)


def target(p):
    """P (B, Tq, K): the heads' mean probability of each chosen key, a
    constant; ``p`` (B, heads, Tq, K)."""
    import jax
    import jax.numpy as jnp

    return jax.lax.stop_gradient(jnp.mean(p, axis=1))


def index_loss(target, chosen_scores, valid):
    """sum over the block's rows of KL(P || softmax over the chosen keys of
    their index scores)."""
    import jax
    import jax.numpy as jnp

    given = jax.nn.log_softmax(jnp.where(valid, chosen_scores, -jnp.inf), -1)
    live = jnp.logical_and(valid, target > 0)
    safe = jnp.where(live, target, 1.0)
    return jnp.sum(jnp.where(live, safe * (jnp.log(safe) - jnp.where(
        live, given, 0.0)), 0.0))


def attend_block(cfg, q, index_q, index_w, first, k, v, index_k):
    """(output (B, heads, Tq, D), the indexer's summed KL) of one block of
    queries from position ``first`` on: q (B, heads, Tq, D) over all of k, v
    (B, kv, T, D), query head n reading key/value head n // (heads / kv)."""
    import jax
    import jax.numpy as jnp

    b, heads, tq, d = q.shape
    kv, t = k.shape[1:3]
    index = index_scores(index_q, index_k, index_w)
    seen = jnp.arange(t)[None, :] <= (first + jnp.arange(tq))[:, None]
    scores, chosen = jax.lax.top_k(jnp.where(seen, index, -jnp.inf),
                                   min(index_top_k(cfg), t))   # (B, Tq, K)
    valid = jnp.isfinite(scores)    # a row before the K-th has fewer

    def gather(x):                  # (B, kv, T, D) -> (B, kv, Tq, K, D)
        return jax.vmap(lambda rows, at: rows[:, at])(x, chosen)

    s = jnp.einsum("bngqd,bnqkd->bngqk", q.reshape(b, kv, heads // kv, tq, d),
                   gather(k)) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(valid[:, None, None], s, -jnp.inf), -1)
    out = jnp.einsum("bngqk,bnqkd->bngqd", p, gather(v))
    return out.reshape(b, heads, tq, d), index_loss(
        target(p.reshape(b, heads, tq, -1)), scores, valid)


def sparse_attention(cfg, q, k, v, index_q, index_k, index_w):
    """(output (B, heads, T, D), summed KL) a block of queries at a time."""
    import jax
    import jax.numpy as jnp

    b, heads, t, d = q.shape
    block = math.gcd(t, ATTENTION_BLOCK)
    n = t // block

    def blocks(x, axis=2):
        """The query axis cut into (n, ..., block, ...), blocks leading."""
        shape = x.shape[:axis] + (n, block) + x.shape[axis + 1:]
        return jnp.moveaxis(x.reshape(shape), axis, 0)

    @jax.checkpoint
    def rows(xs, k, v, index_k):
        qb, iq, iw, first = xs
        return attend_block(cfg, qb, iq, iw, first, k, v, index_k)

    out, kl = jax.lax.map(
        lambda xs: rows(xs, k, v, index_k),
        (blocks(q), blocks(index_q), blocks(index_w),
         jnp.arange(0, t, block)))
    out = jnp.moveaxis(out, 0, 2).reshape(b, heads, t, d)
    return out, jnp.sum(kl)


# --- the mixture ---------------------------------------------------------------

def route(probs, k, norm):
    """(N, E) routing weights: a token's probability at its k most probable
    experts, over their sum (+ 1e-20) if ``norm``; 0 elsewhere."""
    import jax
    import jax.numpy as jnp

    kth = jax.lax.top_k(probs, k)[0][:, -1:]
    kept = jnp.where(probs >= kth, probs, 0.0)
    if norm:
        kept = kept / (jnp.sum(kept, -1, keepdims=True) + 1e-20)
    return kept


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
    """(what the experts held here add to the rows ``t`` (N, H), the router's
    balance term over all the published experts)."""
    import jax
    import jax.numpy as jnp

    held = w["moe_gate_weight"].shape[0]
    first = cfg.get("expert_offset", 0)
    probs = jax.nn.softmax(t @ w["moe_router_weight"].T, -1)
    weights = route(probs, cfg["num_experts_per_tok"], cfg["norm_topk_prob"])
    share = jax.lax.stop_gradient(jnp.mean(weights > 0, 0))        # f_e
    penalty = cfg["router_aux_loss_coef"] * probs.shape[1] \
        * jnp.sum(share * jnp.mean(probs, 0))
    return experts(t, weights[:, first:first + held], w["moe_gate_weight"],
                   w["moe_up_weight"], w["moe_down_weight"]), penalty


# --- the model -----------------------------------------------------------------

def layer(cfg, h, w):
    """(the stream after the layer, its two auxiliary terms as they enter
    the loss)."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    b, t, hidden = h.shape

    def split(z, n, gain):
        z = rms_norm(z.reshape(b, t, n, d), gain, eps) if gain is not None \
            else z.reshape(b, t, n, d)
        return z.transpose(0, 2, 1, 3)

    u = rms_norm(h, w["input_norm_gamma"], eps)
    q = rotary(split(project(u, w["q_weight"]), heads, w["q_norm_gamma"]),
               cfg["rope_theta"])
    k = rotary(split(project(u, w["k_weight"]), kv, w["k_norm_gamma"]),
               cfg["rope_theta"])
    v = split(project(u, w["v_weight"]), kv, None)
    a, kl = sparse_attention(cfg, q, k, v, *indexer(cfg, u, w))
    h = h + project(a.transpose(0, 2, 1, 3).reshape(b, t, heads * d),
                    w["o_weight"])
    m, penalty = moe(cfg, rms_norm(h, w["post_norm_gamma"], eps).reshape(
        b * t, hidden), w)
    return h + m.reshape(b, t, hidden), \
        penalty + index_loss_coef(cfg) * kl / (b * t)


def embed(cfg, table, tokens):
    import jax.numpy as jnp

    return table[tokens.astype(jnp.int32)]


def layer_weights(p, i):
    """Layer i's parameters, their ``l<i>_`` prefix taken off."""
    return {n[len(f"l{i}_"):]: a for n, a in p.items()
            if n.startswith(f"l{i}_")}


def forward(cfg, p, tokens):
    """Scores (B*T, vocabulary)."""
    h = embed(cfg, p["embed_weight"], tokens)
    for i in range(cfg["num_hidden_layers"]):
        h, _ = layer(cfg, h, layer_weights(p, i))
    h = rms_norm(h, p["final_norm_gamma"], cfg["rms_norm_eps"])
    return h.reshape(-1, h.shape[-1]) @ p["pred_weight"].T


def logits(jax, cfg, params, tokens):
    """Scores ``(batch * time, vocab)``, batch-major, of ``tokens`` (B, T)."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda w, t: forward(cfg, w, t))(dict(params), tokens)


def head_losses(jax, cfg, x, gain, w_head, label):
    """(cross-entropy that is differentiated, mean cross-entropy of all rows
    with the pads as label 0) of the last layer's output ``x`` (B, T, H);
    the head a block of rows at a time."""
    import jax.numpy as jnp

    lab = label.reshape(-1).astype(jnp.int32)
    x = rms_norm(x, gain, cfg["rms_norm_eps"]).reshape(-1, x.shape[-1])

    @jax.checkpoint
    def nll(x, lab, w_head):
        return -jnp.take_along_axis(jax.nn.log_softmax(x @ w_head.T, -1),
                                    lab[:, None], 1)[:, 0]

    nll = jnp.concatenate(
        [nll(x[a:a + HEAD_BLOCK], lab[a:a + HEAD_BLOCK], w_head)
         for a in range(0, lab.shape[0], HEAD_BLOCK)])
    trained = jnp.sum(jnp.where(lab != 0, nll, 0.0)) / lab.shape[0]
    return trained, jnp.mean(nll)


def losses(jax, cfg, p, tokens, label):
    """(total loss that is differentiated, mean cross-entropy of all rows)
    as one function of the parameters: what ``value_and_grads``
    differentiates, a layer at a time."""
    h = embed(cfg, p["embed_weight"], tokens)
    extra = 0.0
    for i in range(cfg["num_hidden_layers"]):
        h, aux = layer(cfg, h, layer_weights(p, i))
        extra = extra + aux
    trained, ce = head_losses(jax, cfg, h, p["final_norm_gamma"],
                              p["pred_weight"], label)
    return trained + extra, ce


def chain(jax, cfg):
    """The five compiled pieces of :func:`value_and_grads`: the embedding and
    its vector-Jacobian product, a layer's forward and its vector-Jacobian
    product (its auxiliary terms enter with cotangent 1), the head's losses
    and their gradient. Made once by a caller that takes several steps
    (:func:`adam_steps`): a new ``jax.jit`` compiles again."""
    return dict(
        embed=jax.jit(lambda e, t: embed(cfg, e, t)),
        embed_back=jax.jit(lambda e, t, g: jax.vjp(
            lambda e: embed(cfg, e, t), e)[1](g)[0]),
        forward=jax.jit(lambda h, w: layer(cfg, h, w)[0]),
        backward=jax.jit(lambda h, w, g: jax.vjp(
            lambda h, w: layer(cfg, h, w), h, w)[1]((g, 1.0))),
        head=jax.jit(jax.value_and_grad(
            lambda x, g, w, l: head_losses(jax, cfg, x, g, w, l),
            argnums=(0, 1, 2), has_aux=True)))


def value_and_grads(jax, cfg, params, tokens, label, pieces=None):
    """(mean cross-entropy, {name: d(total loss)/d(parameter)}): the chain
    rule over :func:`losses` written out a layer at a time, each layer's
    forward and each layer's vector-Jacobian product a call of its own
    (``pieces``: :func:`chain`), so that the host holds one layer's
    intermediates at a time; the CPU test holds it to autodiff of the
    whole."""
    params = dict(params)
    run = pieces or chain(jax, cfg)
    depth = cfg["num_hidden_layers"]
    with jax.default_matmul_precision("highest"):
        h = run["embed"](params["embed_weight"], tokens)
        inputs = []
        for i in range(depth):
            inputs.append(h)
            h = run["forward"](h, layer_weights(params, i))
        (_, ce), back = run["head"](
            h, params["final_norm_gamma"], params["pred_weight"], label)
        g, grads = back[0], {"final_norm_gamma": back[1],
                             "pred_weight": back[2]}
        for i in reversed(range(depth)):
            g, dw = run["backward"](inputs[i], layer_weights(params, i), g)
            grads.update({f"l{i}_{n}": a for n, a in dw.items()})
        grads["embed_weight"] = run["embed_back"](
            params["embed_weight"], tokens, g)
    return ce, grads


def first_step(jax, cfg, params, data, label):
    """{"loss": mean cross-entropy over all rows, "grad_norm": norm of
    d(total loss)/dW over every leaf, the indexer's included}."""
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
    seen, pieces = [], chain(jax, cfg)
    for t, (tokens, label) in enumerate(batches, 1):
        ce, grads = value_and_grads(jax, cfg, params, tokens, label, pieces)
        seen.append(float(ce))
        lr_t = lr * math.sqrt(1.0 - beta2 ** t) / (1.0 - beta1 ** t)
        for n, g in grads.items():
            g = g * grad_scale
            mean[n] = beta1 * mean[n] + (1.0 - beta1) * g
            var[n] = beta2 * var[n] + (1.0 - beta2) * g * g
            params[n] = params[n] - lr_t * mean[n] / (jnp.sqrt(var[n]) + eps)
    return seen
