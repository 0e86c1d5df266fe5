"""Plain reference of ``ouro-2.6b``: the looped language model of ByteDance's
Ouro-2.6B (Zhu et al. 2025, *Scaling Latent Reasoning via Looped Language
Models*, arXiv:2510.25741; ``config.json`` named in the configuration's
``source``; what ``config.json`` has no key for follows the paper and the
family's public ``modeling_ouro.py`` and is marked (+) here and listed under
``assumed`` in the configuration) in ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, on the host's CPU device. Whole
score rows against a mask, one head at a time, the passes a Python loop over
ONE dict of weights. ``objective`` is the whole model as one function, and
``jax.grad`` of it is what the gradient IS (the CPU test takes it); at
published widths ``value_and_grads`` writes that chain rule out a piece at a
time (a layer application, an exit's head, ...), each piece's
vector-Jacobian product by ``jax.vjp``, only so that 4 x N layer
applications of 4096 positions and four (4096, 49152) logits fit in the
host's memory; attention runs a block of query rows at a time under
``jax.checkpoint`` for the same reason, and every block scores ALL the keys
against the mask.

It shares only parameter names and layouts with ``mxnet_tpu``: ``*_weight``
of a projection is ``(out, in)``; ``early_exit_gate_weight`` ``(1, H)``,
``early_exit_gate_bias`` ``(1,)``.

The model, ``h`` the ``(B, T, H)`` stream, R = ``total_ut_steps``::

    h(0) = embed[ids]
    a layer:  u = rms(x; input_norm)
              q, k, v = Wq u, Wk u, Wv u -> (16, 128) each; q, k = rotary(q), rotary(k)
              a = softmax(q k^T / sqrt(128) + causal mask) v
              x = x + rms(Wo a; post_attn_norm)                         (+)
              u = rms(x; pre_mlp_norm)
              x = x + rms(down(silu(gate u) * up u); post_mlp_norm)     (+)
    a pass:   h(t) = rms(layers(h(t-1)); final_norm),  t = 1..R: the SAME
              layers and the SAME final norm; the normed stream is what the
              next pass reads                                           (+)
    an exit:  z_t = W_out h(t);  l_t = -log softmax(z_t)[y]
              lam_t = sigmoid(w_g . h(t) + b_g)
    exit distribution:  S_0 = 1, S_t = S_(t-1) (1 - lam_t),
              p_t = lam_t S_(t-1) for t < R,  p_R = S_(R-1)
    objective (Stage I): J = sum over non-pad rows of
              [ sum_t p_t l_t - beta H(p) ],  H(p) = -sum_t p_t log p_t,
              divided by ALL rows; beta 0.1                             (+)

``first_step``'s ``loss`` is the plain mean over all rows of the LAST exit's
cross-entropy (what the program's one output, ``softmax(z_R)``, lets the
driver compute); ``grad_norm`` is of ``J`` over every parameter. Adam is
MXNet's (``olmoe-1b-7b.py``).

Tolerances (relative), with their reasons (readings: PERF.md section 6,
PR 55; 1 x 4096 seeded tokens at published widths).

``TOLERANCES`` hold the program's bfloat16 trunk against this float32
reference on the driver's first training step. The loss of seeded weights
sits near ln(vocabulary) + half the logits' variance whatever the layers
compute (11.22 here), so it holds the program to the last exit's softmax,
the label shift and the row count (limit 4e-4, the accepted cells': 12 times
the largest reading); the check rests on ``grad_norm``, the norm of the
gradient of ``J`` over every parameter, which every exit, every pass and the
exit distribution enter (the gate's weight is 4-5% of the norm's square:
each row pulls it the same way; ``tools/ouro_gate_check.py`` prints the
gate's gradients by name all the same). Its limit lies between two
readings. The bfloat16 trunk on the chip over six seeds: loss 1.1e-6 to
3.2e-5; grad_norm 2.6e-5, 2.4e-4, 7.2e-4, 1.5e-3, 2.1e-3, 2.8e-3, above
and below the reference alike: rounding, four times the Trinity cell's
4.5e-4 because a gradient here crosses 16 layer applications and not 5.
And this reference computed in the precision below, float8_e4m3fn weights
and matmul inputs: loss 4.9e-5, grad_norm 0.81, which comes out as not
correct, by grad_norm and not by the loss. 1e-2 is 3.6 of the trunk's
largest and 1/81 of float8's. (The limit was 2e-3, Trinity's, before the
first reading, and the first traced run read 2.8e-3 against it.)

``F32_TOLERANCES`` hold a float32 trunk (the CPU tests): both sides compute
in float32 and differ by the order of their sums. ``F32_TENSOR_TOLERANCE`` is
for logits, probabilities and each parameter's gradient, as
``max |a - b| / max |b|`` a tensor.
"""

from __future__ import annotations

import math

TOLERANCES = {"loss": 4e-4, "grad_norm": 1e-2}
F32_TOLERANCES = {"loss": 1e-6, "grad_norm": 1e-5}
F32_TENSOR_TOLERANCE = 3e-4

ATTENTION_BLOCK = 512   # queries a checkpointed block (memory only)


def rms_norm(x, gain, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) \
        * gain


def rotary(x, theta):
    """Rotate-half over the whole last axis of ``x`` (B, heads, T, D)."""
    import jax.numpy as jnp

    t, d = x.shape[-2:]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], -1)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(emb) + half * jnp.sin(emb)


def project(x, w):
    """``x`` through a weight ``(out, in)``."""
    return x @ w.T


def attention(q, k, v):
    """Causal softmax attention of q over k, v, all (B, heads, T, D)."""
    import jax
    import jax.numpy as jnp

    t, d = q.shape[-2:]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = j <= i

    @jax.checkpoint
    def rows(qb, mb, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, k) / math.sqrt(d)
        s = jnp.where(mb, s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    return jnp.concatenate(
        [rows(q[:, :, a:a + ATTENTION_BLOCK], mask[a:a + ATTENTION_BLOCK],
              k, v) for a in range(0, t, ATTENTION_BLOCK)], axis=2)


def swiglu(u, w_gate, w_up, w_down):
    import jax

    return project(jax.nn.silu(project(u, w_gate)) * project(u, w_up),
                   w_down)


def post_norm(x, gain, eps):
    """The second norm of a sandwich: on a half-layer's output, before the
    residual add."""
    return rms_norm(x, gain, eps)


def layer(cfg, h, w):
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    b, t, _ = h.shape

    def split(z):
        return z.reshape(b, t, heads, d).transpose(0, 2, 1, 3)

    u = rms_norm(h, w["input_norm_gamma"], eps)
    q = rotary(split(project(u, w["q_weight"])), theta)
    k = rotary(split(project(u, w["k_weight"])), theta)
    v = split(project(u, w["v_weight"]))
    a = attention(q, k, v).transpose(0, 2, 1, 3).reshape(b, t, heads * d)
    h = h + post_norm(project(a, w["o_weight"]), w["post_attn_norm_gamma"],
                      eps)
    u = rms_norm(h, w["pre_mlp_norm_gamma"], eps)
    m = swiglu(u, w["mlp_gate_weight"], w["mlp_up_weight"],
               w["mlp_down_weight"])
    return h + post_norm(m, w["post_mlp_norm_gamma"], eps)


def layer_weights(p, i):
    """Layer i's parameters, their ``l<i>_`` prefix taken off."""
    return {n[len(f"l{i}_"):]: a for n, a in p.items()
            if n.startswith(f"l{i}_")}


def ut_steps(cfg):
    """R: the passes over the one stack."""
    return cfg["total_ut_steps"]


def carried(normed, raw):
    """What the next pass reads of a pass's output: the normed stream. (+)"""
    return normed


def entropy_weight(cfg):
    """beta, the weight of the exit distribution's entropy."""
    return cfg["exit_entropy_beta"]


def last_share(lam_last, stay):
    """p_R: all that has not left before the last exit; its gate is not
    read."""
    return stay


def embed(table, tokens):
    import jax.numpy as jnp

    return table[tokens.astype(jnp.int32)]


def pass_end(cfg, x, gain):
    """(h(t), what pass t + 1 reads) of a pass's last layer's output."""
    normed = rms_norm(x, gain, cfg["rms_norm_eps"])
    return normed, carried(normed, x)


def streams(cfg, p, tokens):
    """[h(1), ..., h(R)], each the normed (B, T, H) output of a pass."""
    import jax

    apply_layer = jax.checkpoint(lambda h, w: layer(cfg, h, w))
    x = embed(p["embed_weight"], tokens)
    out = []
    for _ in range(ut_steps(cfg)):
        for i in range(cfg["num_hidden_layers"]):
            x = apply_layer(x, layer_weights(p, i))
        normed, x = pass_end(cfg, x, p["final_norm_gamma"])
        out.append(normed)
    return out


def exit_logits(w_head, h):
    """(B*T, vocabulary) scores of one exit."""
    return project(h.reshape(-1, h.shape[-1]), w_head)


def exit_nll(h, w_head, lab):
    """(B*T,) cross-entropy of one exit's rows against ``lab``."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.log_softmax(exit_logits(w_head, h), -1)
    return -jnp.take_along_axis(scores, lab[:, None], 1)[:, 0]


def gate(h, w_gate, b_gate):
    """(B*T,) lam of one exit."""
    import jax

    score = project(h.reshape(-1, h.shape[-1]), w_gate)
    return jax.nn.sigmoid(score[:, 0] + b_gate[0])


def exit_distribution(lams):
    """(rows, R) p of the R gates' ``lams`` [(rows,), ...]."""
    import jax.numpy as jnp

    stay, shares = jnp.ones_like(lams[0]), []
    for lam in lams[:-1]:
        shares.append(lam * stay)
        stay = stay * (1.0 - lam)
    shares.append(last_share(lams[-1], stay))
    return jnp.stack(shares, 1)


def mixture(cfg, losses, lams, lab):
    """(J over all rows, mean cross-entropy of the last exit over all rows
    with the pads as label 0, the exit distribution (rows, R)) of the R
    exits' cross-entropies and gates [(rows,), ...]."""
    import jax.numpy as jnp

    shares = exit_distribution(lams)
    # 0 log 0 = 0: a gate saturated in float32 gives a share of exactly 0
    some = shares > 0
    entropy = -jnp.sum(jnp.where(some, shares * jnp.log(
        jnp.where(some, shares, 1.0)), 0.0), 1)
    row = jnp.sum(shares * jnp.stack(losses, 1), 1) \
        - entropy_weight(cfg) * entropy
    trained = jnp.sum(jnp.where(lab != 0, row, 0.0)) / lab.shape[0]
    return trained, (jnp.mean(losses[-1]), shares)


def objective(cfg, p, tokens, label):
    """The model's objective as ONE function of its parameters (what the
    CPU test differentiates whole, to hold :func:`value_and_grads` to)."""
    import jax.numpy as jnp

    lab = label.reshape(-1).astype(jnp.int32)
    hs = streams(cfg, p, tokens)
    return mixture(
        cfg, [exit_nll(h, p["pred_weight"], lab) for h in hs],
        [gate(h, p["early_exit_gate_weight"], p["early_exit_gate_bias"])
         for h in hs], lab)


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
    """[(batch * time, vocab) scores of exit t, t = 1..R], batch-major."""
    params, tokens = _on_host(jax, dict(params), tokens)
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda w, t: [exit_logits(w["pred_weight"], h)
                                     for h in streams(cfg, w, t)])(
                                         params, tokens)


def value_and_grads(jax, cfg, params, tokens, label):
    """(last exit's mean cross-entropy, {name: dJ/d(parameter)}, the exit
    distribution (rows, R)): the chain rule over :func:`objective` written
    out a piece at a time (a layer application, a pass's end, an exit's
    head, an exit's gate, the mixture of the R x 2 numbers a row), each
    piece's forward and each piece's vector-Jacobian product a jitted call
    of its own, a shared weight's gradients added as they come. XLA:CPU
    gives one program of the whole (``jax.grad`` of ``objective``) every
    layer application's intermediates at once: over 40 GB at published
    widths and 4096 positions, more than the chip machine's host has (my
    chip run, PR 55); this way the host holds one piece's at a time. The
    CPU test holds the two equal."""
    import jax.numpy as jnp

    params, tokens, label = _on_host(jax, dict(params), tokens, label)
    lab = label.reshape(-1).astype(jnp.int32)
    layers = range(cfg["num_hidden_layers"])
    grads = {}

    def add(name, g):
        grads[name] = grads[name] + g if name in grads else g

    def back(f):
        return jax.jit(lambda args, g: jax.vjp(f, *args)[1](g))

    run_layer = jax.jit(lambda h, w: layer(cfg, h, w))
    layer_back = back(lambda h, w: layer(cfg, h, w))
    run_end = jax.jit(lambda x, gain: pass_end(cfg, x, gain))
    end_back = back(lambda x, gain: pass_end(cfg, x, gain))
    # every jit is of a lambda made here: a call traces the module's
    # functions as they are NOW (the tests swap them)
    run_nll = jax.jit(lambda h, w: exit_nll(h, w, lab))
    nll_back = back(lambda h, w: exit_nll(h, w, lab))
    run_gate = jax.jit(lambda h, w, b: gate(h, w, b))
    gate_back = back(lambda h, w, b: gate(h, w, b))
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda e, t: embed(e, t))(params["embed_weight"], tokens)
        inputs, ends, hs = [], [], []
        for _ in range(ut_steps(cfg)):
            for i in layers:
                inputs.append(x)
                x = run_layer(x, layer_weights(params, i))
            ends.append(x)
            normed, x = run_end(x, params["final_norm_gamma"])
            hs.append(normed)
        gate_of = (params["early_exit_gate_weight"],
                   params["early_exit_gate_bias"])
        losses = [run_nll(h, params["pred_weight"]) for h in hs]
        lams = [run_gate(h, *gate_of) for h in hs]
        (_, (ce, shares)), (d_losses, d_lams) = jax.jit(jax.value_and_grad(
            lambda l, m: mixture(cfg, l, m, lab), argnums=(0, 1),
            has_aux=True))(losses, lams)
        carry = jnp.zeros_like(x)     # dJ/d(what the pass after reads)
        for t in reversed(range(len(hs))):
            d_h, d_head = nll_back((hs[t], params["pred_weight"]),
                                   d_losses[t])
            add("pred_weight", d_head)
            d_g, d_w, d_b = gate_back((hs[t],) + gate_of, d_lams[t])
            add("early_exit_gate_weight", d_w)
            add("early_exit_gate_bias", d_b)
            g, d_gain = end_back((ends[t], params["final_norm_gamma"]),
                                 (d_h + d_g, carry))
            add("final_norm_gamma", d_gain)
            for i in reversed(layers):
                g, dw = layer_back((inputs.pop(), layer_weights(params, i)),
                                   g)
                for n, a in dw.items():
                    add(f"l{i}_{n}", a)
            carry = g
        grads["embed_weight"] = jax.jit(lambda e, t, g: jax.vjp(
            lambda e: embed(e, t), e)[1](g)[0])(
                params["embed_weight"], tokens, carry)
    return ce, grads, shares


def first_step(jax, cfg, params, data, label):
    """{"loss": the last exit's mean cross-entropy over all rows,
    "grad_norm": norm of dJ/dW over every leaf}."""
    import jax.numpy as jnp

    ce, grads, _ = value_and_grads(jax, cfg, params, data, label)
    norm = jnp.sqrt(sum(jnp.sum(g ** 2) for g in grads.values()))
    return {"loss": float(ce), "grad_norm": float(norm)}


def adam_steps(jax, cfg, params, batches, lr, beta1, beta2, eps,
               grad_scale=1.0):
    """The last exit's mean cross-entropy before each of MXNet's Adam steps
    on ``batches`` = [(tokens, label), ...]: ``lr_t = lr sqrt(1-b2^t) /
    (1-b1^t)``, ``w -= lr_t m / (sqrt(v) + eps)``. ``grad_scale`` is what
    the program's gradient is of ``J``'s: rows (the summed objective) over
    the batch's rows (``rescale_grad``), so the sequence length."""
    import jax.numpy as jnp

    params = dict(params)
    mean = {n: jnp.zeros_like(a) for n, a in params.items()}
    var = dict(mean)
    seen = []
    for t, (tokens, label) in enumerate(batches, 1):
        ce, grads, _ = value_and_grads(jax, cfg, params, tokens, label)
        seen.append(float(ce))
        lr_t = lr * math.sqrt(1.0 - beta2 ** t) / (1.0 - beta1 ** t)
        for n, g in grads.items():
            g = g * grad_scale
            mean[n] = beta1 * mean[n] + (1.0 - beta1) * g
            var[n] = beta2 * var[n] + (1.0 - beta2) * g * g
            params[n] = params[n] - lr_t * mean[n] / (jnp.sqrt(var[n]) + eps)
    return seen
