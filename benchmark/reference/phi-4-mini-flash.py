"""Plain reference of ``phi-4-mini-flash``: Microsoft's
Phi-4-mini-flash-reasoning (``config.json`` named in the configuration's
``source``, ``model_type`` phi4flash; Ren et al. 2025, *Decoder-Hybrid-Decoder
Architecture for Efficient Reasoning with Long Generation*, arXiv:2507.06607:
SambaY with differential attention; the Mamba mixer of Gu and Dao 2023,
arXiv:2312.00752; differential attention of Ye et al. 2024, arXiv:2410.05258,
in its ``multihead_flashdiff_2`` layout; what ``config.json`` has no key for
is marked (+) here and listed under ``assumed`` in the configuration) in
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``. The scan
is a ``lax.scan`` over T, a token at a time; attention scores whole rows
against a mask, a block of query rows at a time under ``jax.checkpoint``
(memory only: every block scores ALL the keys). ``losses`` is the whole
model as one function, and ``jax.grad`` of it is what the gradient IS (the
CPU test takes it); ``value_and_grads`` writes that chain rule out a layer
at a time so that the host holds one layer's intermediates (a scan layer's
(T, 5120, 16) float32 states, 1.3 GB an array), and carries the cotangents
of the two tensors that layers far apart share (the memory ``M``, the
shared keys and values) to the layer that made them.

It shares only parameter names and layouts with ``mxnet_tpu``: ``*_weight``
of a projection is ``(out, in)``; ``conv_weight`` ``(channels, taps)``;
``scan_A_log`` ``(channels, states)``; ``scan_dt_bias`` is ``dt_proj``'s bias.

The model, ``x`` the ``(B, T, H)`` stream, layer ``l`` its PUBLISHED index::

    x = x + mixer_l(LN(x; input_norm));  x = x + MLP(LN(x; post_norm))
    LN(x) = (x - mean) / sqrt(var + eps) * gamma + beta          eps 1e-5
    MLP(u): (gate, up) = split(fc1 u);  fc2 (up * silu(gate))
    out: logits = LN(x; final_norm) E^T          E the embedding (tied)

    mamba:  (xs, z) = split(in_proj u);  xs = silu(conv1d(xs) + b)   4 taps (+)
            (dt, B, C) = split(x_proj xs, [160, 16, 16])             (+)
            delta = softplus(dt_proj dt + b_dt);  A = -exp(A_log)
            h_t = exp(delta_t A) h_{t-1} + delta_t B_t xs_t,  h_{-1} = 0
            y_t = C_t . h_t + D xs_t;   out_proj (y * silu(z))
            mamba_memory also exports M = y (before the gate)
    window / full_shared (differential attention):
            (q, k, v) = split(Wqkv u + b) as 40 / 20 / 20 heads of 64  (+ b)
            pairs of consecutive heads (+): q1, q2 = q[2n], q[2n+1] (20
            pairs), k1, k2 likewise (10), v = [v[2n] | v[2n+1]] (10 of 128)
            a_i = softmax(q_i k_i^T / 8 + mask) v,  query pair n over
                  key/value pair n // 2;  mask: causal, and on a window
                  layer 0 <= t - s < 512
            lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init(l)
            lam_init(l) = 0.8 - 0.6 exp(-0.3 l)
            o = rms(a_1 - lam a_2; subln, eps 1e-5 (+)) (1 - lam_init(l))
            Wo reshape(o) + b;   full_shared also exports (k1, k2, v)
    gmu:    W_2 (silu(W_1 u) * M)
    cross:  differential attention with q = Wq u + b alone, over
            full_shared's (k1, k2, v), causal

Cross-entropy, Adam and their departures are those of ``olmoe-1b-7b.py``
(summed over the rows whose label is not the pad (0), divided by ALL rows;
``first_step``'s ``loss`` is the plain mean over all rows).

Tolerances (relative), with their reasons. ``TOLERANCES`` hold the program's
bfloat16 trunk against this float32 reference on the driver's first training
step, 1 x 4096 seeded tokens at published widths. Every reading is a chip run
of PR 65 and can be made again: the lower ones are the traced runs'
``compared``, the upper ones ``tools/phi4flash_readings.py`` (this file
against itself with a precision lowered or a piece changed; seeds 6500000031
and 2965000112). PERF.md section 6 has them with their seeds. All are read
with the convolution's taps at normal(0, 0.5) (``configs/phi-4-mini-flash.py:
init_rule``): with taps of 0.02, the first tree's, the recurrence was 4e-4
of a scan's output beside the ``D x`` skip and no reading could see it (a
bfloat16 state read 0.0 / 7.8e-8); with these it is 0.3 of it.

What a sound program is off by, six traced runs (two of the tree before the
review's clean-up, four of the final one from a clean checkout), ``loss`` /
``grad_norm``: 4.2e-6 to **5.30e-5** / 7.8e-7 to **2.78e-4**: rounding
(4.85e-5 / 2.74e-4, 6.9e-6 / 7.8e-7; 1.19e-5 / 1.39e-4, 1.43e-5 / 1.62e-4,
5.30e-5 / 1.27e-4, 4.2e-6 / 2.78e-4).

What a lowered precision or a changed piece moves, ``loss`` / ``grad_norm``
(where two, the two seeds): float8_e4m3fn weights and projection inputs (the
precision below the bfloat16 the configuration states) 2.1e-5 / **0.878**;
the ``D`` skip dropped 1.2e-3 / 0.376; the RECURRENCE dropped (``y = D x``
alone) 9.5e-4 / 3.1e-2 and 6.8e-4 / 5.1e-2; the sub-norm dropped 5.3e-4 /
4.9e-2 and 6.5e-4 / 7.9e-2; ``lam_init`` by the place in the cut 3.7e-4 /
**8.2e-3** and 7.0e-5 / 9.3e-3; a bfloat16 scan STATE 5.5e-6 / 4.1e-4 and
4.1e-5 / 3.9e-4.

``grad_norm``, the norm of the gradient over every parameter, which every
mixer enters: 2e-3 (the Trinity and Mellum2 cells') lies 7.2 times over the
trunk's largest of six (2.78e-4) and 4.1 times under the least of the
changed pieces (``lam_init`` by the place in the cut, 8.2e-3), 439 times
under the float8 reading: the float8 reference is not correct, by this limit
and not by the other. It fails the recurrence, the skip and the sub-norm
dropped (15, 188 and 24 times over at their lesser seed). The more room is
above the trunk's reading, since fresh seeds read higher.
``loss``: THE LOSS HARDLY SEES THE PRECISION here (a loss of seeded weights
sits near ln(vocabulary) whatever the layers compute: the float8 reference's
2.1e-5 lies INSIDE the trunk's own range), so it takes the limit of the
harness's accepted cells, 4e-4 (Trinity's, Kimi-Linear's, Ouro's), which
leaves the trunk's largest reading 7.5 times of room; it holds the program to
the head, the label shift and the row count, and fails the recurrence, the
skip and the sub-norm dropped on its own (1.7, 3 and 1.3 times over).

**What ``correct`` does not hold in this cell, which ISSUE 65 asked of it**:
a bfloat16 scan state. It moves ``grad_norm`` by 3.9e-4 to 4.1e-4, which is
1.4 times the trunk's own largest reading: the trunk IS bfloat16 (the scan's
operands, ``x``, ``dt``, ``B`` and ``C``, reach the kernels rounded to it),
and its rounding moves the norm as much as the state's would, so no limit
lies between the two with room. What holds the state to float32 is the
operator's own test (``tests/test_selective_scan.py``: on operands bfloat16
holds exactly, the forms against a float32 token loop to 1e-5, a bfloat16
state 1e-3 and more off). A cross layer reading another layer's keys is not
read at the cell's size (the chain a layer at a time has no place for it);
it, and the sum of two readers' cotangents, are held by
``tests/test_phi4flash.py``, and the benchmark's
``attention.shared_kv_layers_per_step.seq`` reads the wiring off the symbol.

``F32_TOLERANCES`` hold a float32 trunk (the CPU tests): both sides compute
in float32 and differ by the order of their sums. ``F32_TENSOR_TOLERANCE`` is
for logits and each parameter's gradient, as ``max |a - b| / max |b|`` a
tensor.
"""

from __future__ import annotations

import math

TOLERANCES = {"loss": 4e-4, "grad_norm": 2e-3}
F32_TOLERANCES = {"loss": 1e-6, "grad_norm": 1e-5}
F32_TENSOR_TOLERANCE = 3e-4

ATTENTION_BLOCK = 512   # queries a checkpointed block (memory only)
HEAD_BLOCK = 1024       # rows a checkpointed block of the head
SCAN_STATE_DTYPE = "float32"    # a test lowers it


# --- the pieces ----------------------------------------------------------------

def layer_norm(x, gain, bias, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def rms_norm(x, gain, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def project(x, weight, bias=None):
    """``x`` through a weight ``(out, in)`` and its bias, where it has one."""
    y = x @ weight.T
    return y if bias is None else y + bias


def silu(x):
    import jax

    return x * jax.nn.sigmoid(x)


def mlp(u, w):
    import jax.numpy as jnp

    gate, up = jnp.split(project(u, w["fc1_weight"]), 2, -1)
    return project(up * silu(gate), w["fc2_weight"])


def causal_conv(x, weight, bias):
    """Depthwise over time: ``y_t[c] = b[c] + sum_j w[c, j] x_{t-K+1+j}[c]``,
    zeros before the row."""
    import jax.numpy as jnp

    taps, t = weight.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return bias + sum(xp[:, j:j + t] * weight[:, j] for j in range(taps))


def skip(d, xs):
    """The scan's ``D x`` term."""
    return d * xs


def selective_scan(xs, delta, a, b, c):
    """``y_t = C_t . h_t`` of ``h_t = exp(delta_t A) h_{t-1} + delta_t B_t
    x_t`` a token at a time: xs, delta (B, T, C), a (C, N), b, c (B, T, N)."""
    import jax
    import jax.numpy as jnp

    state = jnp.dtype(SCAN_STATE_DTYPE)

    def step(h, row):
        dl, x, bt, ct = row
        h = jnp.exp(dl[:, :, None] * a) * h.astype(jnp.float32) \
            + (dl * x)[:, :, None] * bt[:, None, :]
        h = h.astype(state)
        return h, jnp.sum(h.astype(jnp.float32) * ct[:, None, :], -1)

    rows = [z.swapaxes(0, 1) for z in (delta, xs, b, c)]
    _, ys = jax.lax.scan(
        step, jnp.zeros((xs.shape[0], xs.shape[2], a.shape[1]), state),
        tuple(rows))
    return ys.swapaxes(0, 1)


def mamba(cfg, u, w):
    """(the mixer's output, the scan's output before the gate)."""
    import jax
    import jax.numpy as jnp

    n, rank = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    xs, z = jnp.split(project(u, w["in_proj_weight"]), 2, -1)
    xs = silu(causal_conv(xs, w["conv_weight"], w["conv_bias"]))
    low = project(xs, w["x_proj_weight"])
    delta = jax.nn.softplus(project(low[..., :rank], w["dt_proj_weight"],
                                    w["scan_dt_bias"]))
    y = selective_scan(xs, delta, -jnp.exp(w["scan_A_log"]),
                       low[..., rank:rank + n], low[..., rank + n:]) \
        + skip(w["scan_D"], xs)
    return project(y * silu(z), w["out_proj_weight"]), y


def pair_heads(x, pairs, head_dim):
    """(B, T, 2 pairs head_dim) -> the two heads of each consecutive pair,
    (B, pairs, T, head_dim) each. (+)"""
    b, t, _ = x.shape
    x = x.reshape(b, t, pairs, 2, head_dim)
    return x[:, :, :, 0].transpose(0, 2, 1, 3), \
        x[:, :, :, 1].transpose(0, 2, 1, 3)


def pair_values(x, pairs, head_dim):
    """(B, T, 2 pairs head_dim) -> (B, pairs, T, 2 head_dim): a pair's two
    value heads side by side."""
    b, t, _ = x.shape
    return x.reshape(b, t, pairs, 2 * head_dim).transpose(0, 2, 1, 3)


def attention(q, k, v, window):
    """Causal softmax attention of q (B, P, T, d) over k (B, Pk, T, d) and v
    (B, Pk, T, dv), query pair n over key/value pair n // (P / Pk); under a
    ``window`` a query reads the keys ``0 <= t - s < window``."""
    import jax
    import jax.numpy as jnp

    t, d = q.shape[-2:]
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = j <= i
    if window:
        mask = mask & (i - j < window)

    @jax.checkpoint
    def rows(qb, mb, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, k) / math.sqrt(d)
        s = jnp.where(mb, s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    return jnp.concatenate(
        [rows(q[:, :, a:a + ATTENTION_BLOCK], mask[a:a + ATTENTION_BLOCK],
              k, v) for a in range(0, t, ATTENTION_BLOCK)], axis=2)


def lam_init(layer_id):
    return 0.8 - 0.6 * math.exp(-0.3 * layer_id)


def sub_norm(x, gain, eps):
    """The norm over a pair's 2 head_dim after the subtraction."""
    return rms_norm(x, gain, eps)


def differential(cfg, layer_id, q, kv, window, w):
    """``q`` (B, T, heads x head_dim) over ``kv`` = (k1, k2, v)."""
    import jax.numpy as jnp

    heads = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // heads
    b, t, _ = q.shape
    q1, q2 = pair_heads(q, heads // 2, d)
    k1, k2, v = kv
    lam = jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"])) \
        - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) \
        + lam_init(layer_id)
    o = attention(q1, k1, v, window) - lam * attention(q2, k2, v, window)
    o = sub_norm(o, w["subln_gamma"], cfg["subln_eps"]) \
        * (1.0 - lam_init(layer_id))
    return project(o.transpose(0, 2, 1, 3).reshape(b, t, heads * d),
                   w["out_proj_weight"], w.get("out_proj_bias"))


def keys_and_values(cfg, qkv):
    """(q (B, T, heads x head_dim), (k1, k2, v)) of a layer's ``Wqkv u``."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    q, k, v = (qkv[..., :heads * d], qkv[..., heads * d:(heads + kv) * d],
               qkv[..., (heads + kv) * d:])
    return q, (*pair_heads(k, kv // 2, d), pair_values(v, kv // 2, d))


def gmu(u, memory, w):
    return project(silu(project(u, w["in_proj_weight"])) * memory,
                   w["out_proj_weight"])


def mixer(cfg, kind, layer_id, u, w, memory, shared):
    """(the mixer's output, what the layer exports: M of a ``mamba_memory``,
    (k1, k2, v) of a ``full_shared``, else ())."""
    if kind in ("mamba", "mamba_memory"):
        out, y = mamba(cfg, u, w)
        return out, (y if kind == "mamba_memory" else ())
    if kind in ("window", "full_shared"):
        q, kv = keys_and_values(cfg, project(u, w["qkv_weight"],
                                             w.get("qkv_bias")))
        window = cfg["sliding_window"] if kind == "window" else 0
        return differential(cfg, layer_id, q, kv, window, w), \
            (kv if kind == "full_shared" else ())
    if kind == "gmu":
        return gmu(u, memory, w), ()
    if kind == "cross":
        q = project(u, w["q_weight"], w.get("q_bias"))
        return differential(cfg, layer_id, q, shared, 0, w), ()
    raise ValueError(f"no layer kind {kind!r}")


def layer(cfg, kind, layer_id, h, w, memory, shared):
    """(the stream after the layer, what it exports)."""
    eps = cfg["layer_norm_eps"]
    u = layer_norm(h, w["input_norm_gamma"], w["input_norm_beta"], eps)
    out, exports = mixer(cfg, kind, layer_id, u, w, memory, shared)
    h = h + out
    u = layer_norm(h, w["post_norm_gamma"], w["post_norm_beta"], eps)
    return h + mlp(u, w), exports


# --- the model -----------------------------------------------------------------

def embed(table, tokens):
    import jax.numpy as jnp

    return table[tokens.astype(jnp.int32)]


def layer_weights(p, i):
    """Layer i's parameters, their ``l<i>_`` prefix taken off."""
    return {n[len(f"l{i}_"):]: a for n, a in p.items()
            if n.startswith(f"l{i}_")}


def layers_of(cfg):
    """[(kind, published index), ...] of the layers that are built."""
    return list(zip(cfg["layer_kinds"], cfg["layer_ids"]))


def carry(kind, exports, memory, shared):
    """(memory, shared) after a layer of ``kind`` that exported ``exports``."""
    if kind == "mamba_memory":
        return exports, shared
    if kind == "full_shared":
        return memory, exports
    return memory, shared


def stream(cfg, p, tokens):
    """The last layer's output (B, T, H)."""
    h = embed(p["embed_weight"], tokens)
    memory = shared = None
    for i, (kind, layer_id) in enumerate(layers_of(cfg)):
        h, exports = layer(cfg, kind, layer_id, h, layer_weights(p, i),
                           memory, shared)
        memory, shared = carry(kind, exports, memory, shared)
    return h


def final(cfg, x, p):
    x = layer_norm(x, p["final_norm_gamma"], p["final_norm_beta"],
                   cfg["layer_norm_eps"])
    return x.reshape(-1, x.shape[-1])


def forward(cfg, p, tokens):
    """Scores (B*T, vocabulary)."""
    return final(cfg, stream(cfg, p, tokens), p) @ p["embed_weight"].T


def _on_host(jax, *trees):
    """The arguments on the host's CPU device, where there is one: the
    reference runs there, in true float32 and in the host's memory (a scan
    layer's backward holds several (T, 5120, 16) float32 arrays), and takes
    nothing from a chip that the job under test has filled."""
    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError:
        return trees
    return jax.device_put(trees, cpu)


def logits(jax, cfg, params, tokens):
    """Scores ``(batch * time, vocab)``, batch-major, of ``tokens`` (B, T)."""
    params, tokens = _on_host(jax, dict(params), tokens)
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda w, t: forward(cfg, w, t))(dict(params), tokens)


def head_losses(jax, cfg, x, head, label):
    """(cross-entropy that is differentiated, mean cross-entropy of all rows
    with the pads as label 0) of the last layer's output ``x`` (B, T, H)
    and ``head`` = the final norm's two vectors and the table; the head a
    block of rows at a time."""
    import jax.numpy as jnp

    lab = label.reshape(-1).astype(jnp.int32)
    rows = final(cfg, x, head)

    @jax.checkpoint
    def nll(x, lab, table):
        return -jnp.take_along_axis(jax.nn.log_softmax(x @ table.T, -1),
                                    lab[:, None], 1)[:, 0]

    nll = jnp.concatenate(
        [nll(rows[a:a + HEAD_BLOCK], lab[a:a + HEAD_BLOCK],
             head["embed_weight"])
         for a in range(0, lab.shape[0], HEAD_BLOCK)])
    trained = jnp.sum(jnp.where(lab != 0, nll, 0.0)) / lab.shape[0]
    return trained, jnp.mean(nll)


HEAD_LEAVES = ("final_norm_gamma", "final_norm_beta", "embed_weight")


def losses(jax, cfg, p, tokens, label):
    """(the loss that is differentiated, mean cross-entropy of all rows) as
    one function of the parameters: what ``value_and_grads`` differentiates
    a layer at a time."""
    return head_losses(jax, cfg, stream(cfg, p, tokens),
                       {n: p[n] for n in HEAD_LEAVES}, label)


def _add(a, b):
    import jax

    return b if a is None else jax.tree.map(lambda x, y: x + y, a, b)


def value_and_grads(jax, cfg, params, tokens, label):
    """(mean cross-entropy, {name: d(loss)/d(parameter)}): the chain rule
    over :func:`losses` written out a layer at a time, each layer's forward
    and each layer's vector-Jacobian product a jitted call of its own. A
    layer's function takes the stream, its weights, the memory and the
    shared keys and values (None where nothing made them yet), and gives
    the stream and what it exports; in reverse, the cotangents a reader
    returns for ``memory`` and ``shared`` are added up and handed, with the
    stream's, to the layer that exported them. The CPU test holds it to
    autodiff of the whole."""
    params, tokens, label = _on_host(jax, dict(params), tokens, label)
    kinds = layers_of(cfg)

    def run(i):
        kind, layer_id = kinds[i]
        return lambda h, w, memory, shared: layer(
            cfg, kind, layer_id, h, w, memory, shared)

    with jax.default_matmul_precision("highest"):
        h = jax.jit(embed)(params["embed_weight"], tokens)
        memory = shared = None
        inputs = []
        for i, (kind, _) in enumerate(kinds):
            inputs.append((h, memory, shared))
            h, exports = jax.jit(run(i))(h, layer_weights(params, i), memory,
                                         shared)
            memory, shared = carry(kind, exports, memory, shared)
        head = {n: params[n] for n in HEAD_LEAVES}
        (_, ce), (g, grads) = jax.jit(jax.value_and_grad(
            lambda x, w, l: head_losses(jax, cfg, x, w, l), argnums=(0, 1),
            has_aux=True))(h, head, label)
        g_memory = g_shared = None
        for i in reversed(range(len(kinds))):
            kind = kinds[i][0]
            h_in, memory, shared = inputs[i]
            weights = layer_weights(params, i)

            def back(h, w, memory, shared, g, g_exports, i=i):
                out, vjp = jax.vjp(run(i), h, w, memory, shared)
                return vjp((g, g_exports if g_exports is not None
                            else jax.tree.map(lambda z: 0.0 * z, out[1])))

            g_exports = g_memory if kind == "mamba_memory" else \
                g_shared if kind == "full_shared" else ()
            g, dw, dm, ds = jax.jit(back)(h_in, weights, memory, shared, g,
                                          g_exports)
            if kind == "gmu":
                g_memory = _add(g_memory, dm)
            if kind == "cross":
                g_shared = _add(g_shared, ds)
            grads.update({f"l{i}_{n}": a for n, a in dw.items()})
        grads["embed_weight"] = grads["embed_weight"] + jax.jit(
            lambda e, t, g: jax.vjp(lambda e: embed(e, t), e)[1](g)[0])(
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
    # the whole model as one program, compiled once (small sizes: the tests)
    step = jax.jit(jax.value_and_grad(
        lambda p, tokens, label: losses(jax, cfg, p, tokens, label),
        has_aux=True))
    for t, (tokens, label) in enumerate(batches, 1):
        with jax.default_matmul_precision("highest"):
            (_, ce), grads = step(params, tokens, label)
        seen.append(float(ce))
        lr_t = lr * math.sqrt(1.0 - beta2 ** t) / (1.0 - beta1 ** t)
        for n, g in grads.items():
            g = g * grad_scale
            mean[n] = beta1 * mean[n] + (1.0 - beta1) * g
            var[n] = beta2 * var[n] + (1.0 - beta2) * g * g
            params[n] = params[n] - lr_t * mean[n] / (jnp.sqrt(var[n]) + eps)
    return seen
