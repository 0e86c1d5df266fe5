"""Phi-4-mini-flash (Microsoft's ``config.json``, ``model_type`` phi4flash;
Ren et al. 2025, *Decoder-Hybrid-Decoder Architecture for Efficient Reasoning
with Long Generation*, arXiv:2507.06607: SambaY with differential attention):
a decoder of pre-norm blocks, ``x <- x + mixer(LayerNorm(x))`` then ``x <- x +
MLP(LayerNorm(x))`` (a SwiGLU whose one ``fc1`` makes gate and up), no
position encoding, tied embedding, whose mixers are of six kinds:

* ``mamba``: a Mamba-1 mixer: ``in_proj`` to channels and a gate, a causal
  depthwise convolution with SiLU, ``x_proj`` to the step's low-rank input
  and the token's ``B`` and ``C``, ``dt_proj``, ``SelectiveScan``, the gate
  ``y * silu(z)``, ``out_proj``. ``mamba_memory`` is the same and EXPORTS
  its scan's output before the gate, the memory ``M``.
* ``window``: differential attention (Ye et al. 2024, arXiv:2410.05258) over
  a band of ``sliding_window`` keys: heads of ``head_dim`` pair up
  (consecutive heads 2n, 2n + 1) into queries and keys ``(q1, q2)``, ``(k1,
  k2)`` and values ``[v1 | v2]`` twice as wide; two softmaxes ``a_i =
  softmax(q_i k_i^T / sqrt(head_dim)) v`` (two ``RingAttention`` nodes over
  the SAME values), ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init``,
  ``RMSNorm(a_1 - lam a_2) x (1 - lam_init)``, ``out_proj``. ``lam_init =
  0.8 - 0.6 exp(-0.3 l)`` with ``l`` the layer's PUBLISHED index
  (``layer_ids``). ``full_shared`` is the same over every key before the
  query and EXPORTS its keys and values.
* ``gmu``: a Gated Memory Unit, ``(silu(W_1 u) * M) W_2``:
  reads the stream and ``mamba_memory``'s ``M``, which may lie many layers
  below.
* ``cross``: differential attention whose layer projects a QUERY only and
  reads ``full_shared``'s keys and values.

So a graph of this model has tensors with readers several layers apart: the
executor sums their cotangents, and under per-operator recomputation a
shared tensor is one node's output, kept or made again once.

``layer_kinds`` says which layers are built, in order (``PUBLISHED_KINDS``:
the 32 of Phi-4-mini-flash-reasoning, by the family's rule ``use_mamba = l %
mb_per_layer == 0``, the cross-decoder from ``l = num_layers / 2``); any cut
of it that holds a ``mamba_memory`` before its first ``gmu`` and a
``full_shared`` before its first ``cross`` is a model. Defaults are the
published sizes; the Mamba sizes are the configuration class's defaults
(``config.json`` overrides none)."""

import math

from .. import symbol as sym
from .olmoe import (embed_tokens, linear, merge_heads, next_token_head)
from .recipe import low_precision_io

KINDS = ("mamba", "window", "mamba_memory", "full_shared", "gmu", "cross")


def published_kinds(num_layers=32, mb_per_layer=2):
    """The family's rule: every ``mb_per_layer``-th layer from 0 a scan (a
    GMU in the cross-decoder, which starts at ``num_layers / 2`` with the
    scan whose memory they read); of the others the self-decoder's read a
    window, the cross-decoder's first is the one full attention and the rest
    read its keys and values."""
    half = num_layers // 2
    kinds = []
    for l in range(num_layers):
        if l % mb_per_layer == 0:
            kinds.append("mamba" if l < half else
                         "mamba_memory" if l == half else "gmu")
        else:
            kinds.append("window" if l < half else
                         "full_shared" if l == half + 1 else "cross")
    return tuple(kinds)


PUBLISHED_KINDS = published_kinds()


def lam_init(layer_id):
    """Differential attention's ``lambda_init`` of a layer, by its published
    index."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer_id)


def phi4flash_sym_gen(vocab_size=200064, hidden_size=2560,
                      layer_kinds=PUBLISHED_KINDS, layer_ids=None,
                      num_heads=40, num_kv_heads=20, intermediate_size=10240,
                      sliding_window=512, mamba_d_state=16, mamba_d_conv=4,
                      mamba_expand=2, mamba_dt_rank=None,
                      layer_norm_eps=1e-5, subln_eps=1e-5,
                      attention_bias=True, tie_word_embeddings=True,
                      dtype="float32", ignore_label=0):
    """Return a ``sym_gen(seq_len)`` for ``BucketingModule``: token ids
    ``data`` (B, T) and next-token ids ``softmax_label`` (B, T) in, the
    rows' probabilities (B*T, vocab) out. One layer a ``layer_kinds`` entry
    (``KINDS``); ``layer_ids`` their published indices (None: 0, 1, ...),
    which ``lam_init`` reads. Parameters of layer i (its place in
    ``layer_kinds``), prefix ``l<i>_``: ``input_norm`` / ``post_norm``
    ``_gamma`` and ``_beta``; ``fc1_weight`` (2 x intermediate, hidden: gate
    rows first), ``fc2_weight``; a scan layer's ``in_proj_weight``,
    ``conv_weight`` (C, taps) and ``conv_bias``, ``x_proj_weight``,
    ``dt_proj_weight``, ``scan_A_log`` (C, N), ``scan_D``, ``scan_dt_bias``
    (``dt_proj``'s bias: ``SelectiveScan`` adds it before its softplus),
    ``out_proj_weight``; an attention layer's ``qkv_weight`` / ``_bias``
    (``q_weight`` / ``_bias`` on a cross layer), ``lambda_q1``, ``lambda_k1``,
    ``lambda_q2``, ``lambda_k2`` (head_dim,), ``subln_gamma`` (2 x head_dim,),
    ``out_proj_weight`` / ``_bias``; a GMU's ``in_proj_weight``,
    ``out_proj_weight``. ``dtype`` is the trunk's; parameters stay float32,
    and so do the scan's steps, decays and state, the two softmaxes, their
    difference and its norm, and the norms' statistics."""
    layer_ids = tuple(range(len(layer_kinds))) if layer_ids is None \
        else tuple(layer_ids)
    unknown = set(layer_kinds) - set(KINDS)
    if unknown or len(layer_ids) != len(layer_kinds):
        raise ValueError(f"phi4flash: layer kinds {sorted(unknown)} are none "
                         f"of {KINDS}, or {len(layer_ids)} ids for "
                         f"{len(layer_kinds)} layers")
    head_dim = hidden_size // num_heads
    pairs, kv_pairs = num_heads // 2, num_kv_heads // 2
    d_inner = mamba_expand * hidden_size
    dt_rank = mamba_dt_rank or -(-hidden_size // 16)

    def norm(x, name):
        return sym.LayerNorm(x, eps=layer_norm_eps, name=name)

    def part(x, first, end):
        return sym.slice_axis(x, axis=-1, begin=first, end=end)

    def vector(name, *shape):
        return sym.Variable(name, shape=shape, dtype="float32")

    def biased(x, width, name):
        if not attention_bias:
            return linear(x, width, name)
        return sym.FullyConnected(x, num_hidden=width, flatten=False,
                                  name=name)

    def silu(x):
        return sym.Activation(x, act_type="silu")

    def mlp(u, pre):
        both = linear(u, 2 * intermediate_size, pre + "fc1")
        gate = part(both, 0, intermediate_size)
        up = part(both, intermediate_size, 2 * intermediate_size)
        return linear(up * silu(gate), hidden_size, pre + "fc2")

    def mamba(u, pre):
        """(the mixer's output, the scan's output before the gate)."""
        both = linear(u, 2 * d_inner, pre + "in_proj")
        xs = sym.CausalConv1D(part(both, 0, d_inner), kernel=mamba_d_conv,
                              act_type="silu", no_bias=False,
                              name=pre + "conv")
        z = part(both, d_inner, 2 * d_inner)
        low = linear(xs, dt_rank + 2 * mamba_d_state, pre + "x_proj")
        y = sym.SelectiveScan(
            xs, linear(part(low, 0, dt_rank), d_inner, pre + "dt_proj"),
            vector(pre + "scan_A_log", d_inner, mamba_d_state),
            part(low, dt_rank, dt_rank + mamba_d_state),
            part(low, dt_rank + mamba_d_state, dt_rank + 2 * mamba_d_state),
            name=pre + "scan")
        return linear(y * silu(z), hidden_size, pre + "out_proj"), y

    def paired(x, count, which=None):
        """(B, T, 2 count head_dim) -> (B, count, T, .): the ``which``-th
        (0, 1) head of each consecutive pair, head_dim wide, or (None) both
        side by side, 2 head_dim wide."""
        if which is None:
            x = sym.Reshape(x, shape=(0, 0, count, 2 * head_dim))
        else:
            x = sym.Reshape(sym.slice_axis(
                sym.Reshape(x, shape=(0, 0, count, 2, head_dim)), axis=3,
                begin=which, end=which + 1), shape=(0, 0, count, head_dim))
        return sym.transpose(x, axes=(0, 2, 1, 3))

    def keys_and_values(qkv):
        q_width, k_width = num_heads * head_dim, num_kv_heads * head_dim
        k = part(qkv, q_width, q_width + k_width)
        v = part(qkv, q_width + k_width, q_width + 2 * k_width)
        return (paired(k, kv_pairs, 0), paired(k, kv_pairs, 1),
                paired(v, kv_pairs))

    def differential(q, kv, pre, layer_id, window):
        """``q`` (B, T, heads x head_dim) over ``kv`` = (k1, k2, v)."""
        k1, k2, v = kv
        a1, a2 = [sym.RingAttention(
            paired(q, pairs, i), k, v, causal=True, window=window,
            name=f"{pre}attn{i + 1}") for i, k in enumerate((k1, k2))]
        lam = [sym.exp(sym.sum(
            vector(f"{pre}lambda_q{i}", head_dim)
            * vector(f"{pre}lambda_k{i}", head_dim), axis=0, keepdims=True))
            for i in (1, 2)]
        init = lam_init(layer_id)
        # float32 from here: the parameter promotes the trunk's rows
        diff = low_precision_io(a1, dtype, out=True) - sym.broadcast_mul(
            a2, lam[0] - lam[1] + init)
        o = sym.RMSNorm(diff, eps=subln_eps, name=pre + "subln") \
            * (1.0 - init)
        return biased(merge_heads(low_precision_io(o, dtype)), hidden_size,
                      pre + "out_proj")

    def sym_gen(seq_len):
        data = sym.Variable("data")
        label = sym.Variable("softmax_label")
        embed = sym.Variable("embed_weight") if tie_word_embeddings else None
        x = embed_tokens(data, vocab_size, hidden_size, dtype, weight=embed)
        memory = shared = None
        for i, (kind, layer_id) in enumerate(zip(layer_kinds, layer_ids)):
            pre = f"l{i}_"
            u = norm(x, pre + "input_norm")
            if kind in ("mamba", "mamba_memory"):
                out, y = mamba(u, pre)
                if kind == "mamba_memory":
                    memory = y
            elif kind in ("window", "full_shared"):
                width = (num_heads + 2 * num_kv_heads) * head_dim
                qkv = biased(u, width, pre + "qkv")
                kv = keys_and_values(qkv)
                out = differential(
                    part(qkv, 0, num_heads * head_dim), kv, pre, layer_id,
                    (sliding_window or 0) if kind == "window" else 0)
                if kind == "full_shared":
                    shared = kv
            elif kind == "gmu":
                if memory is None:
                    raise ValueError("phi4flash: a gmu layer before any "
                                     "mamba_memory layer")
                out = linear(silu(linear(u, d_inner, pre + "in_proj"))
                             * memory, hidden_size, pre + "out_proj")
            else:
                if shared is None:
                    raise ValueError("phi4flash: a cross layer before any "
                                     "full_shared layer")
                out = differential(
                    biased(u, num_heads * head_dim, pre + "q"), shared, pre,
                    layer_id, 0)
            x = x + out
            x = x + mlp(norm(x, pre + "post_norm"), pre)
        pred = next_token_head(norm(x, "final_norm"), label, vocab_size,
                               hidden_size, dtype, ignore_label, weight=embed)
        return pred, ("data",), ("softmax_label",)

    return sym_gen
