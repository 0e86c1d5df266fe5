"""ZAYA1 (Zyphra; ``config.json`` of ZAYA1-8B, ``model_type: zaya``; the
mixer from "Compressed Convolutional Attention", arXiv:2510.04476, the
router and the residual scaling from the ZAYA1 technical report,
arXiv:2511.17127): a decoder whose every layer is a mixer sub-block and a
mixture sub-block, each pre-norm with learned residual scaling, ``x <- (s_r
x + b_r) + (s_o f(RMSNorm(x)) + b_o)``.

The mixer is compressed convolutional attention: queries and keys are
projected into a latent of ``num_heads + num_kv_heads`` heads, packed and
mixed over time by two causal convolutions (a depthwise one of
``cca_time0`` taps, then one of ``cca_time1`` taps that mixes the channels
inside each head), and the mean of each pre-convolution query and its key
is added back; the values are two half-width projections, the second of the
token before; queries and keys are scaled to length ``sqrt(head_dim)``, the
keys times a learned temperature a key/value head, rotated on the first
``partial_rotary_factor`` of each head, and go through causal softmax
attention over grouped key/value heads.

The mixture is top-1 of ``num_experts`` SiLU-gated experts, weighted by the
router's softmax probability (not renormalised). The router is a graph of
its own: a down-projection to ``router_hidden_size``, plus the layer
above's router state times a learned vector (exponential depth averaging:
the state is threaded down the stack), an RMS norm and a three-product GeLU
MLP, all in float32; ``MoE(router="graph")`` takes its logits. The
embedding and the head are one variable (``tie_word_embeddings``).
Defaults are ZAYA1-8B's published sizes."""

from .. import symbol as sym
from .olmoe import (embed_tokens, linear, merge_heads, next_token_head,
                    split_heads)
from .recipe import low_precision_io


def zaya_sym_gen(vocab_size=262272, hidden_size=2048, num_layers=40,
                 num_heads=8, num_kv_heads=2, head_dim=128, cca_time0=2,
                 cca_time1=2, partial_rotary_factor=0.5, num_experts=16,
                 expert_width=2048, top_k=1, router_hidden_size=256,
                 num_local_experts=0, expert_offset=0, rms_norm_eps=1e-5,
                 rope_theta=5e6, tie_word_embeddings=True, dtype="float32",
                 ignore_label=0):
    """Return a ``sym_gen(seq_len)`` for ``BucketingModule``: token ids
    ``data`` (B, T) and next-token ids ``softmax_label`` (B, T) in, the
    rows' probabilities (B*T, vocab) out. ``num_local_experts`` of the
    ``num_experts`` the router scores live here, from ``expert_offset`` (0:
    all of them): one chip's share under expert parallelism, whose
    ``vocab_size`` is its slice. Parameters of layer i, prefix ``l<i>_``:
    ``q`` / ``k`` / ``v1`` / ``v2`` / ``o`` ``_weight``; ``conv0_weight``
    (C, taps) and ``conv1_weight`` (heads, d, d, taps) with their
    ``_bias`` (C,), C = (num_heads + num_kv_heads) x head_dim, a row
    ``[queries | keys]``; ``temp_gamma`` (num_kv_heads, 1, 1);
    ``router_down_weight`` / ``_bias``, ``router_carry_gamma`` (layers 1
    and on), ``router_norm_gamma``, ``router_fc1`` / ``router_fc2`` /
    ``router_out`` ``_weight``; the experts ``moe_gate`` / ``moe_up`` /
    ``moe_down`` ``_weight``; and the residual scaling of each sub-block,
    ``attn_`` / ``ffn_`` + ``res_gamma``, ``res_beta``, ``out_gamma``,
    ``out_beta``. ``dtype`` is the trunk's; parameters stay float32, and
    so do the residual scaling, the length norm, temperature and rotation
    of queries and keys, and the whole router."""
    groups = num_heads // num_kv_heads
    q_width, k_width = num_heads * head_dim, num_kv_heads * head_dim
    rotary_dim = int(head_dim * partial_rotary_factor)

    def norm(x, name):
        return sym.RMSNorm(x, eps=rms_norm_eps, name=name)

    def f32(x):
        return low_precision_io(x, dtype, out=True)

    def vector(name, *shape):
        return sym.Variable(name, shape=shape, dtype="float32")

    def part(x, first, end):
        return sym.slice_axis(x, axis=-1, begin=first, end=end)

    def scaled(x, pre):
        # the trunk's rows times a float32 vector: promoted where they are
        # multiplied, so no float32 copy of the stream is kept for backward
        return sym.broadcast_add(
            sym.broadcast_mul(x, vector(pre + "_gamma", hidden_size)),
            vector(pre + "_beta", hidden_size))

    def residual(x, out, pre):
        """``(s_r x + b_r) + (s_o out + b_o)`` in float32, one rounding."""
        return low_precision_io(
            scaled(x, pre + "res") + scaled(out, pre + "out"), dtype)

    def unit_length(x):
        """Each head's vector at length ``sqrt(head_dim)``: divided by the
        root of its mean square."""
        return sym.broadcast_mul(x, sym.rsqrt(
            sym.mean(sym.square(x), axis=-1, keepdims=True)))

    def heads(x, count, temp=None):
        """(B, T, count, d) float32 -> (B, count, T, d) in the trunk's
        dtype: length, temperature, rotation, then the one rounding."""
        x = unit_length(sym.transpose(x, axes=(0, 2, 1, 3)))
        if temp is not None:
            x = sym.broadcast_mul(x, temp)
        return low_precision_io(sym.RotaryEmbedding(
            x, base=rope_theta, rotary_dim=rotary_dim), dtype)

    def shift(x, seq_len):
        """``x_{t-1}`` at t over axis 1, zeros at t = 0."""
        if seq_len == 1:
            return sym.zeros_like(x)
        return sym.Concat(
            sym.zeros_like(sym.slice_axis(x, axis=1, begin=0, end=1)),
            sym.slice_axis(x, axis=1, begin=0, end=seq_len - 1), dim=1)

    def mixer(u, pre, seq_len):
        q0 = linear(u, q_width, pre + "q")
        k0 = linear(u, k_width, pre + "k")
        c = sym.CausalConv1D(
            sym.Concat(q0, k0, dim=2), kernel=cca_time0, act_type="none",
            no_bias=False, name=pre + "conv0")
        c = f32(sym.CausalConv1D(
            c, kernel=cca_time1, act_type="none", no_bias=False,
            num_group=num_heads + num_kv_heads, name=pre + "conv1"))
        # the q-k mean, heads grouped by the key they read
        mean_q = 0.5 * sym.broadcast_add(
            sym.Reshape(f32(q0), shape=(0, 0, num_kv_heads, groups,
                                        head_dim)),
            sym.Reshape(f32(k0), shape=(0, 0, num_kv_heads, 1, head_dim)))
        mean_k = sym.mean(mean_q, axis=3)
        q = sym.Reshape(part(c, 0, q_width),
                        shape=(0, 0, num_heads, head_dim)) \
            + sym.Reshape(mean_q, shape=(0, 0, num_heads, head_dim))
        k = sym.Reshape(part(c, q_width, q_width + k_width),
                        shape=(0, 0, num_kv_heads, head_dim)) + mean_k
        v = sym.Concat(linear(u, k_width // 2, pre + "v1"),
                       shift(linear(u, k_width // 2, pre + "v2"), seq_len),
                       dim=2)
        a = sym.RingAttention(
            heads(q, num_heads),
            heads(k, num_kv_heads,
                  vector(pre + "temp_gamma", num_kv_heads, 1, 1)),
            split_heads(v, num_kv_heads, head_dim), causal=True,
            name=pre + "attn")
        return linear(merge_heads(a), hidden_size, pre + "o")

    def router(u, state, pre):
        """(logits (B, T, E), this layer's router state): float32."""
        r = sym.FullyConnected(f32(u), num_hidden=router_hidden_size,
                               flatten=False, name=pre + "router_down")
        if state is not None:
            r = r + sym.broadcast_mul(
                state, vector(pre + "router_carry_gamma",
                              router_hidden_size))
        z = norm(r, pre + "router_norm")
        for name in ("router_fc1", "router_fc2"):
            z = sym.Activation(linear(z, router_hidden_size, pre + name),
                               act_type="gelu")
        return linear(z, num_experts, pre + "router_out"), r

    def sym_gen(seq_len):
        data = sym.Variable("data")
        label = sym.Variable("softmax_label")
        embed = sym.Variable("embed_weight") if tie_word_embeddings else None
        x = embed_tokens(data, vocab_size, hidden_size, dtype, weight=embed)
        state = None
        for i in range(num_layers):
            pre = f"l{i}_"
            x = residual(x, mixer(norm(x, pre + "input_norm"), pre, seq_len),
                         pre + "attn_")
            u = norm(x, pre + "post_norm")
            logits, state = router(u, state, pre)
            x = residual(x, sym.MoE(
                u, logits, router="graph", num_experts=num_experts,
                num_hidden=expert_width, top_k=top_k,
                num_local_experts=num_local_experts,
                expert_offset=expert_offset, name=pre + "moe"),
                pre + "ffn_")
        pred = next_token_head(norm(x, "final_norm"), label, vocab_size,
                               hidden_size, dtype, ignore_label, weight=embed)
        return pred, ("data",), ("softmax_label",)

    return sym_gen
