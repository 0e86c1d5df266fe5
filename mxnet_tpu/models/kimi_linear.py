"""Kimi Linear (``model_type: kimi_linear``; the ``config.json`` keys of
Kimi-Linear-48B-A3B, the family's public ``modeling_kimi.py`` and "Kimi
Linear: An Expressive, Efficient Attention Architecture", Moonshot AI 2025,
arXiv:2510.26692): a decoder of pre-norm blocks whose mixer is Kimi Delta
Attention (KDA) on the layers ``kda_layers`` names and multi-head latent
attention WITHOUT positions on those ``full_attn_layers`` names (published
numbering, from 1: three KDA layers, then a full one), over one leading dense
SwiGLU and then DeepSeek-V3's sparse block (sigmoid scores, a selection
bias, renormalised and scaled weights, one shared expert).

KDA: a causal depthwise convolution and SiLU over the [q | k | v]
projections, length-normalised queries and keys, and the gated delta rule
whose state fades by a gate a KEY CHANNEL of a head and token (``g`` of (B,
H, T, D) into ``GatedDeltaRule``): ``g = -exp(A_log) softplus(W_fb W_fa u +
dt_bias)``, a low-rank product, one ``A_log`` a head, one ``dt_bias`` a
channel; a sigmoid write strength a head; then a per-head RMS norm gated by
``sigmoid`` of a second low-rank product. The latent block and the sparse
block are ``deepseek_v3.py``'s own functions. Defaults are
Kimi-Linear-48B-A3B's published sizes."""

from .. import symbol as sym
from .deepseek_v3 import latent_attention, sparse_block, swiglu
from .olmoe import embed_tokens, linear, next_token_head, split_heads
from .qwen3_next import log_spaced_parameter

_KDA_LAYERS = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22,
               23, 25, 26)


def kimi_linear_sym_gen(vocab_size=163840, hidden_size=2304, num_layers=27,
                        kda_layers=_KDA_LAYERS,
                        full_attn_layers=(4, 8, 12, 16, 20, 24, 27),
                        first_k_dense_replace=1, linear_heads=32,
                        linear_head_dim=128, conv_kernel=4, num_heads=32,
                        qk_nope_head_dim=128, qk_rope_head_dim=64,
                        v_head_dim=128, kv_lora_rank=512, dense_width=9216,
                        num_experts=256, expert_width=1024, top_k=8,
                        num_shared_experts=1, route_norm=True,
                        route_scale=2.446, num_local_experts=0,
                        expert_offset=0, rms_norm_eps=1e-5, dtype="float32",
                        ignore_label=0):
    """Return a ``sym_gen(seq_len)`` for ``BucketingModule``: token ids
    ``data`` (B, T) and next-token ids ``softmax_label`` (B, T) in, the
    rows' probabilities (B*T, vocab) out. Layer i (from 0) is published
    layer i + 1: latent attention where ``full_attn_layers`` names it, KDA
    where ``kda_layers`` does (every layer is in exactly one), dense while
    ``i < first_k_dense_replace``. ``num_local_experts`` of the
    ``num_experts`` the router scores live here, from ``expert_offset`` (0:
    all of them): one chip's share under expert parallelism, whose
    ``vocab_size`` is its slice. The three published convolutions
    (``q_conv1d``, ``k_conv1d``, ``v_conv1d``) are one depthwise
    ``CausalConv1D`` over the packed row [q | k | v], whose rows ``qkv`` is
    one projection; the low-rank gates' rank is ``linear_head_dim``, as
    published. ``A_log`` is (H, 1, 1), ``dt_bias`` (H, 1, D): the published
    (H,) and (H x D,). ``dtype`` is the trunk's; parameters stay float32,
    and so do the gate, its decays and the write strength."""
    kinds = {}
    for published in range(1, num_layers + 1):
        full, kda = published in full_attn_layers, published in kda_layers
        if full == kda:
            raise ValueError(
                f"kimi_linear: published layer {published} is in "
                f"{'both' if full else 'neither'} of kda_layers and "
                "full_attn_layers")
        kinds[published - 1] = "full" if full else "kda"
    width = linear_heads * linear_head_dim

    def norm(x, name):
        return sym.RMSNorm(x, eps=rms_norm_eps, name=name)

    def part(x, first, end):
        return sym.slice_axis(x, axis=-1, begin=first, end=end)

    def heads(x):
        return split_heads(x, linear_heads, linear_head_dim)

    def low_rank(u, name):
        return linear(linear(u, linear_head_dim, name + "_a"), width,
                      name + "_b")

    def kda(u, pre):
        qkv = sym.CausalConv1D(linear(u, 3 * width, pre + "qkv"),
                               kernel=conv_kernel, name=pre + "conv")
        q, k, v = (heads(part(qkv, n * width, (n + 1) * width))
                   for n in range(3))
        # g = -exp(A_log) * softplus(a + dt_bias), one a key channel
        a = heads(sym.Cast(low_rank(u, pre + "f"), dtype="float32"))
        a_log = log_spaced_parameter(pre + "A_log", (linear_heads, 1, 1), 0,
                                     1.0, 16.0)
        dt_bias = log_spaced_parameter(
            pre + "dt_bias", (linear_heads, 1, linear_head_dim), 2, 0.001,
            0.1)
        g = sym.broadcast_mul(
            sym.negative(sym.exp(a_log)),
            sym.Activation(sym.broadcast_add(a, dt_bias),
                           act_type="softrelu"))
        beta = sym.Activation(
            sym.transpose(sym.Cast(linear(u, linear_heads, pre + "b"),
                                   dtype="float32"), axes=(0, 2, 1)),
            act_type="sigmoid")
        o = sym.GatedDeltaRule(q, k, v, g, beta, name=pre + "delta")
        # the gated norm: over the width of each head, one gain
        o = norm(sym.transpose(o, axes=(0, 2, 1, 3)), pre + "out_norm")
        z = sym.Reshape(low_rank(u, pre + "g"),
                        shape=(0, 0, linear_heads, linear_head_dim))
        y = o * sym.Activation(z, act_type="sigmoid")
        return linear(sym.Reshape(y, shape=(0, 0, -1)), hidden_size,
                      pre + "o")

    def sym_gen(seq_len):
        data = sym.Variable("data")
        label = sym.Variable("softmax_label")
        x = embed_tokens(data, vocab_size, hidden_size, dtype)
        for i in range(num_layers):
            pre = f"l{i}_"
            u = norm(x, pre + "input_norm")
            if kinds[i] == "full":
                x = x + latent_attention(
                    u, pre, norm, hidden_size, num_heads, qk_nope_head_dim,
                    qk_rope_head_dim, v_head_dim, kv_lora_rank, rotate=None)
            else:
                x = x + kda(u, pre)
            u = norm(x, pre + "post_attn_norm")
            if i < first_k_dense_replace:
                x = x + swiglu(u, dense_width, hidden_size, pre + "mlp")
            else:
                x = x + sparse_block(
                    u, pre, hidden_size, num_experts, expert_width, top_k,
                    num_shared_experts, route_norm, route_scale,
                    num_local_experts, expert_offset)
        pred = next_token_head(norm(x, "final_norm"), label, vocab_size,
                               hidden_size, dtype, ignore_label)
        return pred, ("data",), ("softmax_label",)

    return sym_gen
