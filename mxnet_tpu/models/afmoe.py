"""AFMoE (arcee-ai's Trinity family; ``config.json`` and the public
``modeling_afmoe.py``): a decoder of sandwich-normed blocks. Attention over
grouped key/value heads with a per-head RMS norm of queries and keys, a
period of window layers (rotary positions, a band of ``sliding_window``
keys) and full layers (no positions), and a sigmoid gate on its output;
then a dense SwiGLU in the leading layers and, after them, a shared SwiGLU
expert beside a drop-free mixture routed by sigmoid scores, a selection
bias, renormalised and scaled weights. Defaults are Trinity-Mini's
published sizes."""

import math

from .. import symbol as sym
from .olmoe import (embed_tokens, linear, merge_heads, next_token_head,
                    split_heads)


def afmoe_sym_gen(vocab_size=200192, hidden_size=2048,
                  layer_types=("sliding_attention",) * 3
                  + ("full_attention",), num_dense_layers=2, num_heads=32,
                  num_kv_heads=4, head_dim=128, sliding_window=2048,
                  dense_width=6144, num_experts=128, expert_width=1024,
                  top_k=8, num_shared_experts=1, route_norm=True,
                  route_scale=2.826, num_local_experts=0, expert_offset=0,
                  rms_norm_eps=1e-5, rope_theta=10000.0, embed_scale=True,
                  dtype="float32", ignore_label=0):
    """Return a ``sym_gen(seq_len)`` for ``BucketingModule``: token ids
    ``data`` (B, T) and next-token ids ``softmax_label`` (B, T) in, the
    rows' probabilities (B*T, vocab) out; one layer a ``layer_types``
    entry, the first ``num_dense_layers`` dense. ``num_local_experts`` of
    the ``num_experts`` the router scores live here, from
    ``expert_offset`` (0: all of them): one chip's share under expert
    parallelism, whose ``vocab_size`` is its slice. ``embed_scale``
    multiplies the embedding by sqrt(hidden_size) (``mup_enabled``).
    ``l<i>_moe_expert_bias`` steers the router's choice and has no
    gradient; moving it toward balance is the training loop's
    (``load_balance_coeff``) and is not done here. ``dtype`` is the
    trunk's; parameters stay float32."""

    def norm(x, name):
        return sym.RMSNorm(x, eps=rms_norm_eps, name=name)

    def heads(x, count, name, rotate):
        # the norm runs over the head_dim of each head, one gain for all
        x = sym.Reshape(x, shape=(0, 0, count, head_dim))
        x = sym.transpose(norm(x, name), axes=(0, 2, 1, 3)) if name \
            else split_heads(x, count, head_dim)
        return sym.RotaryEmbedding(x, base=rope_theta) if rotate else x

    def swiglu(u, width, name):
        hidden = sym.Activation(linear(u, width, name + "_gate"),
                                act_type="silu") * linear(u, width,
                                                          name + "_up")
        return linear(hidden, hidden_size, name + "_down")

    def sym_gen(seq_len):
        data = sym.Variable("data")
        label = sym.Variable("softmax_label")
        x = embed_tokens(data, vocab_size, hidden_size, dtype)
        if embed_scale:
            x = x * math.sqrt(hidden_size)
        for i, kind in enumerate(layer_types):
            pre = f"l{i}_"
            window = kind == "sliding_attention"
            u = norm(x, pre + "input_norm")
            q = heads(linear(u, num_heads * head_dim, pre + "q"), num_heads,
                      pre + "q_norm", window)
            k = heads(linear(u, num_kv_heads * head_dim, pre + "k"),
                      num_kv_heads, pre + "k_norm", window)
            v = heads(linear(u, num_kv_heads * head_dim, pre + "v"),
                      num_kv_heads, None, False)
            a = sym.RingAttention(
                q, k, v, causal=True, window=sliding_window if window else 0,
                name=pre + "attn")
            gate = sym.Activation(linear(u, num_heads * head_dim, pre + "g"),
                                  act_type="sigmoid")
            x = x + norm(linear(merge_heads(a) * gate, hidden_size,
                                pre + "o"), pre + "post_attn_norm")
            u = norm(x, pre + "pre_mlp_norm")
            if i < num_dense_layers:
                m = swiglu(u, dense_width, pre + "mlp")
            else:
                m = sym.MoE(
                    u, num_experts=num_experts, num_hidden=expert_width,
                    top_k=top_k, score_func="sigmoid", route_norm=route_norm,
                    route_scale=route_scale, expert_bias=True,
                    num_local_experts=num_local_experts,
                    expert_offset=expert_offset, name=pre + "moe")
                if num_shared_experts:
                    m = m + swiglu(u, expert_width * num_shared_experts,
                                   pre + "shared")
            x = x + norm(m, pre + "post_mlp_norm")
        pred = next_token_head(norm(x, "final_norm"), label, vocab_size,
                               hidden_size, dtype, ignore_label)
        return pred, ("data",), ("softmax_label",)

    return sym_gen
