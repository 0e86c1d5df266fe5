"""DeepSeek-V3's decoder layer (``model_type: deepseek_v3``; its
``config.json`` keys and the family's public ``modeling_deepseek_v3.py``):
pre-norm blocks of multi-head latent attention and, after
``first_k_dense_replace`` dense SwiGLU layers, shared experts beside a
drop-free mixture routed by sigmoid scores, a selection bias, renormalised
and scaled weights.

Latent attention: keys and values come up from one ``kv_lora_rank``-wide
normed latent a token; a head's query and key are ``qk_nope_head_dim`` dims
without positions beside ``qk_rope_head_dim`` rotated ones, and the rotated
key is ONE head (the last ``qk_rope_head_dim`` outputs of ``kv_a``) that all
the heads share; values are ``v_head_dim`` wide, narrower than the keys.
Defaults are kanana-2-30b-a3b-instruct-2601's published sizes (no query
latent: ``q_lora_rank`` null).

``latent_attention``, ``sparse_block`` and ``swiglu`` are the family's
blocks as functions: ``kimi_linear.py`` builds its full-attention layers
(the same latent block with NO positions: ``rotate=None``) and its expert
layers from them."""

from .. import symbol as sym
from .olmoe import (embed_tokens, linear, merge_heads, next_token_head,
                    split_heads)


def swiglu(u, width, hidden_size, name):
    """``down(silu(gate u) * up u)``, ``width`` wide."""
    hidden = sym.Activation(linear(u, width, name + "_gate"),
                            act_type="silu") * linear(u, width, name + "_up")
    return linear(hidden, hidden_size, name + "_down")


def latent_attention(u, pre, norm, hidden_size, num_heads, qk_nope_head_dim,
                     qk_rope_head_dim, v_head_dim, kv_lora_rank, rotate=None):
    """The latent mixer on the normed stream ``u`` (B, T, hidden): queries
    straight from ``u`` (no query latent), every head's ``[k_nope | v]`` up
    from the normed ``kv_lora_rank`` latent, and the ONE
    ``qk_rope_head_dim``-wide key of ``kv_a`` broadcast into every head's
    key (its gradient comes back summed over the heads). ``rotate`` turns
    those dims of the queries and of the one key by their positions; None:
    they are plain dims, a model without positions (``mla_use_nope``).
    ``norm(x, name)`` is the builder's RMS norm."""
    qk_head_dim = qk_nope_head_dim + qk_rope_head_dim

    def dims(x, begin, end):
        return sym.slice_axis(x, axis=-1, begin=begin, end=end)

    q = split_heads(linear(u, num_heads * qk_head_dim, pre + "q"),
                    num_heads, qk_head_dim)
    if rotate:
        q = sym.Concat(dims(q, 0, qk_nope_head_dim),
                       rotate(dims(q, qk_nope_head_dim, qk_head_dim)), dim=3)
    # [latent | the one rotated key]; [k_nope | v] of every head
    kv_a = linear(u, kv_lora_rank + qk_rope_head_dim, pre + "kv_a")
    kv = split_heads(
        linear(norm(dims(kv_a, 0, kv_lora_rank), pre + "kv_a_norm"),
               num_heads * (qk_nope_head_dim + v_head_dim), pre + "kv_b"),
        num_heads, qk_nope_head_dim + v_head_dim)
    k_rope = sym.expand_dims(
        dims(kv_a, kv_lora_rank, kv_lora_rank + qk_rope_head_dim), axis=1)
    if rotate:
        k_rope = rotate(k_rope)
    k = sym.Concat(dims(kv, 0, qk_nope_head_dim),
                   sym.broadcast_axis(k_rope, axis=1, size=num_heads), dim=3)
    v = dims(kv, qk_nope_head_dim, qk_nope_head_dim + v_head_dim)
    a = sym.RingAttention(q, k, v, causal=True, name=pre + "attn")
    return linear(merge_heads(a), hidden_size, pre + "o")


def sparse_block(u, pre, hidden_size, num_experts, expert_width, top_k,
                 num_shared_experts, route_norm, route_scale,
                 num_local_experts, expert_offset):
    """Shared experts beside a drop-free mixture routed by sigmoid scores
    plus a selection bias, the chosen scores renormalised (``route_norm``)
    and scaled; the shared experts are one SwiGLU of their summed width."""
    m = sym.MoE(
        u, num_experts=num_experts, num_hidden=expert_width, top_k=top_k,
        score_func="sigmoid", route_norm=route_norm, route_scale=route_scale,
        expert_bias=True, num_local_experts=num_local_experts,
        expert_offset=expert_offset, name=pre + "moe")
    if num_shared_experts:
        m = m + swiglu(u, expert_width * num_shared_experts, hidden_size,
                       pre + "shared")
    return m


def deepseek_v3_sym_gen(vocab_size=128256, hidden_size=2048, num_layers=48,
                        first_k_dense_replace=1, num_heads=32,
                        qk_nope_head_dim=128, qk_rope_head_dim=64,
                        v_head_dim=128, kv_lora_rank=512, dense_width=6144,
                        num_experts=128, expert_width=768, top_k=6,
                        num_shared_experts=2, route_norm=True,
                        route_scale=2.448, num_local_experts=0,
                        expert_offset=0, rms_norm_eps=1e-6,
                        rope_theta=1000000.0, rope_interleave=True,
                        dtype="float32", ignore_label=0):
    """Return a ``sym_gen(seq_len)`` for ``BucketingModule``: token ids
    ``data`` (B, T) and next-token ids ``softmax_label`` (B, T) in, the
    rows' probabilities (B*T, vocab) out; ``num_layers`` layers, the first
    ``first_k_dense_replace`` dense. ``num_local_experts`` of the
    ``num_experts`` the router scores live here, from ``expert_offset``
    (0: all of them): one chip's share under expert parallelism, whose
    ``vocab_size`` is its slice. ``l<i>_moe_expert_bias``
    (``e_score_correction_bias``) steers the router's choice and has no
    gradient; moving it toward balance is the training loop's and is not
    done here. ``dtype`` is the trunk's; parameters stay float32."""
    def norm(x, name):
        return sym.RMSNorm(x, eps=rms_norm_eps, name=name)

    def rotate(x):
        return sym.RotaryEmbedding(x, base=rope_theta,
                                   interleaved=rope_interleave)

    def sym_gen(seq_len):
        data = sym.Variable("data")
        label = sym.Variable("softmax_label")
        x = embed_tokens(data, vocab_size, hidden_size, dtype)
        for i in range(num_layers):
            pre = f"l{i}_"
            x = x + latent_attention(
                norm(x, pre + "input_norm"), pre, norm, hidden_size,
                num_heads, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                kv_lora_rank, rotate)
            u = norm(x, pre + "post_attn_norm")
            if i < first_k_dense_replace:
                m = swiglu(u, dense_width, hidden_size, pre + "mlp")
            else:
                m = sparse_block(u, pre, hidden_size, num_experts,
                                 expert_width, top_k, num_shared_experts,
                                 route_norm, route_scale, num_local_experts,
                                 expert_offset)
            x = x + m
        pred = next_token_head(norm(x, "final_norm"), label, vocab_size,
                               hidden_size, dtype, ignore_label)
        return pred, ("data",), ("softmax_label",)

    return sym_gen
