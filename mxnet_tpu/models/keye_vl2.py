"""The language model of Keye-VL-2.0 (Kwai-Keye's ``config.json``,
``model_type`` KeyeVL2): the Qwen3-MoE decoder (``modeling_qwen3_moe.py``:
pre-norm blocks, attention over grouped key/value heads with a per-head RMS
norm of queries and keys and rotary positions over the whole head, then a
drop-free mixture routed by a softmax whose top-k weights are renormalised)
whose attention is sparse by a learned selection (``sa_config``;
DeepSeek-V3.2-Exp's lightning indexer, its report and the ``Indexer`` of its
``inference/model.py``): a small second attention of ``index_heads`` ReLU
heads over ONE key head scores every earlier token, each query keeps its
``index_top_k`` best and the main heads run over those alone
(``RingAttention(select_top_k=)``). The indexer reads the layer's input with
its gradient blocked and learns from a term of its own, the KL divergence of
its softmax over the kept keys from the main heads' mean probabilities, which
``RingAttention`` attaches in backward (``index_loss_coef``): the language
model's parameters see the cross-entropy and the router's balance term alone,
the indexer's see the KL term alone.

No vision tower: rows are text, on which the three position streams of
M-RoPE are equal and the rotation is the plain one. The serving code's
Hadamard rotation and FP8 scores of the indexer are a quantisation and are
left out. Defaults are Keye-VL-2.0-30B-A3B's published sizes."""

from .. import symbol as sym
from .olmoe import (embed_tokens, linear, merge_heads, next_token_head,
                    split_heads)


def qwen3_moe_block(x, pre, attention, *, hidden_size, num_heads,
                    num_kv_heads, head_dim, num_experts, expert_width, top_k,
                    route_norm, num_local_experts, expert_offset,
                    rms_norm_eps, rotary, lb_coef):
    """One pre-norm block of the Qwen3-MoE decoder on the stream ``x`` (B,
    T, hidden), its nodes named ``pre`` + ...: q, k, v bias-free over
    grouped key/value heads, a per-head RMS norm of queries and keys (one
    gain of ``head_dim`` each), rotate-half rotary positions over the whole
    head by THIS layer's schedule (``rotary``: the keywords of
    ``RotaryEmbedding``, ``dict(base=theta)`` where every layer turns
    alike), ``attention(q, k, v, u)`` (``u`` the block's normed input) for
    the heads' output (B, heads, T, head_dim), then the mixture. What
    Keye-VL-2.0, SDAR and Mellum share; they differ in ``attention``, and
    Mellum's layers in ``rotary`` by their kind."""

    def norm(z, name):
        return sym.RMSNorm(z, eps=rms_norm_eps, name=name)

    def heads(z, count, name):
        return sym.RotaryEmbedding(
            split_heads(z, count, head_dim, lambda y: norm(y, name)),
            **rotary)

    u = norm(x, pre + "input_norm")
    q = heads(linear(u, num_heads * head_dim, pre + "q"), num_heads,
              pre + "q_norm")
    k = heads(linear(u, num_kv_heads * head_dim, pre + "k"), num_kv_heads,
              pre + "k_norm")
    v = split_heads(linear(u, num_kv_heads * head_dim, pre + "v"),
                    num_kv_heads, head_dim)
    x = x + linear(merge_heads(attention(q, k, v, u)), hidden_size,
                   pre + "o")
    return x + sym.MoE(
        norm(x, pre + "post_norm"), num_experts=num_experts,
        num_hidden=expert_width, top_k=top_k, route_norm=route_norm,
        lb_coef=lb_coef, num_local_experts=num_local_experts,
        expert_offset=expert_offset, name=pre + "moe")


def keye_vl2_sym_gen(vocab_size=151936, hidden_size=2048, num_layers=48,
                     num_heads=32, num_kv_heads=4, head_dim=128,
                     num_experts=128, expert_width=768, top_k=8,
                     route_norm=True, num_local_experts=0, expert_offset=0, index_heads=16,
                     index_head_dim=64, index_top_k=2048,
                     index_loss_coef=1.0, index_norm_eps=1e-6,
                     rms_norm_eps=1e-6, rope_theta=1e7, lb_coef=0.001,
                     dtype="float32", ignore_label=0):
    """Return a ``sym_gen(seq_len)`` for ``BucketingModule``: token ids
    ``data`` (B, T) and next-token ids ``softmax_label`` (B, T) in, the
    rows' probabilities (B*T, vocab) out. ``num_local_experts`` of the
    ``num_experts`` the router scores live here, from ``expert_offset``
    (0: all of them): one chip's share under expert parallelism, whose
    ``vocab_size`` is its slice. ``index_top_k`` 0 is the dense model (no
    indexer in the graph). ``dtype`` is the trunk's; parameters stay
    float32."""
    block = dict(
        hidden_size=hidden_size, num_heads=num_heads,
        num_kv_heads=num_kv_heads, head_dim=head_dim,
        num_experts=num_experts, expert_width=expert_width, top_k=top_k,
        route_norm=route_norm, num_local_experts=num_local_experts,
        expert_offset=expert_offset, rms_norm_eps=rms_norm_eps,
        rotary=dict(base=rope_theta), lb_coef=lb_coef)

    def rotary(x):
        return sym.RotaryEmbedding(x, base=rope_theta)

    def indexer(u, pre):
        """(index_query (B, J, T, Di), index_key (B, 1, T, Di), index_weight
        (B, J, T)) from the layer's normed input, whose gradient stops
        here."""
        u = sym.BlockGrad(u)
        iq = rotary(split_heads(
            linear(u, index_heads * index_head_dim, pre + "index_q"),
            index_heads, index_head_dim))
        ik = sym.LayerNorm(linear(u, index_head_dim, pre + "index_k"),
                           eps=index_norm_eps, name=pre + "index_k_norm")
        ik = rotary(sym.expand_dims(ik, axis=1))
        iw = sym.transpose(linear(u, index_heads, pre + "index_w"),
                           axes=(0, 2, 1))
        return iq, ik, iw * (index_heads * index_head_dim) ** -0.5

    def attention(pre):
        return lambda q, k, v, u: sym.RingAttention(
            q, k, v, *(indexer(u, pre) if index_top_k else ()), causal=True,
            select_top_k=index_top_k, index_loss_coef=index_loss_coef,
            name=pre + "attn")

    def sym_gen(seq_len):
        data = sym.Variable("data")
        label = sym.Variable("softmax_label")
        x = embed_tokens(data, vocab_size, hidden_size, dtype)
        for i in range(num_layers):
            x = qwen3_moe_block(x, f"l{i}_", attention(f"l{i}_"), **block)
        pred = next_token_head(
            sym.RMSNorm(x, eps=rms_norm_eps, name="final_norm"), label,
            vocab_size, hidden_size, dtype, ignore_label)
        return pred, ("data",), ("softmax_label",)

    return sym_gen
