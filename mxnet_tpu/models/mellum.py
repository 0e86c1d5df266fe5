"""Mellum 2 (JetBrains' ``config.json``, ``model_type`` mellum): the
Qwen3-MoE decoder (``keye_vl2.qwen3_moe_block``: pre-norm blocks, attention
over grouped key/value heads with a per-head RMS norm of queries and keys,
then a drop-free mixture routed by a softmax whose top-k weights are
renormalised) whose layers are of two kinds by ``layer_types``: a window
layer reads a band of ``sliding_window`` keys, a full layer all before it,
and each kind turns its queries and keys by its own entry of
``rope_parameters``: the window layers by the plain geometric frequencies,
the full layers by YaRN's (the slow pairs stretched ``factor`` times from
``original_max_position_embeddings``, cos and sin scaled by
``attention_factor``), which is what lets a full layer read positions past
the length its frequencies were first trained at.

The multi-token-prediction head some descriptions of the family mention has
no key in ``config.json`` and is not built. Defaults are
Mellum2-12B-A2.5B-Instruct's published sizes."""

from .. import symbol as sym
from .keye_vl2 import qwen3_moe_block
from .olmoe import embed_tokens, next_token_head

PUBLISHED_LAYER_TYPES = (("sliding_attention",) * 3
                         + ("full_attention",)) * 7
PUBLISHED_ROPE_PARAMETERS = {
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
}


def rotary_keywords(rope):
    """The keywords of ``RotaryEmbedding`` for one entry of a published
    ``rope_parameters`` (``rope_type``, ``rope_theta`` and, where the type
    is not "default", the schedule's own numbers). A type the operator does
    not define is the operator's to refuse."""
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return dict(base=float(rope["rope_theta"]))
    return dict(
        base=float(rope["rope_theta"]), scaling=kind,
        factor=float(rope["factor"]),
        original_max_position=rope["original_max_position_embeddings"],
        beta_fast=float(rope.get("beta_fast", 32)),
        beta_slow=float(rope.get("beta_slow", 1)),
        attention_factor=float(rope.get("attention_factor") or 0))


def mellum_sym_gen(vocab_size=98304, hidden_size=2304,
                   layer_types=PUBLISHED_LAYER_TYPES, num_heads=32,
                   num_kv_heads=4, head_dim=128, sliding_window=1024,
                   num_experts=64, expert_width=896, top_k=8,
                   route_norm=True, num_local_experts=0, expert_offset=0,
                   rms_norm_eps=1e-6, rope_parameters=None, lb_coef=0.001,
                   dtype="float32", ignore_label=0):
    """Return a ``sym_gen(seq_len)`` for ``BucketingModule``: token ids
    ``data`` (B, T) and next-token ids ``softmax_label`` (B, T) in, the
    rows' probabilities (B*T, vocab) out; one layer a ``layer_types``
    entry, "sliding_attention" or "full_attention", turned by that kind's
    entry of ``rope_parameters`` (None: the published ones).
    ``num_local_experts`` of the ``num_experts`` the router scores live
    here, from ``expert_offset`` (0: all of them): one chip's share under
    expert parallelism, whose ``vocab_size`` is its slice. ``dtype`` is the
    trunk's; parameters stay float32."""
    rope_parameters = rope_parameters or PUBLISHED_ROPE_PARAMETERS
    block = dict(
        hidden_size=hidden_size, num_heads=num_heads,
        num_kv_heads=num_kv_heads, head_dim=head_dim,
        num_experts=num_experts, expert_width=expert_width, top_k=top_k,
        route_norm=route_norm, num_local_experts=num_local_experts,
        expert_offset=expert_offset, rms_norm_eps=rms_norm_eps,
        lb_coef=lb_coef)

    def attention(pre, kind):
        window = (sliding_window or 0) if kind == "sliding_attention" else 0
        return lambda q, k, v, u: sym.RingAttention(
            q, k, v, causal=True, window=window, name=pre + "attn")

    def sym_gen(seq_len):
        data = sym.Variable("data")
        label = sym.Variable("softmax_label")
        x = embed_tokens(data, vocab_size, hidden_size, dtype)
        for i, kind in enumerate(layer_types):
            x = qwen3_moe_block(
                x, f"l{i}_", attention(f"l{i}_", kind),
                rotary=rotary_keywords(rope_parameters[kind]), **block)
        pred = next_token_head(
            sym.RMSNorm(x, eps=rms_norm_eps, name="final_norm"), label,
            vocab_size, hidden_size, dtype, ignore_label)
        return pred, ("data",), ("softmax_label",)

    return sym_gen
