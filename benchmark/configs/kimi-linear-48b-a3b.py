"""Builder of ``kimi-linear-48b-a3b``: the program's ``sym_gen``, the seeded
weights (normal(0, 0.02); norm gains normal(1, 0.1), so that a norm left out
moves the answer; the router's selection bias 0; ``A_log`` uniform over [0,
ln 16] a head and ``dt_bias`` uniform over [ln 0.001, ln 0.1] a CHANNEL, so
that inside every head of a KDA layer the 128 channels remember from one
token to a thousand), the model FLOPs of the configuration as it is run (one
chip's share of the deployment), and the least work of its ``MoE``,
``RingAttention``, ``GatedDeltaRule`` and ``CausalConv1D`` operators for
their roofline shares."""

from __future__ import annotations

import math

INIT_STD, GAIN_STD = 0.02, 0.1
A_RANGE = (1.0, 16.0)        # A = exp(A_log), one a head
DT_RANGE = (0.001, 0.1)      # exp(dt_bias), about softplus(dt_bias): a channel


def sym_gen(cfg, mx, dropout=None):
    """(sym_gen, state_names) for ``BucketingModule``. The model has no
    dropout and no state that outlives a row; ``dropout`` is the driver's
    signature."""
    from mxnet_tpu import models

    lin = cfg["linear_attn_config"]
    return models.kimi_linear_sym_gen(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        kda_layers=tuple(lin["kda_layers"]),
        full_attn_layers=tuple(lin["full_attn_layers"]),
        first_k_dense_replace=cfg["first_k_dense_replace"],
        linear_heads=lin["num_heads"], linear_head_dim=lin["head_dim"],
        conv_kernel=lin["short_conv_kernel_size"],
        num_heads=cfg["num_attention_heads"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
        dense_width=cfg["intermediate_size"],
        num_experts=cfg["num_experts_published"],
        expert_width=cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_token"],
        num_shared_experts=cfg["num_shared_experts"],
        route_norm=cfg["moe_renormalize"],
        route_scale=cfg["routed_scaling_factor"],
        num_local_experts=cfg["num_experts"],
        expert_offset=cfg["expert_offset"], rms_norm_eps=cfg["rms_norm_eps"],
        dtype=cfg["compute_dtype"]), []


def input_shapes(cfg, batch, seq_len):
    return {"data": (batch, seq_len), "softmax_label": (batch, seq_len)}


def init_rule(name, shape):
    if name.endswith("_expert_bias"):
        return "const", 0.0, 0.0
    if name.endswith("_A_log"):
        low, high = (math.log(a) for a in A_RANGE)
        return "uniform01", high - low, low
    if name.endswith("_dt_bias"):
        low, high = (math.log(a) for a in DT_RANGE)
        return "uniform01", high - low, low
    if name.endswith("_gamma"):
        return "normal", GAIN_STD, 1.0
    return "normal", INIT_STD, 0.0


def layer_kinds(cfg):
    """[(latent attention?, dense?)] of the layers kept, from the
    configuration's two lists (published numbering, from 1)."""
    full = cfg["linear_attn_config"]["full_attn_layers"]
    return [((i + 1) in full, i < cfg["first_k_dense_replace"])
            for i in range(cfg["num_hidden_layers"])]


def kda_macs_per_token(cfg):
    """Multiply-adds of one token through a KDA mixer: q, k, v and o
    (hidden x 4 head widths), the two low-rank products (hidden -> 128 ->
    width, twice), ``b`` (hidden -> heads), the convolution's taps over q, k
    and v, and the rule in its RECURRENT form, 3 x (128 x 128) a head: the
    read ``S'^T k``, the rank-1 write and the query ``S^T q`` (the chunked
    form the program runs does more arithmetic for the same function; model
    FLOPs do not count it, as ``lib/flops.py`` says of Qwen3-Next's)."""
    h = cfg["hidden_size"]
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    width = heads * d
    return 4 * h * width + 2 * (h * d + d * width) + h * heads \
        + lin["short_conv_kernel_size"] * 3 * width + 3 * heads * d * d


def latent_macs_per_token(cfg):
    """Multiply-adds of one token through the latent mixer: q (hidden ->
    heads x 192), kv_a (hidden -> 512 + 64), kv_b (512 -> heads x (128 +
    128)), o (heads x 128 -> hidden), and its causal scores over T / 2 keys
    on average, ``q.k`` over the keys' 192 and ``p.v`` over the values'
    128."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    t = max(cfg["buckets"])
    return h * heads * (nope + rope) + h * (rank + rope) \
        + rank * heads * (nope + dv) + heads * dv * h \
        + heads * (t // 2) * (nope + rope + dv)


def forward_macs_per_token(cfg):
    """Multiply-adds of one token position through what this chip computes:
    each layer's mixer; the dense SwiGLU of the leading layer; on an expert
    layer the shared expert, the router over all the published experts and
    the expected ``top_k x held / published`` assignments to the experts
    held here; the sliced head."""
    h, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["num_experts_per_token"] * cfg["num_experts"] \
        / cfg["num_experts_published"]
    macs = h * cfg["vocab_size"]
    for full, dense in layer_kinds(cfg):
        macs += latent_macs_per_token(cfg) if full \
            else kda_macs_per_token(cfg)
        if dense:
            macs += 3 * h * cfg["intermediate_size"]
        else:
            macs += cfg["num_shared_experts"] * 3 * h * width \
                + cfg["num_experts_published"] * h + held * 3 * h * width
    return macs


def train_flops_per_unit(cfg):
    """Model FLOPs of one training token position (for ``kernels.mfu_pct``):
    3 x forward, 2 FLOPs a multiply-add, no recomputation."""
    from benchmark.lib import flops

    return flops.train_flops(forward_macs_per_token(cfg))


def channel_gated_delta_rule_work(tokens, heads, d):
    """``lib/flops.py:delta_rule_work`` (q, k, v, the output, two scalar
    gates a head, one state a head) plus what a gate a KEY CHANNEL adds and
    that function does not count: ``g`` is ``d`` float32 numbers a head and
    token and not one, read once with its gradient written once. The
    arithmetic is the recurrent form's, as there: the decay of a state's
    rows by a vector and not a scalar is no product."""
    from benchmark.lib import flops

    work = flops.delta_rule_work(tokens, heads, heads, d, d)
    return {"flops": work["flops"],
            "bytes": work["bytes"] + 2 * 4 * tokens * heads * d}


def conv_work(cfg, tokens, row_bytes=2, weight_bytes=4):
    """The least work of one KDA layer's ``CausalConv1D`` a training step:
    every tap's product forward once and backward twice (the gradient with
    respect to the rows and to the weight); the packed row [q | k | v] in
    and out across HBM once each way (the value forward, its gradient
    backward), the taps read once and their gradient written once. The pad
    and the SiLU are left out."""
    lin = cfg["linear_attn_config"]
    c, taps = 3 * lin["num_heads"] * lin["head_dim"], \
        lin["short_conv_kernel_size"]
    return {"flops": 3 * 2 * tokens * c * taps,
            "bytes": 2 * row_bytes * tokens * 2 * c
            + 2 * weight_bytes * c * taps}


def operator_work(cfg, traffic):
    """{operator: {"flops", "bytes"}} of one training step, the least the
    mathematics needs (``lib/flops.py``: the rule and what each term leaves
    out), for the roofline metrics: ``GatedDeltaRule`` and ``CausalConv1D``
    on the four KDA layers (32 heads, states of 128 x 128, the gate a
    channel's bytes added here); ``RingAttention`` on the one latent layer,
    the full causal triangle of 32 heads, ``q.k`` over the keys' 192 and
    ``p.v`` over the values' 128; ``MoE`` on the four expert layers, the
    router over all 256 published experts and the expected share of the
    assignments that the 8 held here receive (the shared expert and the
    dense SwiGLU are ``FullyConnected`` nodes)."""
    from benchmark.lib import flops

    rows, t = traffic["batch_size"], max(cfg["buckets"])
    kinds = layer_kinds(cfg)
    full = sum(f for f, _ in kinds)
    sparse = sum(not d for _, d in kinds)
    lin = cfg["linear_attn_config"]
    attention = flops.attention_work(
        rows, t, cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    moe = flops.moe_work(rows * t, cfg["hidden_size"],
                         cfg["moe_intermediate_size"],
                         cfg["num_experts_published"], cfg["num_experts"],
                         cfg["num_experts_per_token"])
    delta = channel_gated_delta_rule_work(rows * t, lin["num_heads"],
                                          lin["head_dim"])
    return {"MoE": flops.add_work(*[moe] * sparse),
            "RingAttention": flops.add_work(*[attention] * full),
            "GatedDeltaRule": flops.add_work(*[delta] * (len(kinds) - full)),
            "CausalConv1D": flops.add_work(
                *[conv_work(cfg, rows * t)] * (len(kinds) - full))}
