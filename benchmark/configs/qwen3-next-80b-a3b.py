"""Builder of ``qwen3-next-80b-a3b``: the program's ``sym_gen``, the seeded
weights (normal(0, 0.02); norm gains normal(1, 0.1), so that a norm left out
moves the answer; ``A_log`` uniform over [0, ln 16] and ``dt_bias`` uniform
over [ln 0.001, ln 0.1], so that the linear layers' heads remember 1 to 1000
tokens) and the model FLOPs of the configuration as it is run: one chip's
share of the deployment."""

from __future__ import annotations

import math

INIT_STD, GAIN_STD = 0.02, 0.1
A_RANGE = (1.0, 16.0)        # A = exp(A_log)
DT_RANGE = (0.001, 0.1)      # exp(dt_bias), about softplus(dt_bias)


def sym_gen(cfg, mx, dropout=None):
    """(sym_gen, state_names) for ``BucketingModule``. The model has no
    dropout and no state that outlives a row; ``dropout`` is the driver's
    signature."""
    from mxnet_tpu import models

    return models.qwen3_next_sym_gen(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        full_attention_interval=cfg["full_attention_interval"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        linear_key_heads=cfg["linear_num_key_heads"],
        linear_value_heads=cfg["linear_num_value_heads"],
        linear_key_dim=cfg["linear_key_head_dim"],
        linear_value_dim=cfg["linear_value_head_dim"],
        conv_kernel=cfg["linear_conv_kernel_dim"],
        num_experts=cfg["num_experts_published"],
        expert_width=cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_tok"],
        shared_expert_width=cfg["shared_expert_intermediate_size"],
        route_norm=cfg["norm_topk_prob"],
        num_local_experts=cfg["num_experts"],
        expert_offset=cfg["expert_offset"],
        lb_coef=cfg["router_aux_loss_coef"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        dtype=cfg["compute_dtype"]), []


def input_shapes(cfg, batch, seq_len):
    return {"data": (batch, seq_len), "softmax_label": (batch, seq_len)}


def init_rule(name, shape):
    if name.endswith("_A_log"):
        low, high = (math.log(a) for a in A_RANGE)
        return "uniform01", high - low, low
    if name.endswith("_dt_bias"):
        low, high = (math.log(a) for a in DT_RANGE)
        return "uniform01", high - low, low
    if name.endswith("_gamma"):
        return "normal", GAIN_STD, 1.0
    return "normal", INIT_STD, 0.0


def forward_macs_per_token(cfg):
    """Multiply-adds of one token position through what this chip computes.

    A Gated DeltaNet layer: ``in_proj_qkvz`` (2 key widths + 2 value widths)
    and ``in_proj_ba`` (2 a value head) from the hidden size, ``out_proj``
    back from the value width, the convolution's taps over q, k and v, and
    the recurrence in its RECURRENT form, 3 x (key dim x value dim) a value
    head: the read ``S'^T k``, the rank-1 write and the query ``S^T q``
    (the chunked form the program runs does more arithmetic for the same
    function; model FLOPs do not count it). The attention layer: q with its
    gate, k, v, o, and the scores twice (q.k and p.v) over T / 2 keys. Every
    layer: the router over all the published experts, the shared expert
    with its gate, and the expected ``top_k x held / published``
    assignments to the experts held here. The sliced head."""
    h = cfg["hidden_size"]
    t = max(cfg["buckets"])
    key_width = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    value_width = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    linear = h * (2 * key_width + 2 * value_width) \
        + h * 2 * cfg["linear_num_value_heads"] + value_width * h \
        + cfg["linear_conv_kernel_dim"] * (2 * key_width + value_width) \
        + 3 * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    full = h * 2 * heads * d + 2 * h * kv * d + heads * d * h \
        + 2 * (t / 2) * heads * d
    expert = 3 * h * cfg["moe_intermediate_size"]
    held = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_experts_published"]
    sparse = cfg["num_experts_published"] * h + h \
        + 3 * h * cfg["shared_expert_intermediate_size"] + held * expert
    layers = cfg["num_hidden_layers"]
    full_layers = layers // cfg["full_attention_interval"]
    return (layers - full_layers) * linear + full_layers * full \
        + layers * sparse + h * cfg["vocab_size"]


def train_flops_per_unit(cfg):
    """Model FLOPs of one training token position (for ``kernels.mfu_pct``):
    3 x forward, 2 FLOPs a multiply-add, no recomputation."""
    from benchmark.lib import flops

    return flops.train_flops(forward_macs_per_token(cfg))
