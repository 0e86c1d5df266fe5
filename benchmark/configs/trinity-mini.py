"""Builder of ``trinity-mini``: the program's ``sym_gen``, the seeded weights
(normal(0, 0.02); norm gains normal(1, 0.1), so that a norm left out moves
the answer; the router's selection bias 0) and the model FLOPs of the
configuration as it is run: one chip's share of the deployment."""

from __future__ import annotations

INIT_STD, GAIN_STD = 0.02, 0.1


def sym_gen(cfg, mx, dropout=None):
    """(sym_gen, state_names) for ``BucketingModule``. The model has no
    dropout and no recurrent state; ``dropout`` is the driver's signature."""
    from mxnet_tpu import models

    return models.afmoe_sym_gen(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sliding_window=cfg["sliding_window"],
        dense_width=cfg["intermediate_size"],
        num_experts=cfg["num_experts_published"],
        expert_width=cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["num_shared_experts"],
        route_norm=cfg["route_norm"], route_scale=cfg["route_scale"],
        num_local_experts=cfg["num_experts"],
        expert_offset=cfg["expert_offset"], rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]), embed_scale=cfg["mup_enabled"],
        dtype=cfg["compute_dtype"]), []


def input_shapes(cfg, batch, seq_len):
    return {"data": (batch, seq_len), "softmax_label": (batch, seq_len)}


def init_rule(name, shape):
    if name.endswith("_expert_bias"):
        return "const", 0.0, 0.0
    if name.endswith("_gamma"):
        return "normal", GAIN_STD, 1.0
    return "normal", INIT_STD, 0.0


def band_pairs(t, window):
    """Query-key pairs of one head of a causal window layer over ``t``
    positions: query i reads ``min(i + 1, window)`` keys."""
    w = min(window, t)
    return w * (w + 1) // 2 + (t - w) * w


def forward_macs_per_token(cfg):
    """Multiply-adds of one token position through what this chip computes:
    the five projections of a layer (q, k, v, output gate, o); the scores
    twice (q.k and p.v) over the band's pairs on a window layer and T/2
    keys on a full one; the dense SwiGLU of a leading layer; on an expert
    layer the shared expert, the router over all the published experts and
    the expected ``top_k x held / published`` assignments to the experts
    held here; the sliced head."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    t = max(cfg["buckets"])
    projections = 3 * h * heads * d + 2 * h * kv * d
    shared = cfg["num_shared_experts"] * 3 * h * cfg["moe_intermediate_size"]
    held = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_experts_published"]
    macs = h * cfg["vocab_size"]
    for i, kind in enumerate(cfg["layer_types"]):
        keys = band_pairs(t, cfg["sliding_window"]) / t \
            if kind == "sliding_attention" else t / 2
        macs += projections + 2 * keys * heads * d
        if i < cfg["num_dense_layers"]:
            macs += 3 * h * cfg["intermediate_size"]
        else:
            macs += shared + cfg["num_experts_published"] * h \
                + held * 3 * h * cfg["moe_intermediate_size"]
    return macs


def train_flops_per_unit(cfg):
    """Model FLOPs of one training token position (for ``kernels.mfu_pct``):
    3 x forward, 2 FLOPs a multiply-add, no recomputation."""
    from benchmark.lib import flops

    return flops.train_flops(forward_macs_per_token(cfg))
