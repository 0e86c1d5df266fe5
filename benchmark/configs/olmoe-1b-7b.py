"""Builder of ``olmoe-1b-7b``: the program's ``sym_gen``, the seeded weights
(normal(0, 0.02); norm gains normal(1, 0.1), so that a norm left out moves
the answer) and the model FLOPs of the configuration as it is run."""

from __future__ import annotations

INIT_STD, GAIN_STD = 0.02, 0.1


def sym_gen(cfg, mx, dropout=None):
    """(sym_gen, state_names) for ``BucketingModule``. The model has no
    dropout and no recurrent state; ``dropout`` is the driver's signature."""
    from mxnet_tpu import models

    return models.olmoe_sym_gen(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], num_experts=cfg["num_experts"],
        expert_width=cfg["intermediate_size"],
        top_k=cfg["num_experts_per_tok"], rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        lb_coef=cfg["router_aux_loss_coef"],
        z_coef=cfg["router_z_loss_coef"], dtype=cfg["compute_dtype"]), []


def input_shapes(cfg, batch, seq_len):
    return {"data": (batch, seq_len), "softmax_label": (batch, seq_len)}


def init_rule(name, shape):
    if name.endswith("_gamma"):
        return "normal", GAIN_STD, 1.0
    return "normal", INIT_STD, 0.0


def forward_macs_per_token(cfg):
    """Multiply-adds of one token position through the model as run: the
    causal scores at half the row (a token sees T/2 keys on average, twice:
    q.k and p.v), the eight routed experts of the 64 (gate, up, down), the
    router over all of them, the four projections, the untied head."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    t = max(cfg["buckets"])
    layer = (4 * h * h
             + 2 * (t // 2) * h
             + cfg["num_experts"] * h
             + cfg["num_experts_per_tok"] * 3 * h * f)
    return cfg["num_hidden_layers"] * layer + h * cfg["vocab_size"]


def train_flops_per_unit(cfg):
    """Model FLOPs of one training token position (for ``kernels.mfu_pct``):
    3 x forward, 2 FLOPs a multiply-add, no recomputation."""
    from benchmark.lib import flops

    return flops.train_flops(forward_macs_per_token(cfg))
