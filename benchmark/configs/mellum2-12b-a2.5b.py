"""Builder of ``mellum2-12b-a2.5b``: the program's ``sym_gen``, the seeded
weights (normal(0, 0.02); the embedding normal(0, 1): :func:`init_rule` says
why; norm gains normal(1, 0.1), so that a norm left out moves the answer),
the model FLOPs of the configuration as it is run (one chip's share of the
deployment), and the least work of its ``MoE`` and ``RingAttention``
operators for their roofline shares: on a window layer the band's pairs
exactly, on a full layer the causal triangle."""

from __future__ import annotations

INIT_STD, GAIN_STD, EMBED_STD = 0.02, 0.1, 1.0


def sym_gen(cfg, mx, dropout=None):
    """(sym_gen, state_names) for ``BucketingModule``. The model has no
    dropout and no recurrent state; ``dropout`` is the driver's signature."""
    from mxnet_tpu import models

    return models.mellum_sym_gen(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sliding_window=cfg["sliding_window"],
        num_experts=cfg["num_experts_published"],
        expert_width=cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_tok"], route_norm=cfg["norm_topk_prob"],
        num_local_experts=cfg["num_experts"],
        expert_offset=cfg["expert_offset"], rms_norm_eps=cfg["rms_norm_eps"],
        rope_parameters=cfg["rope_parameters"],
        lb_coef=cfg["router_aux_loss_coef"], dtype=cfg["compute_dtype"]), []


def input_shapes(cfg, batch, seq_len):
    return {"data": (batch, seq_len), "softmax_label": (batch, seq_len)}


def init_rule(name, shape):
    """(kind, scale, offset) of a seeded leaf. The embedding is normal(0, 1)
    (``torch.nn.Embedding``'s own default) and not normal(0, 0.02), for the
    reason ``keye-vl-2.0-30b-a3b.py:init_rule`` gives of the same block: an
    attention of seeded weights adds nearly the same vector to every token
    (a mean over up to 1024 values or more), larger than a 0.02 embedding
    after the next norm, so the routers of layers 1-3 would see one token
    16 384 times, a few experts would take every row and the 8 held here
    from none to over twice a round by seed. Under a unit embedding the
    tokens stay apart through the four layers and the held experts see about
    their balanced share, as under a trained router. Counted for this
    configuration (``tools/mellum2_readings.py routing``; the CPU, rows of
    2048, three seeds; a layer's assignments to the held experts over the
    balanced count): 0.17 to 1.48 at 0.02, 0.75 to 1.07 at 1.0."""
    if name == "embed_weight":
        return "normal", EMBED_STD, 0.0
    if name.endswith("_gamma"):
        return "normal", GAIN_STD, 1.0
    return "normal", INIT_STD, 0.0


def window_of(cfg, kind):
    """Keys a query of a layer of ``kind`` reads; 0: every key before it."""
    return cfg["sliding_window"] if kind == "sliding_attention" else 0


def layer_pairs(cfg, kind, t):
    """Query-key pairs one head of a layer of ``kind`` keeps over ``t``
    positions: query i its ``min(i + 1, sliding_window)`` on a window layer,
    its ``i + 1`` on a full one."""
    from benchmark.lib import flops

    return flops.causal_pairs(t, window_of(cfg, kind))


def forward_macs_per_token(cfg):
    """Multiply-adds of one token position through what this chip computes:
    a layer's four projections (q, k, v, o); the scores twice (q.k and p.v)
    over the band's pairs on a window layer and the triangle's on a full
    one; the router over all the published experts and the expected ``top_k
    x held / published`` assignments to the experts held here; the sliced
    head."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    t = max(cfg["buckets"])
    held = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_experts_published"]
    layer = 2 * h * heads * d + 2 * h * kv * d \
        + cfg["num_experts_published"] * h \
        + held * 3 * h * cfg["moe_intermediate_size"]
    scores = sum(2 * heads * d * layer_pairs(cfg, kind, t) / t
                 for kind in cfg["layer_types"])
    return len(cfg["layer_types"]) * layer + scores + h * cfg["vocab_size"]


def train_flops_per_unit(cfg):
    """Model FLOPs of one training token position (for ``kernels.mfu_pct``):
    3 x forward, 2 FLOPs a multiply-add, no recomputation."""
    from benchmark.lib import flops

    return flops.train_flops(forward_macs_per_token(cfg))


def operator_work(cfg, traffic):
    """{operator: {"flops", "bytes"}} of one training step, the least the
    mathematics needs (``lib/flops.py``: the rule and what each term leaves
    out), for the roofline metrics: ``MoE`` on every layer, the router over
    all 64 published experts and the expected share of the assignments that
    the 8 held here receive; ``RingAttention`` on every layer, the band's
    pairs exactly on a window layer and the full triangle on a full one, 32
    query heads over 4 key/value heads of 128."""
    from benchmark.lib import flops

    rows, t = traffic["batch_size"], max(cfg["buckets"])
    d = cfg["head_dim"]
    moe = flops.moe_work(rows * t, cfg["hidden_size"],
                         cfg["moe_intermediate_size"],
                         cfg["num_experts_published"], cfg["num_experts"],
                         cfg["num_experts_per_tok"])
    kinds = cfg["layer_types"]
    attention = [flops.attention_work(
        rows, t, cfg["num_attention_heads"], cfg["num_key_value_heads"], d,
        d, window=window_of(cfg, kind)) for kind in kinds]
    return {"MoE": flops.add_work(*[moe] * len(kinds)),
            "RingAttention": flops.add_work(*attention)}
