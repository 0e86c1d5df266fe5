"""Builder of ``kanana-2-30b-a3b``: the program's ``sym_gen``, the seeded
weights (normal(0, 0.02); norm gains normal(1, 0.1), so that a norm left out
moves the answer; the router's selection bias 0), the model FLOPs of the
configuration as it is run (one chip's share of the deployment, ``p.v`` at
the values' 128 and not at the keys' 192), and the operations and bytes of
the attention kernels for their roofline."""

from __future__ import annotations

INIT_STD, GAIN_STD = 0.02, 0.1


def sym_gen(cfg, mx, dropout=None):
    """(sym_gen, state_names) for ``BucketingModule``. The model has no
    dropout and no recurrent state; ``dropout`` is the driver's signature."""
    from mxnet_tpu import models

    return models.deepseek_v3_sym_gen(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_heads=cfg["num_attention_heads"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
        dense_width=cfg["intermediate_size"],
        num_experts=cfg["n_routed_experts_published"],
        expert_width=cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["n_shared_experts"],
        route_norm=cfg["norm_topk_prob"],
        route_scale=cfg["routed_scaling_factor"],
        num_local_experts=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"], rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        rope_interleave=cfg["rope_interleave"],
        dtype=cfg["compute_dtype"]), []


def input_shapes(cfg, batch, seq_len):
    return {"data": (batch, seq_len), "softmax_label": (batch, seq_len)}


def init_rule(name, shape):
    if name.endswith("_expert_bias"):
        return "const", 0.0, 0.0
    if name.endswith("_gamma"):
        return "normal", GAIN_STD, 1.0
    return "normal", INIT_STD, 0.0


def mixer_macs_per_token(cfg):
    """Multiply-adds of one token through a layer's four projections: q
    (hidden -> heads x 192), kv_a (hidden -> 512 + 64), kv_b (512 -> heads
    x (128 + 128)), o (heads x 128 -> hidden)."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    return h * heads * (nope + rope) + h * (rank + rope) \
        + rank * heads * (nope + dv) + heads * dv * h


def score_macs_per_token(cfg):
    """Multiply-adds of one token's causal attention in one layer: T / 2
    keys a query on average, ``q.k`` over the keys' 192 and ``p.v`` over
    the values' 128, in every head."""
    t = max(cfg["buckets"])
    return cfg["num_attention_heads"] * (t // 2) * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def forward_macs_per_token(cfg):
    """Multiply-adds of one token position through what this chip computes:
    a layer's mixer (its projections and its scores); the dense SwiGLU of a
    leading layer; on an expert layer the shared experts, the router over
    all the published experts and the expected ``top_k x held / published``
    assignments to the experts held here; the sliced head."""
    h, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["n_routed_experts_published"]
    macs = h * cfg["vocab_size"]
    for i in range(cfg["num_hidden_layers"]):
        macs += mixer_macs_per_token(cfg) + score_macs_per_token(cfg)
        if i < cfg["first_k_dense_replace"]:
            macs += 3 * h * cfg["intermediate_size"]
        else:
            macs += cfg["n_shared_experts"] * 3 * h * width \
                + cfg["n_routed_experts_published"] * h \
                + held * 3 * h * width
    return macs


def train_flops_per_unit(cfg):
    """Model FLOPs of one training token position (for ``kernels.mfu_pct``):
    3 x forward, 2 FLOPs a multiply-add, no recomputation."""
    from benchmark.lib import flops

    return flops.train_flops(forward_macs_per_token(cfg))


# --- the attention kernels' roofline (PERF.md section 5; ROADMAP Reach A1 (c))
def visited_pairs(t, bq, bk):
    """Query-key pairs one head's kernels score over ``t`` causal
    positions in tiles of ``bq`` queries x ``bk`` keys: a query block
    visits the key blocks up to the one that holds its last position."""
    return sum(bq * bk * ((a + bq - 1) // bk + 1) for a in range(0, t, bq))


def attention_kernel_flops(cfg, bq=512, bk=512, batch=1):
    """{kernel: FLOPs of one layer's launch}: ``attention_fwd`` runs q.k
    over 192 and p.v over 128 on every visited pair; ``attention_bwd``
    recomputes q.k and runs d_out.v, dv (128 each), dk and dq (192 each).
    ``bq`` / ``bk`` are the tiles ``flash_attention.plan`` gives the cell."""
    dk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    pairs = batch * cfg["num_attention_heads"] * visited_pairs(
        max(cfg["buckets"]), bq, bk)
    return {"attention_fwd": 2 * pairs * (dk + dv),
            "attention_bwd": 2 * pairs * (3 * dk + 2 * dv)}


def attention_kernel_bytes(cfg, batch=1):
    """{kernel: bytes of one layer's launch to and from HBM, as the arrays
    are declared}: bfloat16 q, k (192) and v (128) in, the output (128) and
    the rows' float32 log-sum-exp out; backward reads q, k, v, d_out, the
    log-sum-exp and delta and writes dq, dk (192) and dv (128). A head's
    keys and values are read once for all its query blocks. (The v5e's tiled
    layout stores a minor dimension of 192 in 256 lanes: the bytes that
    move are 4/3 of the 192-wide terms.)"""
    dk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    rows = batch * cfg["num_attention_heads"] * max(cfg["buckets"])
    return {"attention_fwd": rows * (2 * (2 * dk + 2 * dv) + 4),
            "attention_bwd": rows * (2 * (4 * dk + 3 * dv) + 8)}
