"""Builder of ``keye-vl-2.0-30b-a3b``: the program's ``sym_gen``, the seeded
weights (normal(0, 0.02); the embedding normal(0, 1): :func:`init_rule` says
why; norm gains normal(1, 0.1), so that a norm left out moves the answer; the
indexer's LayerNorm bias normal(0, 0.02)), the model
FLOPs of the configuration as it is run (one chip's share of the deployment),
and the least work of its ``MoE`` and ``RingAttention`` operators for their
roofline shares: under the selection ``RingAttention``'s is the pairs each
query KEEPS at both widths plus the pairs its indexer scores, never the
masked triangle."""

from __future__ import annotations

INIT_STD, GAIN_STD, EMBED_STD = 0.02, 0.1, 1.0


def sym_gen(cfg, mx, dropout=None):
    """(sym_gen, state_names) for ``BucketingModule``. The model has no
    dropout and no recurrent state; ``dropout`` is the driver's signature."""
    from mxnet_tpu import models

    sa = cfg["sa_config"]
    return models.keye_vl2_sym_gen(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["num_experts_published"],
        expert_width=cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_tok"], route_norm=cfg["norm_topk_prob"],
        num_local_experts=cfg["num_experts"],
        expert_offset=cfg["expert_offset"],
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_top_k=sa["topk"],
        index_loss_coef=cfg["index_loss_coef"],
        index_norm_eps=cfg["index_norm_eps"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        lb_coef=cfg["router_aux_loss_coef"], dtype=cfg["compute_dtype"]), []


def input_shapes(cfg, batch, seq_len):
    return {"data": (batch, seq_len), "softmax_label": (batch, seq_len)}


def init_rule(name, shape):
    """(kind, scale, offset) of a seeded leaf. The embedding is normal(0, 1)
    (``torch.nn.Embedding``'s own default) and not normal(0, 0.02), so that
    every seed routes alike: an attention of seeded weights adds nearly the
    same vector to every token (a mean over up to 2048 values), 50 times a
    0.02 embedding after the next norm, so the routers of layers 1-3 saw one
    token 16 384 times, a few experts took every row, and the 8 held here
    got from none to over twice a round by seed: ``MoE`` ran 0 to 3 second
    rounds and the step's time followed the seed (PERF.md section 6, PR 51).
    Under a unit embedding the tokens stay apart through the four layers and
    the held experts see their balanced share to a few per cent, as under a
    trained router."""
    if name == "embed_weight":
        return "normal", EMBED_STD, 0.0
    if name.endswith("_gamma"):
        return "normal", GAIN_STD, 1.0
    return "normal", INIT_STD, 0.0


def kept_pairs(t, top_k):
    """Query-key pairs of one head under the selection over ``t`` causal
    positions: query i keeps ``min(i + 1, top_k)`` keys, as many as a band
    of ``top_k`` holds."""
    from benchmark.lib import flops

    return flops.causal_pairs(t, top_k)


def index_pairs(t):
    """Pairs one indexer head scores: every earlier key of every query."""
    return t * (t + 1) // 2


def forward_macs_per_token(cfg):
    """Multiply-adds of one token position through what this chip computes:
    a layer's four projections (q, k, v, o) and the indexer's three; the
    main heads' scores twice (q.k and p.v) over the pairs a query KEEPS and
    the indexer's once over every earlier key, both averaged over the row;
    the router over all the published experts and the expected ``top_k x
    held / published`` assignments to the experts held here; the sliced
    head."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    t = max(cfg["buckets"])
    held = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_experts_published"]
    layer = 2 * h * heads * d + 2 * h * kv * d + h * (j * di + di + j) \
        + 2 * heads * d * kept_pairs(t, sa["topk"]) / t \
        + j * di * index_pairs(t) / t \
        + cfg["num_experts_published"] * h \
        + held * 3 * h * cfg["moe_intermediate_size"]
    return cfg["num_hidden_layers"] * layer + h * cfg["vocab_size"]


def train_flops_per_unit(cfg):
    """Model FLOPs of one training token position (for ``kernels.mfu_pct``):
    3 x forward, 2 FLOPs a multiply-add, no recomputation."""
    from benchmark.lib import flops

    return flops.train_flops(forward_macs_per_token(cfg))


def selected_attention_work(rows, t, heads, kv_heads, d, top_k, index_heads,
                            index_dim, row_bytes=2):
    """The least work of one layer of attention under a learned selection,
    a training step: ``lib/flops.py:attention_work`` over the pairs the
    queries KEEP (query i its ``min(i + 1, top_k)``: as many pairs as a band
    of ``top_k`` has, which is how that function is asked), plus the
    indexer: one product of ``index_dim`` on every causal pair of each of
    its heads, forward once and backward twice (its own term's gradient
    with respect to each operand), and its three operands (``index_heads``
    queries and ONE key of ``index_dim``, a weight a head) across HBM once
    with their gradients. The top-k, the ReLU, the weighted sum over the
    indexer's heads and the two softmaxes of the KL term are left out, as
    the softmax is there."""
    from benchmark.lib import flops

    kept = flops.attention_work(rows, t, heads, kv_heads, d, d,
                                window=top_k, row_bytes=row_bytes)
    width = index_heads * index_dim + index_dim + index_heads
    return flops.add_work(kept, {
        "flops": 3 * 2 * rows * index_heads * index_pairs(t) * index_dim,
        "bytes": 2 * row_bytes * rows * t * width})


def operator_work(cfg, traffic):
    """{operator: {"flops", "bytes"}} of one training step, the least the
    mathematics needs (``lib/flops.py``: the rule and what each term leaves
    out), for the roofline metrics: ``RingAttention`` on every layer, the
    kept pairs of 32 query heads over 4 key/value heads of 128 and the
    pairs the 16 x 64 indexer scores (:func:`selected_attention_work`);
    ``MoE`` on every layer, the router over all 128 published experts and
    the expected share of the assignments that the 8 held here receive."""
    from benchmark.lib import flops

    rows, t = traffic["batch_size"], max(cfg["buckets"])
    sa = cfg["sa_config"]
    layers = cfg["num_hidden_layers"]
    moe = flops.moe_work(rows * t, cfg["hidden_size"],
                         cfg["moe_intermediate_size"],
                         cfg["num_experts_published"], cfg["num_experts"],
                         cfg["num_experts_per_tok"])
    attention = selected_attention_work(
        rows, t, cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], sa["topk"], sa["indexer_num_heads"],
        sa["indexer_head_dim"])
    return {"MoE": flops.add_work(*[moe] * layers),
            "RingAttention": flops.add_work(*[attention] * layers)}
