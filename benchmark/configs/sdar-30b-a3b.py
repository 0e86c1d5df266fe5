"""Builder of ``sdar-30b-a3b``: the program's ``sym_gen``, the seeded
weights (normal(0, 0.02); the embedding normal(0, 1) so that every seed
routes alike, ``keye-vl-2.0-30b-a3b.py:init_rule`` says why; norm gains
normal(1, 0.1), so that a norm left out moves the answer; the MASK token's
vector, the routers and the gains under them NOT from the run's seed, so
that every seed does the same work: :func:`still_leaf`), the model FLOPs of
the configuration as it is run (one chip's share of the deployment: TWO trunk
rows a clean token, the head once) and the least work of its ``MoE`` and
``RingAttention`` operators for their roofline shares: ``RingAttention``'s
is the pairs the block-diffusion mask KEEPS, ``L (L + Bd)`` a head over the
two copies of a row, never a causal triangle over them."""

from __future__ import annotations

INIT_STD, GAIN_STD, EMBED_STD = 0.02, 0.1, 1.0
STILL_SEEDS = (5784, 5793, 5850, 6114)     # a layer; still_leaf says why


def sym_gen(cfg, mx, dropout=None):
    """(sym_gen, state_names) for ``BucketingModule``. The model has no
    dropout and no recurrent state; ``dropout`` is the driver's signature,
    and its reference check is the one caller that gives it (0.0): that
    binding holds the noise to ``check_noise_seed`` so that the reference
    draws the same; the timed path gives none and draws fresh noise every
    step."""
    from mxnet_tpu import models

    return models.sdar_sym_gen(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["num_experts_published"],
        expert_width=cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_tok"], route_norm=cfg["norm_topk_prob"],
        num_local_experts=cfg["num_experts"],
        expert_offset=cfg["expert_offset"],
        block_length=cfg["block_length"], noise_eps=cfg["noise_eps"],
        noise_seed=None if dropout is None else cfg["check_noise_seed"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        lb_coef=cfg["router_aux_loss_coef"], dtype=cfg["compute_dtype"]), []


def input_shapes(cfg, batch, seq_len):
    return {"data": (batch, seq_len), "softmax_label": (batch, seq_len)}


def still_leaf(name, shape):
    """Layer i's (``l<i>_...``) router (experts, hidden) or the gain
    (hidden,) of the norm under it, drawn like every other leaf (normal(0, 0.02) and
    normal(1, 0.1)) but from ``STILL_SEEDS``, the same in every run.

    Why: a quarter of the trunk's rows are masked positions, which all carry
    the MASK token's one vector; seeded attention is an average and adds
    nearly the same to each, so all 4096 choose the same 8 experts a layer,
    and each of those that is among the 16 held here is 4096 more rows for
    its matmuls (0.68 ms of a 323 ms step). Under the run's seed that count
    was 0-5 over the four layers and the rate followed it (PERF.md section
    6, PR 57). Their choice is ``top_8(router . (gain x MASK vector))``, so
    with these three off the run's seed it is one choice. ``STILL_SEEDS``
    are the first four seeds from 5700 on at which EXACTLY ONE of the 8 is
    held (the expected 8 x 16 / 128), with 0.7 of score (0.8 deviations)
    between that one and the ninth, and between the eighth and the best held
    expert outside: room for what the layers add to the rows and for Adam's
    hundred steps on the router (``benchmark/tests/test_sdar_config.py``
    holds the choice at the published sizes)."""
    import numpy as np

    layer = int(name[1:name.index("_")])
    draws = np.random.RandomState(STILL_SEEDS[layer % len(STILL_SEEDS)])
    gain = 1.0 + GAIN_STD * draws.standard_normal(shape[-1])
    leaf = gain if len(shape) == 1 \
        else INIT_STD * draws.standard_normal(shape)
    return leaf.astype(np.float32)


def init_rule(name, shape):
    """(kind, scale, offset) of a seeded leaf (``lib/gen.py:make_leaves``: a
    leaf is ``draw * scale + offset``, broadcast); the embedding normal(0, 1),
    as the Keye-VL-2.0 cell's and for its reason, but for its last row, the
    MASK token's, which is all ones in every run: a new token of a converted
    model, whose vector the converter chooses, and under seeded weights
    ones is any vector of the other rows' length. :func:`still_leaf` says
    what for, and gives the routers and the gains under them."""
    import numpy as np

    if name == "embed_weight":
        mask = np.arange(shape[0])[:, None] == shape[0] - 1
        return ("normal", np.where(mask, 0.0, EMBED_STD).astype(np.float32),
                mask.astype(np.float32))
    if name.endswith(("_moe_router_weight", "_post_norm_gamma")):
        return "const", still_leaf(name, shape), 0.0
    if name.endswith("_gamma"):
        return "normal", GAIN_STD, 1.0
    return "normal", INIT_STD, 0.0


def kept_pairs(t, block):
    """Query-key pairs of one head over the two copies of a row of ``t``
    positions in blocks of ``block``: the clean copy's block b sees blocks
    0..b of itself (``t (t + block) / 2``), the noised copy's sees itself
    (``t block``) and blocks 0..b-1 of the clean one (``t (t - block) /
    2``)."""
    return t * (t + block)


def forward_macs_per_token(cfg):
    """Multiply-adds of one CLEAN token through what this chip computes: its
    two trunk rows through a layer's four projections, the router over all
    the published experts and the expected ``top_k x held / published``
    assignments to the experts held here; the main heads' scores twice (q.k
    and p.v) over the ``L + Bd`` pairs a token's two rows keep on average;
    the sliced head ONCE (the noised copy alone)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    t = max(cfg["buckets"])
    held = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_experts_published"]
    row = 2 * h * heads * d + 2 * h * kv * d \
        + cfg["num_experts_published"] * h \
        + held * 3 * h * cfg["moe_intermediate_size"]
    layer = 2 * row + 2 * heads * d * kept_pairs(t, cfg["block_length"]) / t
    return cfg["num_hidden_layers"] * layer + h * cfg["vocab_size"]


def train_flops_per_unit(cfg):
    """Model FLOPs of one clean training token (for ``kernels.mfu_pct``):
    3 x forward, 2 FLOPs a multiply-add, no recomputation."""
    from benchmark.lib import flops

    return flops.train_flops(forward_macs_per_token(cfg))


def diffusion_attention_work(rows, t, heads, kv_heads, d, block,
                             row_bytes=2):
    """The least work of one layer of block-diffusion attention over
    ``rows`` rows read twice, a training step: ``q.k`` and ``p.v`` over
    ``d`` on every KEPT pair of every query head (forward ``4 d`` FLOPs a
    pair, backward twice that), and each operand and result of the ``2
    rows`` trunk rows (q and o at ``heads``, k and v at ``kv_heads``)
    across HBM once, and once more as its gradient. The softmax and the
    join of a noised row's two parts are left out. (Written out here:
    ``lib/flops.py:attention_work`` counts a causal triangle or a band.)"""
    pairs = rows * heads * kept_pairs(t, block)
    width = 2 * d * (heads + kv_heads)
    return {"flops": 3 * 2 * pairs * 2 * d,
            "bytes": 2 * row_bytes * 2 * rows * t * width}


def operator_work(cfg, traffic):
    """{operator: {"flops", "bytes"}} of one training step, the least the
    mathematics needs (``lib/flops.py``: the rule and what each term leaves
    out), for the roofline metrics: ``RingAttention`` on every layer
    (:func:`diffusion_attention_work`: 32 query heads over 4 key/value
    heads of 128, the pairs the mask keeps); ``MoE`` on every layer over the
    2 B L trunk rows, the router over all 128 published experts and the
    expected share of the assignments that the held experts receive."""
    from benchmark.lib import flops

    rows, t = traffic["batch_size"], max(cfg["buckets"])
    layers = cfg["num_hidden_layers"]
    moe = flops.moe_work(2 * rows * t, cfg["hidden_size"],
                         cfg["moe_intermediate_size"],
                         cfg["num_experts_published"], cfg["num_experts"],
                         cfg["num_experts_per_tok"])
    attention = diffusion_attention_work(
        rows, t, cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], cfg["block_length"])
    return {"MoE": flops.add_work(*[moe] * layers),
            "RingAttention": flops.add_work(*[attention] * layers)}
