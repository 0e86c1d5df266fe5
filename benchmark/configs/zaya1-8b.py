"""Builder of ``zaya1-8b``: the program's ``sym_gen``, the seeded weights
(normal(0, 0.02); norm gains, residual scales and the key temperature
normal(1, 0.1) and the router carry's gamma normal(0.5, 0.1), so that one
left out moves the answer; the residual biases and the router's MLP at the
scales that keep routing near balance), the model FLOPs of the configuration as it is
run (one chip's share of the deployment: 8 of 16 experts at top-1, an
eighth of the tied vocabulary), and the least work of its ``RingAttention``,
``MoE`` and ``CausalConv1D`` operators for their roofline shares."""

from __future__ import annotations

INIT_STD, GAIN_STD = 0.02, 0.1
# What keeps the seeded router near balance and still, as a deployment's
# trained and bias-balanced router is (PERF.md section 6, PR 44: with every
# weight and bias at 0.02 one expert took up to 4392 of a layer's 8192
# tokens, the rows held here ran from 2274 to 5688 a layer and the rate
# followed the seed by 0.6%). The stream's residual biases at 0.02 were as
# large as an embedding row, so most of every token's normed stream was one
# shared vector: they are at 1/100 of a weight. GeLU's mean (0.4 sigma^2
# against a spread of 0.5 sigma) gives each expert a head start of 0.8
# sigma of the logits' spread: the router's norm has gains around 1/8 and
# its second product weights of 1/8, pre-activations of 0.04 in both. The
# output layer's entries are +-1/2, so that every expert's row has the same
# length (an expert wins in proportion to about the cube of its row's
# length), and the logits spread by 0.16, which 150 Adam steps at 1e-6 move
# by under a hundredth (a first product at 0.0025 moved by 6% in a window
# and the rate fell 1.9% as tokens went over to the experts held here).
BETA_STD = 0.0002
ROUTER_GAIN, ROUTER_FC2_STD = 0.125, 0.125


def sym_gen(cfg, mx, dropout=None):
    """(sym_gen, state_names) for ``BucketingModule``. The model has no
    dropout and no recurrent state; ``dropout`` is the driver's signature."""
    from mxnet_tpu import models

    return models.zaya_sym_gen(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        cca_time0=cfg["cca_time0"], cca_time1=cfg["cca_time1"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        num_experts=cfg["num_experts_published"],
        expert_width=cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_tok"],
        router_hidden_size=cfg["router_hidden_size"],
        num_local_experts=cfg["num_experts"],
        expert_offset=cfg["expert_offset"], rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_parameters"]["hybrid"]["rope_theta"]),
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["compute_dtype"]), []


def input_shapes(cfg, batch, seq_len):
    return {"data": (batch, seq_len), "softmax_label": (batch, seq_len)}


def init_rule(name, shape):
    if name.endswith("_carry_gamma"):
        return "normal", GAIN_STD, 0.5
    if name.endswith("_router_norm_gamma"):
        return "normal", GAIN_STD * ROUTER_GAIN, ROUTER_GAIN
    if name.endswith("_gamma"):   # norm gains, residual scales, temperature
        return "normal", GAIN_STD, 1.0
    if name.endswith(("_res_beta", "_out_beta")):
        return "normal", BETA_STD, 0.0
    if name.endswith("_router_fc2_weight"):
        return "normal", ROUTER_FC2_STD, 0.0
    if name.endswith("_router_out_weight"):
        return "randint", 2.0, -0.5    # floor(2 u) - 1/2: -1/2 or +1/2
    return "normal", INIT_STD, 0.0


def packed_channels(cfg):
    """Channels of the packed row [queries | keys] the convolutions mix."""
    return (cfg["num_attention_heads"] + cfg["num_key_value_heads"]) \
        * cfg["head_dim"]


def conv_macs_per_token(cfg):
    """Multiply-adds of one token through a layer's two convolutions: the
    depthwise one, ``cca_time0`` taps a channel, and the one that mixes the
    ``head_dim`` channels inside each head, ``cca_time1`` taps."""
    return packed_channels(cfg) * (
        cfg["cca_time0"] + cfg["head_dim"] * cfg["cca_time1"])


def mixer_macs_per_token(cfg):
    """Multiply-adds of one token through a layer's five projections: q
    (hidden -> 8 x 128), k (hidden -> 2 x 128), the two value halves
    (hidden -> 128 each), o (8 x 128 -> hidden)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return h * (q + kv + kv) + q * h


def score_macs_per_token(cfg):
    """Multiply-adds of one token's causal attention in one layer: T / 2
    keys a query on average, ``q.k`` and ``p.v`` over the head's 128, in
    every query head."""
    t = max(cfg["buckets"])
    return cfg["num_attention_heads"] * (t // 2) * 2 * cfg["head_dim"]


def router_macs_per_token(cfg):
    """The router's four products: down to ``router_hidden_size``, two
    square ones, out to all the published experts."""
    r = cfg["router_hidden_size"]
    return cfg["hidden_size"] * r + 2 * r * r \
        + r * cfg["num_experts_published"]


def forward_macs_per_token(cfg):
    """Multiply-adds of one token position through what this chip computes:
    a layer's mixer (projections, convolutions, scores), its router, the
    expected ``top_k x held / published`` assignments to the experts held
    here; the sliced tied head."""
    h, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_experts_published"]
    layer = mixer_macs_per_token(cfg) + conv_macs_per_token(cfg) \
        + score_macs_per_token(cfg) + router_macs_per_token(cfg) \
        + held * 3 * h * width
    return cfg["num_hidden_layers"] * layer + h * cfg["vocab_size"]


def train_flops_per_unit(cfg):
    """Model FLOPs of one training token position (for ``kernels.mfu_pct``):
    3 x forward, 2 FLOPs a multiply-add, no recomputation."""
    from benchmark.lib import flops

    return flops.train_flops(forward_macs_per_token(cfg))


def conv_work(cfg, tokens, row_bytes=2, weight_bytes=4):
    """The least work of one layer's two ``CausalConv1D`` nodes a training
    step: every tap's product forward once and backward twice (the gradient
    with respect to the rows and to the weight); the packed row ``c``,
    ``c1`` between the two and ``c2`` after them across HBM once each way
    (the value forward, its gradient backward), the weights and biases read
    once and their gradients written once. The pad and the bias add are
    left out."""
    c = packed_channels(cfg)
    weights = c * cfg["cca_time0"] + c * cfg["head_dim"] * cfg["cca_time1"] \
        + 2 * c
    return {"flops": 3 * 2 * tokens * conv_macs_per_token(cfg),
            "bytes": 2 * row_bytes * tokens * 3 * c
            + 2 * weight_bytes * weights}


def expert_work(cfg, tokens):
    """``lib/flops.py:moe_work`` without its router's product and weight,
    which this model's ``MoE`` does not hold (``router="graph"``: the logits
    are an input, float32, read once with their gradient written once)."""
    from benchmark.lib import flops

    h, e = cfg["hidden_size"], cfg["num_experts_published"]
    work = flops.moe_work(tokens, h, cfg["moe_intermediate_size"], e,
                          cfg["num_experts"], cfg["num_experts_per_tok"])
    return {"flops": work["flops"] - 3 * 2 * tokens * e * h,
            "bytes": work["bytes"] - 2 * 4 * e * h + 2 * 4 * tokens * e}


def operator_work(cfg, traffic):
    """{operator: {"flops", "bytes"}} of one training step, the least the
    mathematics needs (``lib/flops.py``: the rule and what each term leaves
    out), for the roofline metrics, on all four layers: ``RingAttention``,
    the full causal triangle of 8 query heads over 2 key/value heads of
    128; ``MoE``, the expected half of the top-1 assignments that the 8
    experts held here receive (the router is ``FullyConnected`` nodes of
    the graph); ``CausalConv1D``, both convolutions of the mixer."""
    from benchmark.lib import flops

    rows, t = traffic["batch_size"], max(cfg["buckets"])
    layers = cfg["num_hidden_layers"]
    attention = flops.attention_work(
        rows, t, cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], cfg["head_dim"])
    return {"RingAttention": flops.add_work(*[attention] * layers),
            "MoE": flops.add_work(*[expert_work(cfg, rows * t)] * layers),
            "CausalConv1D": flops.add_work(
                *[conv_work(cfg, rows * t)] * layers)}
