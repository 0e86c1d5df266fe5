"""Builder of ``ouro-2.6b``: the program's ``sym_gen``, the seeded weights
(normal(0, 0.02); norm gains normal(1, 0.1), so that a norm left out moves
the answer; the exit gate's bias -1, so that leaving it out does too) and the
model FLOPs of the configuration as it is run: the first layers of the one
stack, every one of them applied ``total_ut_steps`` times a step, and the
head once an exit."""

from __future__ import annotations

INIT_STD, GAIN_STD, GATE_BIAS = 0.02, 0.1, -1.0
LOSS = "ExitSoftmaxOutput"


def sym_gen(cfg, mx, dropout=None):
    """(sym_gen, state_names) for ``BucketingModule``. The model has no
    dropout and no recurrent state; ``dropout`` is the driver's signature."""
    from mxnet_tpu import models

    return models.ouro_sym_gen(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        total_ut_steps=cfg["total_ut_steps"],
        exit_entropy_beta=cfg["exit_entropy_beta"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]), dtype=cfg["compute_dtype"]), []


def input_shapes(cfg, batch, seq_len):
    return {"data": (batch, seq_len), "softmax_label": (batch, seq_len)}


def init_rule(name, shape):
    if name == "early_exit_gate_bias":
        return "const", GATE_BIAS, 0.0
    if name.endswith("_gamma"):
        return "normal", GAIN_STD, 1.0
    return "normal", INIT_STD, 0.0


def layer_macs_per_token(cfg):
    """Multiply-adds of one token position through ONE application of a
    layer: q, k, v, o; the scores twice (q.k and p.v) over the causal
    triangle's exact pairs, (T + 1) / 2 keys a query; gate, up and down of
    the SwiGLU."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, t = cfg["num_attention_heads"], max(cfg["buckets"])
    return (4 * h * heads * d + 2 * ((t + 1) / 2) * heads * d
            + 3 * h * cfg["intermediate_size"])


def forward_macs_per_token(cfg):
    """Every layer ``total_ut_steps`` times and the head once an exit; the
    gate's 2048 a row and the embedding's gather are left out."""
    return cfg["total_ut_steps"] * (
        cfg["num_hidden_layers"] * layer_macs_per_token(cfg)
        + cfg["hidden_size"] * cfg["vocab_size"])


def train_flops_per_unit(cfg):
    """Model FLOPs of one training token position (for ``kernels.mfu_pct``):
    3 x forward, 2 FLOPs a multiply-add, no recomputation."""
    from benchmark.lib import flops

    return flops.train_flops(forward_macs_per_token(cfg))


def exit_loss_work(rows, classes, exits, row_bytes=2, out_bytes=4):
    """The loss over ``exits`` exits' logits (the head is the graph's, a
    ``FullyConnected`` node an exit): each exit's logits read once, the last
    exit's float32 softmax written once, each exit's gradient written once.
    No product: the exponentials, the two reductions a row and the exit
    distribution's few numbers a row are left out, so the share is of the
    bandwidth peak. Backward's second read of the logits (it makes each
    softmax again rather than keep it) is a recomputation, not work."""
    return {"flops": 0,
            "bytes": rows * classes * (2 * exits * row_bytes + out_bytes)}


def operator_work(cfg, traffic):
    """{operator: {"flops", "bytes"}} of one training step, the least the
    mathematics needs (``lib/flops.py``: the rule and what each term leaves
    out), for the roofline metrics: ``RingAttention`` the full causal
    triangle of 16 heads of 128, once a layer APPLICATION
    (``total_ut_steps`` x layers); ``ExitSoftmaxOutput`` as
    :func:`exit_loss_work` says."""
    from benchmark.lib import flops

    rows, t = traffic["batch_size"], max(cfg["buckets"])
    d = cfg["head_dim"]
    attention = flops.attention_work(rows, t, cfg["num_attention_heads"],
                                     cfg["num_key_value_heads"], d, d)
    applications = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    return {"RingAttention": flops.add_work(*[attention] * applications),
            LOSS: exit_loss_work(rows * t, cfg["vocab_size"],
                                 cfg["total_ut_steps"])}
