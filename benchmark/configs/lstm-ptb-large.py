"""Builder of ``lstm-ptb-large``: the program's ``sym_gen`` and the seeded
weights (uniform in +-init_scale, as the paper; biases 0)."""

from __future__ import annotations

INIT_SCALE = 0.04


def sym_gen(cfg, mx, dropout=None):
    """(sym_gen, state_names) for ``BucketingModule``; ``dropout`` None
    takes the configuration's, 0.0 is for the reference check."""
    from mxnet_tpu import models

    return models.lstm_lm_sym_gen(
        num_hidden=cfg["num_hidden"], num_layers=cfg["num_layers"],
        num_embed=cfg["num_embed"], vocab_size=cfg["vocab_size"],
        dropout=cfg["dropout"] if dropout is None else dropout)


def input_shapes(cfg, batch, seq_len):
    return {"data": (batch, seq_len), "softmax_label": (batch, seq_len)}


def init_rule(name, shape):
    if name.endswith("_bias"):
        return "const", 0.0, 0.0
    return "uniform", INIT_SCALE, 0.0


def train_flops_per_unit(cfg):
    """Model FLOPs of one training token position (for ``kernels.mfu_pct``)."""
    from benchmark.lib import flops

    return flops.train_flops(flops.lstm_forward_macs_per_token(cfg))
