"""Builder of ``resnet50-v2``: the program's symbol and the seeded weights.

bf16 trunk with f32 master weights the way a user gets it
(``train_imagenet.py --dtype bfloat16``): the data arrives in bfloat16 and
the executor's master-dtype rule keeps every parameter and BatchNorm
statistic float32 (``models/recipe.py``).
"""

from __future__ import annotations

import math


def symbol(cfg, mx):
    from mxnet_tpu import models

    return models.resnet(
        num_classes=cfg["num_classes"], num_layers=cfg["num_layers"],
        image_shape=",".join(map(str, cfg["image_shape"])))


def input_shapes(cfg, batch):
    return {"data": (batch,) + tuple(cfg["image_shape"]),
            "softmax_label": (batch,)}


def init_rule(name, shape):
    """(kind, scale, offset) of one leaf, for ``gen.make_leaves``. Gamma and beta are drawn, not 1 and 0, so
    that a BatchNorm left out or folded wrongly changes the answer."""
    if name.endswith("_gamma"):
        return "normal", 0.1, 1.0
    if name.endswith("_beta") or name.endswith("_moving_mean"):
        return "normal", 0.1, 0.0
    if name.endswith("_moving_var"):
        return "uniform01", 1.0, 0.5
    if name.endswith("_bias"):
        return "const", 0.0, 0.0
    fan_in = 1
    for d in shape[1:]:
        fan_in *= d
    return "normal", math.sqrt(2.0 / fan_in), 0.0


def train_flops_per_unit(cfg):
    """Model FLOPs of one training image (for ``kernels.mfu_pct``)."""
    from benchmark.lib import flops

    return flops.train_flops(flops.resnet_forward_macs(cfg))
