"""Model FLOPs of one sample, from the sizes in a configuration file.

The benchmark's own copy of the arithmetic behind ``bench.py:
_resnet_train_flops`` / ``models/recipe.py:estimate_flops``: convolutions
and dense layers only, one multiply-add = 2 FLOPs, training = 3 x forward
(forward, input gradient, weight gradient), no recomputation. The program's
estimator counts one multiply-add as ONE (4.1 G for ResNet-50); this file
says two, which is what a peak in FLOP/s is counted in.
"""

from __future__ import annotations


def resnet_forward_macs(cfg):
    """Multiply-adds of one image through the pre-activation bottleneck
    ResNet of ``cfg`` (stride on the 3x3, as the reference symbol has it)."""
    _, h, w = cfg["image_shape"]
    filters = cfg["filter_list"]
    h, w = h // 2, w // 2                      # 7x7 stem, stride 2
    macs = h * w * filters[0] * cfg["image_shape"][0] * 49
    h, w = h // 2, w // 2                      # 3x3 max pool, stride 2
    cin = filters[0]
    for stage, units in enumerate(cfg["units"]):
        f = filters[stage + 1]
        mid = f // 4
        for unit in range(units):
            stride = 2 if (unit == 0 and stage > 0) else 1
            ho, wo = h // stride, w // stride
            macs += h * w * cin * mid          # 1x1 at the input resolution
            macs += ho * wo * mid * mid * 9    # 3x3, carries the stride
            macs += ho * wo * mid * f          # 1x1
            if unit == 0:
                macs += ho * wo * cin * f      # projection shortcut
            h, w, cin = ho, wo, f
    macs += cin * cfg["num_classes"]
    return macs


def lstm_forward_macs_per_token(cfg):
    """Multiply-adds of one token position through the stacked LSTM LM."""
    hidden, embed = cfg["num_hidden"], cfg["num_embed"]
    macs = 0
    for layer in range(cfg["num_layers"]):
        macs += 4 * hidden * ((embed if layer == 0 else hidden) + hidden)
    return macs + hidden * cfg["vocab_size"]


def train_flops(forward_macs):
    """Training FLOPs of one unit whose forward pass is ``forward_macs``."""
    return 3 * 2 * forward_macs
