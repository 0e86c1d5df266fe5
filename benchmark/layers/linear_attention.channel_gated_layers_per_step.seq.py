"""executor.linear_attention_channel_gated_layers counter per step: the
linear-attention layers (GatedDeltaRule nodes) of a launched train program
whose gate has a key-channel axis, g of (B, Hv, T, Dk): Kimi Delta
Attention's decay, one a key channel of a head and token, whose chunks' Gram
matrices carry the decay inside their sum (mxnet_tpu/ops/gated_delta.py:
_channel_grams). 4.0 in the Kimi-Linear cell (published layers 1, 2, 3 and
5). 0 is a model rewritten onto a gate a head (the scalar rule, which has
kernels and would read as a speed-up), a program without the counter (the
parent of PR 48), or a path that is gone."""

from benchmark.lib import readers

NAME = "linear_attention.channel_gated_layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step(
    "executor.linear_attention_channel_gated_layers")
