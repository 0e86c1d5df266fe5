"""executor.linear_attention_layers counter per step: the linear-attention
layers (GatedDeltaRule nodes, mxnet_tpu/ops/gated_delta.py) of a launched
train program. 3.0 in the qwen3-next cell: three of the four layers of its
period.

0 where the program has no such counter (a tree before PR 34, a graph
without such a layer), as the other counter readers."""

from benchmark.lib import readers

NAME = "linear_attention.layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.linear_attention_layers")
