"""executor.attention_layers counter per step: the attention layers
(RingAttention nodes) a launched train program holds. 1.0 a layer of the
model; 0 is the alarm."""

from benchmark.lib import readers

NAME = "attention.layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.attention_layers")
