"""executor.moe_layers counter per step: the sparse-expert layers a launched
train program holds. 1.0 a layer of the model; 0 is the alarm that a change
took the MoE path away."""

from benchmark.lib import readers

NAME = "moe.layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.moe_layers")
