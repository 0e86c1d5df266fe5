"""executor.rotary_scaled_nodes counter per step: the RotaryEmbedding nodes of
a launched train program whose schedule of frequencies is not the geometric
one (scaling="yarn": the frequencies blended pair by pair with the same
divided by the factor, cos and sin times the attention factor): 2.0 a layer
that turns so, its queries and its keys; 2.0 in mellum2-12b-train-1c (one
full layer of four). 0 is a program whose full layers fell back to the plain
frequencies and the amplitude 1 (the step runs all the same and the loss is
near the same at seeded weights, so nothing else says it), or a tree before
PR 62, which has no such counter."""

from benchmark.lib import readers

NAME = "rotary.scaled_nodes_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.rotary_scaled_nodes")
