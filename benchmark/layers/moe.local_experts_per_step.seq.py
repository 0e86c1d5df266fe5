"""executor.moe_local_experts counter per step: the experts the MoE layers of
a launched train program hold here (num_local_experts, or all of them). 32
in the trinity-mini cell: 4 layers x 8 of the 128 the router scores.

0 where the program has no such counter (a tree before PR 32, a graph
without such a layer), as the other counter readers."""

from benchmark.lib import readers

NAME = "moe.local_experts_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.moe_local_experts")
