"""executor.moe_assignments counter per step: the rows through the grouped
expert matmuls of a launched train program, tokens x top_k a layer."""

from benchmark.lib import readers

NAME = "moe.assignments_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.moe_assignments")
