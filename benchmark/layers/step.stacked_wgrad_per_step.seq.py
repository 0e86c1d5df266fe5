"""executor.stacked_wgrad counter per step: the shared-weight groups whose
gradient a launched train program computes as one stacked matmul."""

from benchmark.lib import readers

NAME = "step.stacked_wgrad_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.stacked_wgrad")
