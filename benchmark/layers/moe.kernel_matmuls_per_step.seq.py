"""executor.moe_kernel_matmuls counter per step: the expert matmuls of a
launched train program that run the Pallas grouped-matmul kernels
(mxnet_tpu/ops/grouped_matmul.py). 9.0 a MoE layer when forward, dgrad and
wgrad of gate, up and down all engage; 0 is a program on the ragged_dot path
(the parent of PR 30, or a later change that silently falls back)."""

from benchmark.lib import readers

NAME = "moe.kernel_matmuls_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.moe_kernel_matmuls")
