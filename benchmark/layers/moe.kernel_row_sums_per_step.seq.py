"""executor.moe_kernel_row_sums counter per step: the row sums of a launched
train program's held-range MoE layers that run the Pallas row sum kernel
(mxnet_tpu/ops/row_sum_kernels.py) in place of XLA's scatter-add: 2.0 a layer
whose round engages it (the combine of a round's rows into their tokens and
the backward of the dispatch ``x[tok]``, both or none), so 8.0 in a cell of
four such layers. 0 is a program whose rounds the rule leaves to XLA's scatter
(a round under the rule's size, the CPU), one that holds every expert (no
scatter to replace), or one with no such counter (the parent of PR 63)."""

from benchmark.lib import readers

NAME = "moe.kernel_row_sums_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.moe_kernel_row_sums")
