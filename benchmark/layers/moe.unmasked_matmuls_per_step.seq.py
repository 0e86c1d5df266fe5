"""executor.moe_unmasked_matmuls counter per step: the expert matmuls of a
launched train program's held-range MoE layers that run with no row select
traced around them: 9.0 a layer (forward, dgrad and wgrad of gate, up and
down) whose round runs the Pallas grouped-matmul kernels, which own the
round's dead rows (zeros past the live rows in every row tile, none read into
a live row: mxnet_tpu/ops/grouped_matmul.py), so 36.0 in a cell of four such
layers. 0 is a program whose rounds keep ``where(live, matmul(where(live, r,
0), w), 0)`` (``ragged_dot``: the CPU, a float32 trunk), one that holds every
expert (no dead row, never a mask), or one with no such counter (the parent
of PR 64)."""

from benchmark.lib import readers

NAME = "moe.unmasked_matmuls_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.moe_unmasked_matmuls")
