"""executor.selective_scan_layers counter per step: the state-space layers
(SelectiveScan nodes, mxnet_tpu/ops/selective_scan.py: a Mamba-1 mixer's
recurrence, a (channels x states) state a row) of a launched train program.
2.0 in the phi4-mini-flash cell: the Mamba layer and the Mamba layer whose
scan is the cross-decoder's memory.

0 where the program has no such counter (a tree before PR 65, a graph
without such a layer), as the other counter readers."""

from benchmark.lib import readers

NAME = "state_space.layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.selective_scan_layers")
