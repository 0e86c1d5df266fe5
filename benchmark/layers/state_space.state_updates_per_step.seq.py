"""executor.selective_scan_state_updates counter per step: the state elements
the SelectiveScan nodes of a launched train program update, batch x T x
channels x states a node (one decay, one multiply-add and one share of the
output's sum each, forward): 2 x 4096 x 5120 x 16 = 671 088 640 in the
phi4-mini-flash cell. What the state-space layers cost scales with it; it
moves only if the graph, the widths or the rows do.

0 where the program has no such counter (a tree before PR 65), as the other
counter readers."""

from benchmark.lib import readers

NAME = "state_space.state_updates_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.selective_scan_state_updates")
