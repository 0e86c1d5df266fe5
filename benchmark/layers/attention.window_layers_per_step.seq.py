"""executor.attention_window_layers counter per step: the attention layers of
a launched train program that have a window (RingAttention nodes whose
block plan skips the key blocks outside the band). 4.0 in the trinity-mini
cell; 0 is the alarm of a masked full triangle.

0 where the program has no such counter (a tree before PR 32, a graph
without such a layer), as the other counter readers."""

from benchmark.lib import readers

NAME = "attention.window_layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.attention_window_layers")
