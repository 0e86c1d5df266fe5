"""executor.attention_latent_layers counter per step: the RingAttention
layers of a launched train program whose values are not as wide as their
keys (a latent-attention head: queries and keys of 192 = 128 + 64 rotated,
values of 128). 5.0 in the kanana2-30b cell; 0 there is the alarm that the
model was rewritten onto equal widths (keys or values padded).

0 where the program has no such counter (a tree before PR 41, a graph
without such a layer), as the other counter readers."""

from benchmark.lib import readers

NAME = "attention.latent_layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.attention_latent_layers")
