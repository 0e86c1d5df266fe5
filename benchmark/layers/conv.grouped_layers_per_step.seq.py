"""executor.conv_grouped_layers counter per step: the CausalConv1D nodes of
a launched train program that mix channels inside groups (``num_group``: the
second convolution of compressed convolutional attention, the 128 channels
of each of the 10 heads) and are not depthwise. 4.0 in the zaya1-8b cell; 0
there is the alarm that the mixer's channel mixing was dropped or rewritten
onto another operator.

0 where the program has no such counter (a tree before PR 44, a graph
without such a layer), as the other counter readers."""

from benchmark.lib import readers

NAME = "conv.grouped_layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.conv_grouped_layers")
