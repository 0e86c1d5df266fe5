"""executor.attention_selected_layers counter per step: the attention layers
(RingAttention nodes) of a launched train program that take a selection
(select_top_k > 0: an indexer's three operands, each query keeps the keys it
scores highest and the softmax runs over those alone;
mxnet_tpu/parallel/ring_attention.py: selected_attention). 4.0 in
keye-vl2-30b-train-1c (published layers 0-3). 0 is a model that fell back to
dense causal attention (which has fused kernels and would read as a
speed-up), a program without the counter (the parent of PR 51), or a path
that is gone: an alarm, never a gain."""

from benchmark.lib import readers

NAME = "attention.selected_layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.attention_selected_layers")
