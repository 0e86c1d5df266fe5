"""executor.attention_diffusion_layers counter per step: the attention layers
(RingAttention nodes) of a launched train program that run the block-diffusion
mask (diffusion_block > 0: the batch holds a noised and a clean copy of every
row, attention is bidirectional inside a block and causal across blocks, the
noised copy reads the clean one; mxnet_tpu/parallel/ring_attention.py:
diffusion_attention). 4.0 in sdar-30b-a3b-train-1c (published layers 0-3). 0
is a model that fell back to plain causal attention over one copy (half the
trunk rows and a different objective: it would read as a speed-up), or a
program without the counter (the parent of PR 57): an alarm, never a gain."""

from benchmark.lib import readers

NAME = "attention.diffusion_layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.attention_diffusion_layers")
