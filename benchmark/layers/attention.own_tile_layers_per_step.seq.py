"""executor.attention_own_tile_layers counter per step: the block-diffusion
attention layers (RingAttention(diffusion_block > 0) nodes) of a launched
train program whose noised copy scores its own blocks INSIDE the fused
kernels: one more masked tile of the strict walk's attention_fwd /
attention_bwd (mxnet_tpu/ops/flash_attention.py: _fwd / _bwd, own=), so a
noised row's softmax is formed once over both copies. 4.0 in
sdar-30b-a3b-train-1c (every layer, where the rule gives kernels). 0 is the
jax.numpy little squares and their log-sum-exp join around the kernels
(17.8 ms a step of that cell at PR 60), or a program without the counter (the
parent of PR 61): an alarm, never a gain."""

from benchmark.lib import readers

NAME = "attention.own_tile_layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.attention_own_tile_layers")
