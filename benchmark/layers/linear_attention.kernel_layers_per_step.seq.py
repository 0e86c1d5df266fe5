"""executor.linear_attention_kernel_layers counter per step: the
linear-attention layers (GatedDeltaRule nodes) of a launched train program
whose chunk-local algebra runs in the Pallas kernels
(mxnet_tpu/ops/gated_delta_kernels.py), in which a chunk's 64 x 64 float32
triangular system never leaves VMEM. 1.0 a layer on one TPU with a bfloat16
trunk and heads of 128; 0 is a program on the jax.numpy form (the parent of
PR 35, or a later change that silently falls back)."""

from benchmark.lib import readers

NAME = "linear_attention.kernel_layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.linear_attention_kernel_layers")
