"""executor.attention_kernel_layers counter per step: the attention layers of
a launched train program that run the fused Pallas kernels
(mxnet_tpu/ops/flash_attention.py), in which no score tile reaches HBM. 1.0
a RingAttention layer on one TPU with a bfloat16 trunk; 0 is a program on the
jax.numpy blocks (the parent of PR 33, or a later change that silently falls
back)."""

from benchmark.lib import readers

NAME = "attention.kernel_layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.attention_kernel_layers")
