"""executor.conv_kernel_layers counter per step: the CausalConv1D nodes of a
launched train program that run in the Pallas kernels
(mxnet_tpu/ops/causal_conv_kernels.py: one kernel forward and one backward,
each (B, T, C) array across HBM once a pass). 1.0 a depthwise convolution on
one TPU with a bfloat16 trunk whose channels 128 divides and whose data is at
least half the chip's VMEM: 3.0 in the Qwen3-Next cell (its three Gated
DeltaNet layers, rows of 128 MiB). 0 is a program on the jax.numpy form: the
parent of PR 46, which has no such counter; the zaya1-8b cell, whose first
convolutions' rows of 20 MiB are under the rule's size (XLA holds them in
VMEM between its fusions; the second convolutions mix channels inside groups
and never take the kernels); or a later change that silently falls back."""

from benchmark.lib import readers

NAME = "conv.kernel_layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.conv_kernel_layers")
