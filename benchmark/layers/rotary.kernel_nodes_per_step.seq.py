"""executor.rotary_kernel_nodes counter per step: the RotaryEmbedding nodes of
a launched train program that run in the Pallas kernel
(mxnet_tpu/ops/rotary_kernels.py: one kernel, forward and, with the sine's
sign changed, backward; the array across HBM once a pass). 1.0 a node on one
TPU whose bfloat16 data is at least half the chip's VMEM and whose heads of
128 turn whole in rotate-half pairs: 4.0 in the SDAR and Keye-VL-2.0 cells
(their four layers' queries, 128 MiB each). 0 is a program on the jax.numpy
form: the parent of PR 59, which has no such counter; the cells whose nodes
are under the rule's size (the keys everywhere, the queries of the OLMoE,
Trinity and Ouro cells: XLA holds them in VMEM between its fusions), turn part
of a head (Qwen3-Next, ZAYA1) or neighbouring pairs of a head of 64
(kanana-2); or a later change that silently falls back."""

from benchmark.lib import readers

NAME = "rotary.kernel_nodes_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.rotary_kernel_nodes")
