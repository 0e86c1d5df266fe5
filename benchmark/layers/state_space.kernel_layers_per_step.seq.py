"""executor.selective_scan_kernel_layers counter per step: the SelectiveScan
nodes of a launched train program that run in the Pallas kernels
(mxnet_tpu/ops/selective_scan.py: one kernel forward and one backward, the
state in VMEM from a row's first position to its last, no (T, channels,
states) array across HBM). 1.0 a node on one TPU with a bfloat16 trunk whose
channels 128 divides and whose states are 8 or 16 a channel: 2.0 in the
phi4-mini-flash cell. 0 is a program on the jax.numpy form (an associative
scan a chunk: (64, 5120, 16) float32 arrays, several, a chunk), or a later
change that silently falls back."""

from benchmark.lib import readers

NAME = "state_space.kernel_layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.selective_scan_kernel_layers")
