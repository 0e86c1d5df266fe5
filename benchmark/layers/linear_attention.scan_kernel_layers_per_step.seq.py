"""executor.linear_attention_scan_kernel_layers counter per step: the
linear-attention layers (GatedDeltaRule nodes) of a launched train program
whose scan over chunks runs in the Pallas kernels
(mxnet_tpu/ops/gated_delta_kernels.py: gated_delta_scan_fwd / _bwd), in which
a head's float32 state stays in VMEM from a row's first chunk to its last.
1.0 a layer on one TPU with a bfloat16 trunk and heads of 128; 0 is a program
whose scan is a lax.scan (the parent of PR 42, or a later change that
silently falls back)."""

from benchmark.lib import readers

NAME = "linear_attention.scan_kernel_layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.linear_attention_scan_kernel_layers")
