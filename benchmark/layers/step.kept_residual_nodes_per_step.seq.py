"""executor.kept_residual_nodes counter per step: the op nodes of a launched
train program whose per-operator recomputation (MXNET_BACKWARD_DO_MIRROR=1)
kept a residual the operator named (mxnet_tpu/ops/registry.py: keep), so
that backward does not run the operator's forward again to rebuild it. One
a GatedDeltaRule, RingAttention and MoE node: 8.0 in the Qwen3-Next cell
(3 + 1 + 4), 9.0 in the Trinity cell (5 + 4). 0 is a program whose
recomputation keeps nothing (the parent of PR 39, or a later change that
took the policy or the marks away) and wherever the switch is off."""

from benchmark.lib import readers

NAME = "step.kept_residual_nodes_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.kept_residual_nodes")
