"""executor.shared_weight_reads counter per step: the op nodes of a launched
train program that read a parameter some other node reads too, each of which
casts the float32 master where it uses it and adds its gradient to the
others' in float32 (``total_ut_steps`` x (11 x layers + 3) in
``ouro-2.6b-train-1c``: 7 projections and 4 norms a layer application, a
final norm, a head and a gate a pass). Fewer is a pass that no longer reads
the one stack."""

from benchmark.lib import readers

NAME = "step.shared_weight_reads_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.shared_weight_reads")
