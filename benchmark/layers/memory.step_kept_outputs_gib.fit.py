"""executor.program_kept_output_bytes at the window's end: the heaviest train
program's outputs less the ones that reuse a donated argument, so what ONE
launch allocates anew and its caller keeps (published gradients, the loss
head's probabilities). ONE generation: the launch before's, still alive
while this one runs, is in memory.unattributed_gib.*. None where the
program has no such gauge."""

from benchmark.lib import harness as hx
from benchmark.lib.readers import GIB

NAME = "memory.step_kept_outputs_gib.fit"
UNIT = "GiB"
LAYER = "fused step"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    gauge = hx.tm_leaf(run["obs"]["tm1"], "executor.program_kept_output_bytes")
    return None if gauge is None else gauge["value"] / GIB
