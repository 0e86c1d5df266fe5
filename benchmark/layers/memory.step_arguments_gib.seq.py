"""executor.program_argument_bytes at the window's end: the arguments of the
heaviest train program launched (weights, optimizer state, batches; in the
fused update donated and written over in place), as its executable's own
memory_analysis() gave them where aot.AOTProgram._resolve took it in hand.
One device's share. None where the program has no such gauge (a parent of
PR 53, a backend that gives no analysis)."""

from benchmark.lib import harness as hx
from benchmark.lib.readers import GIB

NAME = "memory.step_arguments_gib.seq"
UNIT = "GiB"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    gauge = hx.tm_leaf(run["obs"]["tm1"], "executor.program_argument_bytes")
    return None if gauge is None else gauge["value"] / GIB
