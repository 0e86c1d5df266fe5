"""executor.program_temp_bytes + executor.program_code_bytes at the window's
end: the heaviest train program's XLA temporaries (activations, the
residuals an operator keeps, kernels' scratch) and its generated code, which
the TPU holds reserved while the program is loaded. None where the program
has no such gauges."""

from benchmark.lib import harness as hx
from benchmark.lib.readers import GIB

NAME = "memory.step_scratch_gib.seq"
UNIT = "GiB"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    tm1 = run["obs"]["tm1"]
    parts = [hx.tm_leaf(tm1, "executor.program_temp_bytes"),
             hx.tm_leaf(tm1, "executor.program_code_bytes")]
    if None in parts:
        return None
    return sum(g["value"] for g in parts) / GIB
