"""executor.published_grad_bytes at the window's end: the gradients the
heaviest train program returns to grad_dict, from the shapes of the
arguments a gradient is taken of; 0 where update()'s default found one set
over an eighth of the device and left them out (Executor._grads_crowd_device)
or the caller said publish_grads=False. Part of memory.step_kept_outputs_gib.*,
once a generation. None where the program has no such gauge."""

from benchmark.lib import harness as hx
from benchmark.lib.readers import GIB

NAME = "memory.published_grads_gib.seq"
UNIT = "GiB"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    gauge = hx.tm_leaf(run["obs"]["tm1"], "executor.published_grad_bytes")
    return None if gauge is None else gauge["value"] / GIB
