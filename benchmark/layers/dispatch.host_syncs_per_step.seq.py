"""Counted host syncs (asnumpy + wait_to_read) per step, the harness's own fences taken out."""

from benchmark.lib import readers

NAME = "dispatch.host_syncs_per_step.seq"
UNIT = "1/step"
LAYER = "host dispatch"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_counter"
read = readers.host_syncs_per_step
