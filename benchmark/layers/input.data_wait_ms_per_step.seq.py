"""Host time fit spent waiting for the next batch (fit.data_wait span), per step."""

from benchmark.lib import readers

NAME = "input.data_wait_ms_per_step.seq"
UNIT = "ms"
LAYER = "input plane"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_span"
read = readers.span_ms_per_step("fit.data_wait")
