"""Host time of forward_backward + update (fit.dispatch span), per step."""

from benchmark.lib import readers

NAME = "dispatch.host_ms_per_step.seq"
UNIT = "ms"
LAYER = "host dispatch"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_span"
read = readers.span_ms_per_step("fit.dispatch")
