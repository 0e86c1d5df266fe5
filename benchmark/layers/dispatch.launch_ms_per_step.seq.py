"""Host time inside the call into the executable alone (executor.launch span sum / steps)."""

from benchmark.lib import spans

NAME = "dispatch.launch_ms_per_step.seq"
UNIT = "ms"
LAYER = "host dispatch"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_span"
read = spans.window_ms_per_step("executor.launch", "sum")
