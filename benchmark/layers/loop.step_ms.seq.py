"""One whole iteration of fit's loop, callback included (fit.step span sum / steps): times the window's steps it is the window's seconds."""

from benchmark.lib import spans

NAME = "loop.step_ms.seq"
UNIT = "ms"
LAYER = "host dispatch"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_span"
read = spans.window_ms_per_step("fit.step", "sum")
