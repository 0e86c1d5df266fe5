"""What an iteration of fit's loop keeps for itself, between its phases (fit.step self time / steps)."""

from benchmark.lib import spans

NAME = "loop.self_ms_per_step.seq"
UNIT = "ms"
LAYER = "host dispatch"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_span"
read = spans.window_ms_per_step("fit.step", "self_sum")
