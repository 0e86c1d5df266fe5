"""Seconds the loop's own spans kept for themselves before the window: warm-up steps and their fences (self time of fit.step and its phases)."""

from benchmark.lib import spans

NAME = "setup.warmup_steps_s"
UNIT = "s"
LAYER = "host dispatch"
MOVES = "setup_s"
BETTER = "lower"
SOURCE = "program_span"
read = spans.setup_part(NAME)
