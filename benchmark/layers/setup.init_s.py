"""Seconds inside init_params and init_optimizer themselves (self time of both spans)."""

from benchmark.lib import spans

NAME = "setup.init_s"
UNIT = "s"
LAYER = "module set-up"
MOVES = "setup_s"
BETTER = "lower"
SOURCE = "program_span"
read = spans.setup_part(NAME)
