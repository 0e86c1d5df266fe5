"""Seconds inside Module.bind itself, every bucket's bind counted (module.bind self time)."""

from benchmark.lib import spans

NAME = "setup.bind_s"
UNIT = "s"
LAYER = "module set-up"
MOVES = "setup_s"
BETTER = "lower"
SOURCE = "program_span"
read = spans.setup_part(NAME)
