"""Seconds in lowered.compile() or a cache read, as the program times them (executor.compile span); beside compile.setup_compile_s, which jax reports."""

from benchmark.lib import spans

NAME = "setup.compile_or_load_s"
UNIT = "s"
LAYER = "compile and cache"
MOVES = "setup_s"
BETTER = "lower"
SOURCE = "program_span"
read = spans.setup_part(NAME)
