"""Seconds of jaxpr tracing and MLIR lowering of the program's steps (executor.trace_lower span)."""

from benchmark.lib import spans

NAME = "setup.trace_lower_s"
UNIT = "s"
LAYER = "compile and cache"
MOVES = "setup_s"
BETTER = "lower"
SOURCE = "program_span"
read = spans.setup_part(NAME)
