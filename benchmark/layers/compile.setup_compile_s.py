"""jax backend_compile_duration summed inside set-up (a cache read on a hit)."""

from benchmark.lib import readers

NAME = "compile.setup_compile_s"
UNIT = "s"
LAYER = "compile and cache"
MOVES = "setup_s"
BETTER = "lower"
SOURCE = "program_span"
read = readers.setup_compile_s
