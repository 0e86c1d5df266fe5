"""Seconds of `import mxnet_tpu`, jax's import included (startup.import span)."""

from benchmark.lib import spans

NAME = "setup.import_s"
UNIT = "s"
LAYER = "process start-up"
MOVES = "setup_s"
BETTER = "lower"
SOURCE = "program_span"
read = spans.setup_part(NAME)
