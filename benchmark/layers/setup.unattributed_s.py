"""setup_s less the cell's other setup.* metrics: what no span of the program covers."""

from benchmark.lib import spans

NAME = "setup.unattributed_s"
UNIT = "s"
LAYER = "process start-up"
MOVES = "setup_s"
BETTER = "lower"
SOURCE = "program_span"
read = spans.setup_unattributed
