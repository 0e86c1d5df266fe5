"""Pad positions over all positions of the batches retired, counted by the benchmark."""

from benchmark.lib import readers

NAME = "input.pad_waste_pct.seq"
UNIT = "%"
LAYER = "input plane"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_counter"
read = readers.pad_waste_pct
