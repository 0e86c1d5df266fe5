"""Summed all-reduce time on device 0 in the traced slice, per step; cells on several chips only."""

from benchmark.lib import readers

NAME = "collective.device_ms_per_step.fit"
UNIT = "ms"
LAYER = "data parallel"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"
read = readers.collective_ms_per_step
