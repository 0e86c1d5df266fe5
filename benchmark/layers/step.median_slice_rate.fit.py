"""Median of the fenced slices' rates: the steady step, which one stalled
slice does not move; train_samples_per_s is the whole window, which it does."""

from benchmark.lib import readers

NAME = "step.median_slice_rate.fit"
UNIT = "samples/s"
LAYER = "fused step"
MOVES = "train_samples_per_s"
BETTER = "higher"
SOURCE = "host_clock"
read = readers.median_slice_rate
