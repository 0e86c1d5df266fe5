"""Dispatched steps not yet finished, observed after every dispatch (fit.steps_in_flight mean): how far the host runs ahead."""

from benchmark.lib import spans

NAME = "dispatch.steps_in_flight.fit"
UNIT = "steps"
LAYER = "host dispatch"
MOVES = "train_samples_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = spans.window_mean("fit.steps_in_flight")
