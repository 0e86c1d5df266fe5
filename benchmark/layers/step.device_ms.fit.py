"""Device busy time of the traced slice over its steps."""

from benchmark.lib import readers

NAME = "step.device_ms.fit"
UNIT = "ms"
LAYER = "fused step"
MOVES = "train_samples_per_s"
BETTER = "lower"
SOURCE = "device_trace"
read = readers.step_device_ms
