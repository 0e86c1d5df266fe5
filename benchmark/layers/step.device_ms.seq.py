"""Device busy time of the traced slice over its steps."""

from benchmark.lib import readers

NAME = "step.device_ms.seq"
UNIT = "ms"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "device_trace"
read = readers.step_device_ms
