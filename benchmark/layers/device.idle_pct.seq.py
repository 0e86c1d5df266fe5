"""1 - union of device-op intervals over the traced slice, device 0."""

from benchmark.lib import readers

NAME = "device.idle_pct.seq"
UNIT = "%"
LAYER = "device"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "device_trace"
read = readers.idle_pct
