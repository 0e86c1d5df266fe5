"""The ``ExitSoftmaxOutput`` operator's share of its roofline: every exit's
logits read once, the last exit's softmax written once and every exit's
gradient written once (``operator_work`` of the cell's builder: bytes, no
product) over the peaks, divided by ALL the device time under the operator's
name."""

from benchmark.lib import readers

NAME = "exit_loss_roofline.seq"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "device_trace"
read = readers.operator_roofline_pct("ExitSoftmaxOutput")
