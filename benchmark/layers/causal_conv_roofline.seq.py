"""The ``CausalConv1D`` operator's share of its roofline: the least work of
the short causal convolutions (``operator_work`` of the cell's builder:
every tap's product forward once and backward twice, the rows between them
across HBM once each way) over the peaks, divided by ALL the device time
under the operator's name. Bound by bytes at two taps. None, and left out,
where the cell's builder counts no such work (the Qwen3-Next builder) or the
trace has no row of that name (a tree before PR 36)."""

from benchmark.lib import readers

NAME = "causal_conv_roofline.seq"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "device_trace"
read = readers.operator_roofline_pct("CausalConv1D")
