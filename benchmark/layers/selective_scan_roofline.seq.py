"""The ``SelectiveScan`` operator's share of its roofline: the least work of
the state-space recurrences (``operator_work`` of the cell's builder: 9
FLOPs a token, channel and state forward and twice that backward; x, dt, y,
B and C across HBM once forward and, with their gradients, twice backward)
over the peaks, divided by ALL the device time under the operator's name
(the kernels, the broadcast of B and C over lanes and the sums of their
gradients alike). The count's binding side is bytes (0.74 MFLOP against 31
KB a token and layer); the kernels' is the VPU, so the share reads low.
None, and left out, where the cell's builder counts no such work or the
trace has no row of that name (a tree before PR 65)."""

from benchmark.lib import readers

NAME = "selective_scan_roofline.seq"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "device_trace"
read = readers.operator_roofline_pct("SelectiveScan")
