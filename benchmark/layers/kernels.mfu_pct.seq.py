"""Model FLOPs x rate over chips x peak bf16 FLOP/s: end-to-end utilisation."""

from benchmark.lib import readers

NAME = "kernels.mfu_pct.seq"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "host_clock"
read = readers.mfu_pct
