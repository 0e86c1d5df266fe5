"""peak_bytes_in_use + peak_bytes_reserved (the loaded programs' scratch)
after the window, fullest chip; both parts are in the result line."""

from benchmark.lib import readers

NAME = "device.peak_hbm_gib.seq"
UNIT = "GiB"
LAYER = "device"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_counter"
read = readers.peak_hbm_gib
