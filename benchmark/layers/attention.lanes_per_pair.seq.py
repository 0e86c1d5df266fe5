"""executor.attention_pair_lanes over executor.attention_layers: the lanes
one query-key pair is computed over, a layer: the width its score contracts
over plus the width p.v writes, as the path that was taken is handed them.
320 = 192 + 128 in the kanana2-30b cell as published; 384 if the keys were
padded to 256, 512 if the values were too. Lower is better.

0 where the program has neither counter (a tree before PR 41) or the window
launched no attention layer, as the other counter readers."""

from benchmark.lib.harness import tm_delta

NAME = "attention.lanes_per_pair.seq"
UNIT = "lanes"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    o = run["obs"]
    layers = tm_delta(o["tm0"], o["tm1"], "executor.attention_layers")
    lanes = tm_delta(o["tm0"], o["tm1"], "executor.attention_pair_lanes")
    return lanes / layers if layers else 0.0
