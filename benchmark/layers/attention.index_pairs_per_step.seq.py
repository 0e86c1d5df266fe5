"""executor.attention_index_pairs counter per step: the query-key pairs the
indexers of the selecting attention layers score, batch x index heads x
T (T + 1) / 2 (every earlier key of every query, each of the indexer's heads
against its ONE key): 4 x 16 x 134 225 920 in keye-vl2-30b-train-1c. The
indexer's least work is this x its head width x 2 FLOPs forward.

0 where the program has no such counter (the parent of PR 51, a graph without
such a layer), as the other counter readers."""

from benchmark.lib import readers

NAME = "attention.index_pairs_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.attention_index_pairs")
