"""executor.attention_selected_pairs counter per step: the query-key pairs the
softmax of the selecting attention layers KEEPS, batch x query heads x sum
over positions of min(t + 1, select_top_k), from shapes alone: 4 x 32 x
31 458 304 in keye-vl2-30b-train-1c (rows of 16 384, 2048 keys a query).
Beside attention.scored_pairs_per_step.seq (the pairs of the tiles the main
heads compute, masked after the fact) the ledger shows how much of the causal
triangle the program still walks: the gap is what kernels that take a
selection are for.

0 where the program has no such counter (the parent of PR 51, a graph without
such a layer), as the other counter readers."""

from benchmark.lib import readers

NAME = "attention.selected_pairs_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.attention_selected_pairs")
