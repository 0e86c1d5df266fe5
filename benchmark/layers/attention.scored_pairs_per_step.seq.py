"""executor.attention_scored_pairs counter per step: the query-key pairs the
attention layers of a launched train program score, from their block plans
(parallel/ring_attention.scored_pairs x query heads x batch): the score
tiles the blockwise path computes, forward. A window layer left to the full
triangle reads 1.94 times its plan.

0 where the program has no such counter (a tree before PR 32, a graph
without such a layer), as the other counter readers."""

from benchmark.lib import readers

NAME = "attention.scored_pairs_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.attention_scored_pairs")
