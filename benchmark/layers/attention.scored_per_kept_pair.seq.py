"""executor.attention_scored_pairs over executor.attention_kept_pairs: the
query-key pairs the block-diffusion layers' walks score (the tiles their
visit lists reach, forward) over the pairs the mask keeps (exactly L (L + Bd)
a head and row). 1.0 is no masked work; what is above it is the far side of
the tiles the block-cut diagonal crosses; about 2 is a causal triangle walked
over both copies of a row, about 4 the square over them: the number that says
whether the tiles the mask empties are skipped. Lower is better.

0 where the program has no such counter (a tree before PR 57) or the window
launched no diffusion layer, as the other counter readers."""

from benchmark.lib.harness import tm_delta

NAME = "attention.scored_per_kept_pair.seq"
UNIT = "ratio"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    o = run["obs"]
    kept = tm_delta(o["tm0"], o["tm1"], "executor.attention_kept_pairs")
    scored = tm_delta(o["tm0"], o["tm1"], "executor.attention_scored_pairs")
    return scored / kept if kept else 0.0
