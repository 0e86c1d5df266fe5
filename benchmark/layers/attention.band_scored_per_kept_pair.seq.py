"""executor.attention_band_scored_pairs over
executor.attention_band_kept_pairs, window layers only: the query-key pairs
their tiles score (the kernels' visit list at the plan's tiles, or the
jax.numpy blocks'; forward) over the pairs their band keeps (exactly, query t
its min(t + 1, window)). 1.0 is no masked work; what is above it is what the
band's two edges cost, since a tile an edge cuts is scored whole: 1.25 at
tiles of 256 queries x 128 keys under a band of 1024 keys at T 16 384
(mellum2-12b-train-1c), where 8.5 would be the causal triangle scored and
masked, the band not skipped at all; 1.125 at 256 x 256 under a band of 2048
at T 4096 (trinity-mini-train-1c, the other cell with window layers). A full
layer adds to neither counter. Lower is better.

0 where the program has no such counter (a tree before PR 62) or the window
launched no window layer, as the other counter readers."""

from benchmark.lib.harness import tm_delta

NAME = "attention.band_scored_per_kept_pair.seq"
UNIT = "ratio"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    o = run["obs"]
    kept = tm_delta(o["tm0"], o["tm1"], "executor.attention_band_kept_pairs")
    scored = tm_delta(o["tm0"], o["tm1"],
                      "executor.attention_band_scored_pairs")
    return scored / kept if kept else 0.0
