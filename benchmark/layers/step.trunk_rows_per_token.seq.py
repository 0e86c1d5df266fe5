"""The rows the trunk reads for every token position a block-diffusion step
trains on: executor.diffusion_trunk_rows (the rows x positions of the queries
each RingAttention node under diffusion_block is HANDED, both copies) over
executor.attention_diffusion_layers (those nodes) and over
executor.diffusion_noised_rows (the positions BlockDiffusionNoise noises).
Each operator counts what it sees, so the number is the model's as built: 2.0
in sdar-30b-a3b-train-1c, a noised copy and a clean one; a builder that fed
the trunk one copy, or three, would read 1 or 3. It says what a token costs
there: train_tokens_per_s counts clean tokens, and every projection, norm and
expert sees two rows for each. Lower is better.

0 where the program has none of the counters (a tree before PR 57), as the
other counter readers."""

from benchmark.lib.harness import tm_delta

NAME = "step.trunk_rows_per_token.seq"
UNIT = "rows/token"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    o = run["obs"]
    noised = tm_delta(o["tm0"], o["tm1"], "executor.diffusion_noised_rows")
    trunk = tm_delta(o["tm0"], o["tm1"], "executor.diffusion_trunk_rows")
    layers = tm_delta(o["tm0"], o["tm1"],
                      "executor.attention_diffusion_layers")
    if not (noised and layers):
        return 0.0
    return trunk / (layers / o["steps"]) / noised
