"""The attention layers of the program's graph that project a query only and
read ANOTHER layer's keys and values, counted from the symbol the cell binds
(the builder's ``graph_counts``: ``RingAttention`` pairs whose keys descend
from another norm than their queries): the cross-decoder's cross-attention
layers of a SambaY model. 1.0 in the phi4-mini-flash cell (published layer 19
reading layer 17's). 0 there is the alarm that a cross layer was rewritten to
project keys and values of its own. Every step launches the one program, so a
count of its graph is a count a step.

Nothing where the configuration's builder counts no such thing."""

NAME = "attention.shared_kv_layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"


def read(run):
    counts = getattr(run["builder"], "graph_counts", None)
    return None if counts is None else float(
        counts(run["config"], run["mx"])["shared_kv_layers"])
