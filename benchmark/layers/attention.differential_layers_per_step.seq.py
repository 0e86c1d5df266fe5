"""The differential attention layers of the program's graph, counted from
the symbol the cell binds (the builder's ``graph_counts``: two RingAttention
nodes over one layer's queries and the SAME values, which the graph
subtracts). 3.0 in the phi4-mini-flash cell (the window layer, the full
layer, the cross layer), whose attention.kernel_layers_per_step.seq reads
6.0: two nodes a layer. Every step launches the one program, so a count of
its graph is a count a step.

Nothing where the configuration's builder counts no such thing."""

NAME = "attention.differential_layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"


def read(run):
    counts = getattr(run["builder"], "graph_counts", None)
    return None if counts is None else float(
        counts(run["config"], run["mx"])["differential_layers"])
