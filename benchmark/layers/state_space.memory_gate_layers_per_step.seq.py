"""The Gated Memory Units of the program's graph, counted from the symbol the
cell binds (the builder's ``graph_counts``: products of a SelectiveScan's
output, before its own gate, with rows a layer ABOVE projected:
silu(W_1 u) * M). 1.0 in the phi4-mini-flash cell (published layer 18 reading
layer 16's scan). Every step launches the one program, so a count of its
graph is a count a step.

Nothing where the configuration's builder counts no such thing."""

NAME = "state_space.memory_gate_layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"


def read(run):
    counts = getattr(run["builder"], "graph_counts", None)
    return None if counts is None else float(
        counts(run["config"], run["mx"])["memory_gate_layers"])
