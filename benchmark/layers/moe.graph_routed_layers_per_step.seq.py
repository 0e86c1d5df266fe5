"""executor.moe_graph_routed_layers counter per step: the MoE layers of a
launched train program whose router logits are an input the graph computed
(``MoE(router="graph")``: ZAYA1's MLP router with its state carried down the
layers) and not a product inside the operator. 4.0 in the zaya1-8b cell; 0
there is the alarm that the router fell back into the operator.

0 where the program has no such counter (a tree before PR 44, a graph
without such a layer), as the other counter readers."""

from benchmark.lib import readers

NAME = "moe.graph_routed_layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.moe_graph_routed_layers")
