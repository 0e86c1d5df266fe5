"""executor.moe_one_round_layers counter per step: the MoE layers of a
launched train program that trace no loop over rounds of held assignments:
every expert is held, or the one round ``held_round_rows`` gives holds every
assignment (4.0 in the ZAYA1 cell, 1.0 a layer of OLMoE). 0 a layer is a held
range of several rounds, whose further rounds sit under a branch; a program
with no such counter (the parent of PR 56) reads 0."""

from benchmark.lib import readers

NAME = "moe.one_round_layers_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.moe_one_round_layers")
