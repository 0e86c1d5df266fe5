"""executor.scoped_nodes counter per step: the op nodes a launched train
program lowered under their own ``Operator[node]`` scope, which is what the
trace's by-operator table is read from. The graph's op-node count (the mean
over the buckets a pass visits where there are several); 0 is the alarm
that a path evaluates the graph with no names. A program from before the
counter reads 0."""

from benchmark.lib import readers

NAME = "step.scoped_nodes_per_step.seq"
UNIT = "1/step"
LAYER = "fused step"
MOVES = "train_tokens_per_s"
BETTER = "higher"
SOURCE = "program_counter"
read = readers.counter_per_step("executor.scoped_nodes")
