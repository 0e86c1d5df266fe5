"""symbol.infer_eval at the fence that ends warm-up: the abstract evaluations of operators (shapes and types) the program
really ran since process start, every bucket's bind and the harness's own inference counted; None where the program has
no such counter (a program older than the inference memo)."""

from benchmark.lib.harness import tm_leaf

NAME = "setup.infer_evals"
UNIT = "1"
LAYER = "module set-up"
MOVES = "setup_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    leaf = tm_leaf(run["obs"]["tm0"], "symbol.infer_eval")
    return None if leaf is None or isinstance(leaf, dict) else leaf
