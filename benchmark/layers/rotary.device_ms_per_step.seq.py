"""Device time a step of everything the traced slice books under the
operator ``RotaryEmbedding``: every row of the table by operator and pass
with that name (forward, backward and recompute; kernels, glue and copies
alike), read as ``readers.operator_roofline`` reads an operator's seconds.
No roofline share: the operator has no model FLOPs and ``lib/flops.py``
rightly counts none, so its cost is read in milliseconds. 0.0 where the
table has no row of that name (a model without the operator; the canned
table of ``benchmark/tests``, whose cells' tests want a value of every
metric); None, and left out, where the run has no table by operator to
read."""

NAME = "rotary.device_ms_per_step.seq"
UNIT = "ms"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "device_trace"
OPERATOR = "RotaryEmbedding"


def read(run):
    o = run["obs"]
    rows = ((o.get("trace") or {}).get("table") or {}).get("by_operator")
    if rows is None:
        return None
    return sum(r["ms"] for r in rows
               if r["operator"] == OPERATOR) / o["trace_slice"][0]
