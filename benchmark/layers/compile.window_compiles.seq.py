"""Programs the executor traced, lowered and compiled (or read from a cache)
INSIDE the measured window: the window's change of the ``count`` of the
program's ``executor.trace_lower`` span (``aot.py``: every program an
``AOTProgram`` resolves). Expected 0: every shape is warm before the
window. It does not see ``metric.py``'s eager programs nor the harness's
own ``jit(loss)``, which jax compiles without the executor; the window
line's ``compile_events_in_window`` counts those from jax's own events."""

from benchmark.lib.harness import tm_delta

NAME = "compile.window_compiles.seq"
UNIT = "1"
LAYER = "compile and cache"
MOVES = "train_tokens_per_s"
BETTER = "lower"
SOURCE = "program_span"


def read(run):
    o = run["obs"]
    return tm_delta(o["tm0"], o["tm1"], "executor.trace_lower", "count")
