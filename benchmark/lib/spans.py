"""Readers of the program's own spans (``mxnet_tpu.telemetry``), for the
set-up split and the loop metrics.

A span's histogram holds ``sum`` (microseconds) and ``self_sum``: the
duration less what its child spans covered. Set-up metrics read the first
snapshot alone (``run["obs"]["tm0"]``, taken at the fence that ends warm-up:
totals since process start); loop metrics read ``tm1 - tm0`` over the
window's steps. Where the program has no such span, as a program older than
the spans has not, every reader returns None and the metric is left out.
"""

from __future__ import annotations

from benchmark.lib.harness import tm_leaf

LOOP_SPANS = ("fit.step", "fit.dispatch", "fit.data_wait", "fit.metric",
              "fit.callback", "executor.stage_args", "executor.launch")

# metric -> (spans, field): the parts of set-up that the program names
SETUP_PARTS = {
    "setup.import_s": (("startup.import",), "sum"),
    "setup.input_build_s": (("rnn.bucket_iter_build",), "sum"),
    "setup.bind_s": (("module.bind",), "self_sum"),
    "setup.init_s": (("module.init_params", "module.init_optimizer"),
                     "self_sum"),
    "setup.trace_lower_s": (("executor.trace_lower",), "sum"),
    "setup.compile_or_load_s": (("executor.compile",), "sum"),
    # self times partition what the spans cover: a bucket bound or a program
    # compiled inside a step is counted once, in its own row
    "setup.warmup_steps_s": (LOOP_SPANS, "self_sum"),
}


def field_of(snapshot, span, field):
    """``field`` of the histogram ``span`` in a snapshot, or None where the
    program recorded no such span or no such field."""
    leaf = tm_leaf(snapshot, span)
    if not isinstance(leaf, dict) or field not in leaf:
        return None
    return leaf[field]


def total(snapshot, spans, field):
    """Sum of ``field`` over those of ``spans`` the snapshot holds; None
    where it holds none."""
    found = [v for v in (field_of(snapshot, s, field) for s in spans)
             if v is not None]
    return sum(found) if found else None


def setup_part(metric):
    """Seconds, since process start, of one part of set-up."""
    spans, field = SETUP_PARTS[metric]

    def read(run):
        us = total(run["obs"]["tm0"], spans, field)
        return None if us is None else us / 1e6
    return read


def setup_unattributed(run):
    """``setup_s`` less the parts above that the cell reports: process start
    before the import, the harness's own work, the iteration open at the
    fence, whatever no span covers."""
    listed = run["bench"].get("per_layer")
    reported = None if listed is None else {
        m["name"] for m in listed
        if run["cell"]["name"] in m.get("workloads", [run["cell"]["name"]])}
    parts = [setup_part(m)(run) for m in SETUP_PARTS
             if reported is None or m in reported]
    parts = [p for p in parts if p is not None]
    if not parts:
        return None
    return run["setup_s"] - sum(parts)


def window_delta(run, name, field):
    """Growth of ``field`` of the histogram ``name`` over the window, or
    None where the program has no such histogram."""
    o = run["obs"]
    after = field_of(o["tm1"], name, field)
    if after is None:
        return None
    return after - (field_of(o["tm0"], name, field) or 0)


def window_ms_per_step(span, field):
    """``field`` of ``span`` over the window, per step, in milliseconds."""
    def read(run):
        us = window_delta(run, span, field)
        return None if us is None else us / 1e3 / run["obs"]["steps"]
    return read


def window_mean(name):
    """Mean of what the histogram ``name`` observed inside the window."""
    def read(run):
        count = window_delta(run, name, "count")
        if not count:
            return None
        return window_delta(run, name, "sum") / count
    return read
