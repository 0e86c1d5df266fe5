"""The readers behind the per-layer metrics, kept once.

Each takes the ``run`` dict a driver filled (``run["obs"]``) and returns a
number, or None where there is nothing to read; each file under
``benchmark/layers/`` binds one metric name to one of these.
"""

from __future__ import annotations

from benchmark.lib.harness import tm_delta

GIB = float(1 << 30)


def span_ms_per_step(span):
    """Summed duration of a telemetry span (microseconds) over the window,
    per step, in milliseconds."""
    def read(run):
        o = run["obs"]
        return tm_delta(o["tm0"], o["tm1"], span, "sum") / 1e3 / o["steps"]
    return read


def counter_per_step(name):
    def read(run):
        o = run["obs"]
        return tm_delta(o["tm0"], o["tm1"], name) / o["steps"]
    return read


def host_syncs_per_step(run):
    """``ndarray.asnumpy`` + ``ndarray.wait_to_read`` inside the window,
    less the harness's own, per step: an exact count."""
    o = run["obs"]
    return o["program_syncs"] / o["steps"]


def pad_waste_pct(run):
    o = run["obs"]
    if "all_tokens" not in o:
        return None
    return 100.0 * o["pad_tokens"] / o["all_tokens"]


def step_device_ms(run):
    """Device busy time of the traced slice over its steps (chip mean)."""
    o = run["obs"]
    return 1e3 * o["trace"]["busy_s"] / o["trace_slice"][0]


def collective_ms_per_step(run):
    o = run["obs"]
    if o["chips"] < 2:
        return None
    return 1e3 * o["trace"]["collective_s_device0"] / o["trace_slice"][0]


def setup_compile_s(run):
    return run["obs"]["setup_compile_s"]


def median_slice_rate(run):
    return run["obs"]["median_slice_rate"]


def mfu_pct(run):
    """Model FLOPs (3 x forward, no recomputation) x the whole-window rate
    over chips x peak: an end-to-end utilisation, not a kernel's roofline
    share."""
    o = run["obs"]
    return 100.0 * o["flops_per_unit"] * o["rate"] / (
        o["chips"] * o["peak_flops"])


def idle_pct(run):
    return 100.0 * run["obs"]["trace"]["idle_share_device0"]


def peak_hbm_gib(run):
    """``peak_bytes_in_use + peak_bytes_reserved`` of the fullest chip: see
    ``harness.device_stamp``; the result line's ``device`` has both parts."""
    return run["obs"]["memory_peak_bytes"] / GIB
