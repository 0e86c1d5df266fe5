"""Reduction from a profiler trace (``.xplane.pb``) to busy time, idle
share, the longest device operations and the longest idle gaps.

The interval arithmetic is pure and takes plain lists, so it is tested
without a trace; :func:`load` is the thin part that reads the file with
``jax.profiler.ProfileData``. ``telemetry.kernel_table`` in the program
divides by *attributed* device time and so cannot give an idle share; this
divides by the window.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
COLLECTIVE_WORDS = ("all-reduce", "all_reduce", "allreduce", "all-gather",
                    "all_gather", "reduce-scatter", "reduce_scatter",
                    "collective-permute", "collective_permute", "all-to-all")


def merge(intervals):
    """Union of [(start, end)] as a sorted list of disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy(intervals, lo, hi):
    """Length of the union of ``intervals`` inside [lo, hi]."""
    return sum(e - s for s, e in merge(clip(intervals, lo, hi)))


def gaps(intervals, lo, hi):
    """The idle intervals of [lo, hi]: what the union leaves uncovered."""
    out, at = [], lo
    for s, e in merge(clip(intervals, lo, hi)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def attribute(gap, spans):
    """Name of the innermost (shortest) host span that covers the gap's
    midpoint; ``spans`` is [(name, start, end)]."""
    mid = (gap[0] + gap[1]) / 2.0
    best = None
    for name, s, e in spans:
        if s <= mid <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "unattributed"


def top_ops(named, lo, hi, n=10):
    """[(name, seconds)] of the ``n`` operations with most summed time
    inside [lo, hi]; ``named`` is [(name, start, end)] in seconds."""
    total = {}
    for name, s, e in named:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            total[name] = total.get(name, 0.0) + d
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]


class ClocksDisagree(ValueError):
    """The span that marks the traced slice does not hold the device
    operations: host and device clocks of this trace cannot be compared."""


def reduce(device_ops, host_spans, window_span, n=10, covered=0.98):
    """Metrics of one traced slice, or None where no device operation was
    traced.

    ``device_ops``: {device id: [(name, start, end)]}, seconds;
    ``host_spans``: [(name, start, end)], seconds on the same clock;
    ``window_span``: the name of the host span that marks the slice, from
    the fence before its first dispatch to the fence after its last. The
    slice starts and ends on a drained device, so that span holds every
    traced operation but for the clocks' offset (about 1 ms on the v5e);
    where it holds less than ``covered`` of their time, the run fails with
    :class:`ClocksDisagree` and no metric is read from a window of another
    kind.
    """
    if not any(device_ops.values()):
        return None
    marks = [(s, e) for name, s, e in host_spans if name == window_span]
    if not marks:
        raise ClocksDisagree(f"the trace holds no span {window_span!r}")
    window = max(marks, key=lambda m: m[1] - m[0])
    inside = sum(busy([(s, e) for _, s, e in ops], *window)
                 for ops in device_ops.values())
    whole = sum(busy([(s, e) for _, s, e in ops], float("-inf"),
                     float("inf")) for ops in device_ops.values())
    if inside < covered * whole:
        raise ClocksDisagree(
            f"span {window_span!r} {window} holds {inside:.6f} s of "
            f"{whole:.6f} s of device operations")
    lo, hi = window
    # the span that marks the window covers every gap and names none
    host_spans = [sp for sp in host_spans if sp[0] != window_span]
    per_device = {d: busy([(s, e) for _, s, e in ops], lo, hi)
                  for d, ops in device_ops.items()}
    first = min(device_ops)
    ops0 = device_ops[first]
    idle = gaps([(s, e) for _, s, e in ops0], lo, hi)
    idle.sort(key=lambda g: g[0] - g[1])
    collective = sum(
        min(e, hi) - max(s, lo) for name, s, e in ops0
        if min(e, hi) > max(s, lo)
        and any(w in name.lower() for w in COLLECTIVE_WORDS))
    return {
        "window_s": hi - lo,
        "busy_s": sum(per_device.values()) / len(per_device),
        "busy_s_device0": per_device[first],
        "idle_share_device0": 1.0 - per_device[first] / (hi - lo),
        "collective_s_device0": collective,
        "device_ops": [[k, v] for k, v in top_ops(ops0, lo, hi, n)],
        "idle_gaps": [[attribute(g, host_spans), g[1] - g[0]]
                      for g in idle[:n]],
        "devices": len(per_device),
    }


def short_name(event_name):
    """``%fusion.8 = bf16[...] fusion(...)`` -> ``fusion.8``: the trace
    names a device operation by its whole HLO line."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path, span_prefix="bench."):
    """(device_ops, host_spans, layout) from an ``.xplane.pb``.

    Device operations are the events of the "XLA Ops" line of each
    ``/device:TPU:<n>`` plane; host spans are the events of any other plane
    whose name starts with ``span_prefix`` (the benchmark's own
    ``TraceAnnotation``). ``layout`` lists planes and lines with their event
    counts, for a reader who has to look at a new trace by hand.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, host_spans, layout = {}, [], []
    for plane in data.planes:
        is_device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            events = list(line.events)
            layout.append((plane.name, line.name, len(events)))
            if is_device:
                if line.name != OPS_LINE:
                    continue
                dev = int(plane.name[len(DEVICE_PLANE):].split()[0])
                device_ops.setdefault(dev, []).extend(
                    (short_name(ev.name), ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in events)
            else:
                host_spans.extend(
                    (ev.name, ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in events if ev.name.startswith(span_prefix))
    return device_ops, host_spans, layout
