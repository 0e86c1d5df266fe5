"""Framework-wide telemetry: counters, gauges, histograms, host spans.

The reference MXNet pairs its engine with an in-engine profiler dumping
Chrome trace-event JSON (src/engine/profiler.{h,cc}); our port wraps the
jax *device* trace in :mod:`mxnet_tpu.profiler`, which says nothing about
the host side of the async pipeline — whether an epoch is data-bound,
dispatch-bound or sync-bound. This module is the host half:

- **Instruments** (:func:`counter`, :func:`gauge`, :func:`histogram`) form
  a process-wide registry. They are ALWAYS on: an increment is one lock +
  one add, cheap enough for per-batch hot paths. :func:`snapshot` renders
  the registry as a nested dict, :func:`dump` writes it as JSON plus a
  Prometheus-style text exposition, :func:`reset` zeroes values in place
  (handles cached by hot paths stay valid).

- **Spans** (:func:`span`) time a region and know the span that was open
  on the same thread when they began (a thread-local stack). The duration
  always feeds the histogram of the same name (microseconds), whose
  ``self_sum`` is the duration less the part child spans covered, so two
  :func:`snapshot` calls give self time over a window with no event list.
  Whenever jax's profiler is tracing — whoever started it — every span is
  also a ``TraceMe`` on the trace's host plane under its own name, on the
  same timeline as the device operations; a span given ``step_num`` is
  emitted as ``jax.profiler.StepTraceAnnotation`` emits a step, and the
  spans opened inside it carry its ``step``. Only when span recording is
  enabled via ``MXNET_TELEMETRY`` (:func:`enable_spans`) is an in-memory
  event kept as well (:func:`events`): name, ``id``, ``parent``, and
  ``ts``/``dur`` from the clock the profiler uses (epoch nanoseconds, read
  once per boundary).

Instrumented hot paths (see docs/observability.md for the full catalog):
``io.prefetch.*`` (DevicePrefetchIter), ``fit.*``/``score.*`` (Module
epoch loops), ``executor.jit_*``/``executor.fused_plan_*`` (compile cache),
``aot.*`` (persistent executable cache: cache_hit/cache_miss/cache_store
counters, deserialize/serialize/compile spans — mxnet_tpu.aot),
``bucketing.switch``/``bucketing.compile_on_switch`` (bucket-miss
recompiles), the ``fit.train_window_k``/``fit.dispatch_depth``/
``fit.windows_in_flight`` gauges + ``fit.window``/``fit.window_wait``
spans (adaptive windows and their pipelined dispatch),
``kvstore.*``/``kvstore_async.*`` (push/pull/bytes/barrier),
``metric.*`` (device vs numpy-fallback accumulation, drain syncs),
``ndarray.asnumpy``/``ndarray.wait_to_read`` (every host-blocking sync),
and ``serving.*`` (request admission/shed, batch composition,
queue-wait/infer/latency, hot reloads — mxnet_tpu.serving).
"""

from __future__ import annotations

import functools
import itertools
import json
import os
import threading
import time

__all__ = [
    "counter", "gauge", "histogram", "span", "snapshot", "dump", "reset",
    "prometheus", "spans_enabled", "enable_spans", "events", "phase_totals",
]


class Counter:
    """Monotonic counter (resettable via :func:`reset`)."""

    __slots__ = ("name", "value", "_lock")

    def __init__(self, name):
        self.name = name
        self.value = 0
        self._lock = threading.Lock()

    def inc(self, n=1):
        with self._lock:
            self.value += n

    def _zero(self):
        with self._lock:
            self.value = 0

    def _render(self):
        return self.value


class Gauge:
    """Last-set value plus the high-water mark since the last reset."""

    __slots__ = ("name", "value", "max", "_lock")

    def __init__(self, name):
        self.name = name
        self.value = 0
        self.max = 0
        self._lock = threading.Lock()

    def set(self, v):
        with self._lock:
            self.value = v
            if v > self.max:
                self.max = v

    def _zero(self):
        with self._lock:
            self.value = 0
            self.max = 0

    def _render(self):
        return {"value": self.value, "max": self.max}


class Histogram:
    """Streaming count/sum/min/max (values are whatever unit the caller
    observes; span durations are microseconds). ``self_sum`` is the part
    of ``sum`` that no child span covered; a plain ``observe`` has no
    children, so there it grows with ``sum``."""

    __slots__ = ("name", "count", "sum", "self_sum", "min", "max", "_lock")

    def __init__(self, name):
        self.name = name
        self.count = 0
        self.sum = 0
        self.self_sum = 0
        self.min = None
        self.max = None
        self._lock = threading.Lock()

    def observe(self, v, self_v=None):
        with self._lock:
            self.count += 1
            self.sum += v
            self.self_sum += v if self_v is None else self_v
            if self.min is None or v < self.min:
                self.min = v
            if self.max is None or v > self.max:
                self.max = v

    def _zero(self):
        with self._lock:
            self.count = 0
            self.sum = 0
            self.self_sum = 0
            self.min = None
            self.max = None

    def _render(self):
        out = {"count": self.count, "sum": self.sum,
               "self_sum": self.self_sum}
        if self.count:
            out["min"] = self.min
            out["max"] = self.max
            out["avg"] = self.sum / self.count
        return out


_lock = threading.Lock()
_instruments = {}  # name -> instrument (kind enforced on first use)


def _get(name, cls):
    inst = _instruments.get(name)
    if inst is None:
        with _lock:
            inst = _instruments.get(name)
            if inst is None:
                inst = cls(name)
                _instruments[name] = inst
    if not isinstance(inst, cls):
        raise TypeError(
            f"telemetry name {name!r} is a {type(inst).__name__}, "
            f"not a {cls.__name__}"
        )
    return inst


def counter(name):
    """The process-wide counter called ``name`` (created on first use)."""
    return _get(name, Counter)


def gauge(name):
    """The process-wide gauge called ``name`` (created on first use)."""
    return _get(name, Gauge)


def histogram(name):
    """The process-wide histogram called ``name`` (created on first use)."""
    return _get(name, Histogram)


# --- span recording --------------------------------------------------------

def _env_spans():
    from . import env as _env

    return bool(_env.get("MXNET_TELEMETRY"))


_spans_on = _env_spans()
_events = []
_events_lock = threading.Lock()
_MAX_EVENTS = 500_000  # memory backstop; overflow counted, not grown
_open = threading.local()  # .stack: the spans open on this thread, outermost first
_ids = itertools.count(1)  # next() is atomic under the interpreter lock
_trace_me = None  # jaxlib's TraceMe, resolved at the first span


def spans_enabled():
    """True when span() calls record in-memory trace events."""
    return _spans_on


def enable_spans(on=True):
    """Turn span recording on/off at runtime (MXNET_TELEMETRY sets the
    import-time default)."""
    global _spans_on
    _spans_on = bool(on)


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation``: imported at the first span and not
    with this module, so the ``startup.import`` span covers jax's import."""
    global _trace_me
    from jax.profiler import TraceAnnotation

    _trace_me = TraceAnnotation
    return _trace_me


class _Span:
    """Times a region on the profiler's clock. Always: the histogram, with
    the time no child covered. While jax's profiler traces: a TraceMe of
    the same name. When spans are on: an in-memory event with id and
    parent. Usable as a decorator: every call of the function is a span."""

    __slots__ = ("name", "args", "id", "parent", "step", "_t0", "_child_ns",
                 "_annotation")

    def __init__(self, name, args):
        self.name = name
        self.args = args

    def __call__(self, fn):
        name, args = self.name, self.args

        @functools.wraps(fn)
        def spanned(*a, **kw):
            with _Span(name, args):
                return fn(*a, **kw)

        return spanned

    def __enter__(self):
        try:
            stack = _open.stack
        except AttributeError:
            stack = _open.stack = []
        parent = self.parent = stack[-1] if stack else None
        self.id = next(_ids)
        step = self.args.get("step_num") if self.args else None
        if step is None and parent is not None:
            step = parent.step
        self.step = step
        self._child_ns = 0
        self._annotation = None
        trace_me, t0 = _trace_me, None
        if trace_me is None:
            # the first span of the process (startup.import) pays for
            # importing jax's profiler: that time is inside it
            t0 = time.time_ns()
            trace_me = _profiler_annotation()
        if trace_me.is_enabled():
            meta = self._labels()
            if "step_num" in meta:
                meta["_r"] = 1  # what StepTraceAnnotation adds: a step root
            self._annotation = trace_me(self.name, **meta)
            self._annotation.__enter__()
        stack.append(self)
        self._t0 = t0 or time.time_ns()
        return self

    def __exit__(self, *exc):
        dur_ns = max(time.time_ns() - self._t0, 0)  # the epoch clock may step
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        stack = getattr(_open, "stack", None)
        if stack and stack[-1] is self:
            stack.pop()
        elif stack and self in stack:
            # a span that was entered above this one and never left goes
            # with it (a span left on another thread than it was entered on
            # is not in this stack, which then stays as it is)
            while stack.pop() is not self:
                pass
        if self.parent is not None:
            self.parent._child_ns += dur_ns
        histogram(self.name).observe(
            dur_ns // 1000, max(dur_ns - self._child_ns, 0) // 1000)
        if _spans_on:
            self._record(dur_ns)
        return False

    def _labels(self):
        """The span's arguments, with the step it inherited."""
        args = dict(self.args)
        if self.step is not None and "step_num" not in args:
            args["step"] = self.step
        return args

    def _record(self, dur_ns):
        ev = {
            "name": self.name, "ph": "X", "cat": "host",
            "ts": self._t0 / 1e3, "dur": max(dur_ns / 1e3, 1e-3),
            "pid": os.getpid(), "tid": threading.get_ident(),
            "id": self.id,
            "parent": None if self.parent is None else self.parent.id,
        }
        args = self._labels()
        if args:
            ev["args"] = args
        with _events_lock:
            if len(_events) < _MAX_EVENTS:
                _events.append(ev)
            else:
                counter("telemetry.dropped_events").inc()


def span(name, **args):
    """Context manager (or decorator) timing a region.

    The duration (microseconds) always feeds ``histogram(name)``, and its
    ``self_sum`` the part no child span covered. While jax's profiler is
    tracing the span is on the trace's host plane under ``name``; with
    ``step_num=n`` it is a step as ``jax.profiler.StepTraceAnnotation``
    marks one, and spans opened inside it carry ``step=n``. When span
    recording is enabled an in-memory event is kept as well.
    """
    return _Span(name, args)


def events():
    """A copy of the recorded host trace events."""
    with _events_lock:
        return list(_events)


# --- export ----------------------------------------------------------------

def snapshot():
    """The registry as a nested dict (names split on '.')."""
    with _lock:
        items = sorted(_instruments.items())
    # build a tree of instrument objects first, render at the end: while
    # building, dicts are always tree nodes and instruments always leaves,
    # so a name nested under another instrument's name ("a.b" vs "a.b.c")
    # demotes the occupying leaf to key "" instead of merging into its
    # rendered dict
    root = {}
    for name, inst in items:
        node = root
        parts = name.split(".")
        for p in parts[:-1]:
            nxt = node.get(p)
            if not isinstance(nxt, dict):
                nxt = node[p] = {} if nxt is None else {"": nxt}
            node = nxt
        leaf = parts[-1]
        if isinstance(node.get(leaf), dict):
            node[leaf][""] = inst
        else:
            node[leaf] = inst

    def render(node):
        return {
            k: render(v) if isinstance(v, dict) else v._render()
            for k, v in node.items()
        }

    return render(root)


def phase_totals(prefix=""):
    """{name: summed duration} for every histogram under ``prefix`` —
    Speedometer's phase-breakdown feed."""
    with _lock:
        items = list(_instruments.items())
    return {
        n: h.sum for n, h in items
        if isinstance(h, Histogram) and n.startswith(prefix)
    }


def prometheus():
    """Prometheus text exposition of the registry (counters/gauges map
    directly; histograms expose _count/_sum/_min/_max)."""
    with _lock:
        items = sorted(_instruments.items())
    lines = []

    def metric_name(name, suffix=""):
        return "mxnet_" + name.replace(".", "_").replace("-", "_") + suffix

    for name, inst in items:
        if isinstance(inst, Counter):
            lines.append(f"# TYPE {metric_name(name)} counter")
            lines.append(f"{metric_name(name)} {inst.value}")
        elif isinstance(inst, Gauge):
            lines.append(f"# TYPE {metric_name(name)} gauge")
            lines.append(f"{metric_name(name)} {inst.value}")
            lines.append(f"{metric_name(name, '_max')} {inst.max}")
        else:
            lines.append(f"# TYPE {metric_name(name)} summary")
            lines.append(f"{metric_name(name, '_count')} {inst.count}")
            lines.append(f"{metric_name(name, '_sum')} {inst.sum}")
            if inst.count:
                lines.append(f"{metric_name(name, '_min')} {inst.min}")
                lines.append(f"{metric_name(name, '_max')} {inst.max}")
    return "\n".join(lines) + "\n"


def dump(path):
    """Write the snapshot as JSON to ``path`` and the Prometheus text
    exposition next to it (``<path stem>.prom``). Returns both paths."""
    with open(path, "w") as f:
        json.dump(snapshot(), f, indent=2, sort_keys=True)
    prom_path = os.path.splitext(path)[0] + ".prom"
    with open(prom_path, "w") as f:
        f.write(prometheus())
    return path, prom_path


def reset():
    """Zero every instrument in place (cached handles stay valid) and
    drop recorded span events. Does not change span enablement."""
    with _lock:
        insts = list(_instruments.values())
    for inst in insts:
        inst._zero()
    with _events_lock:
        _events.clear()
