"""Profiler — execution tracing.

Reference: ``python/mxnet/profiler.py:10-38`` + the in-engine profiler
(``src/engine/profiler.{h,cc}``) dumping Chrome trace-event JSON at the
configured filename. TPU mapping (SURVEY.md §5): the jax/XLA profiler
captures the device trace (op-level, HBM, MXU utilisation);
``dump_profile`` honours the reference's file contract by extracting the
chrome-trace JSON out of the captured run and writing it to
``filename`` — loadable in chrome://tracing / Perfetto exactly like the
reference's output. ``MXNET_PROFILER_AUTOSTART`` starts tracing at import
(reference env_var.md:69-78).

Every entry point degrades gracefully when jax profiling is unavailable
(stripped builds, backends without a profiler plugin): the operation
becomes a warn-once no-op instead of raising at import or construction
time — profiling must never be able to take a training job down. The
host half of the timeline lives in :mod:`mxnet_tpu.telemetry`: while a
trace runs, every ``telemetry.span`` is on its host plane already.
"""

from __future__ import annotations

import glob
import gzip
import logging
import os
import shutil

_state = {"mode": "symbolic", "filename": "profile.json", "running": False}

_warned = set()


def _warn_once(key, msg):
    if key not in _warned:
        _warned.add(key)
        logging.warning(msg)


def _jax_profiler():
    """The jax profiler module, or None (warn once) when unavailable."""
    try:
        import jax

        return jax.profiler
    except Exception as e:  # ImportError, stripped builds, plugin errors
        _warn_once("import", f"jax profiler unavailable ({e}); "
                             "device profiling is a no-op")
        return None


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """Set up the profiler (reference profiler_set_config)."""
    _state["mode"] = mode
    _state["filename"] = filename


def profiler_set_state(state="stop"):
    """'run' starts a jax profiler trace; 'stop' ends it. A backend whose
    profiler cannot start/stop logs one warning and leaves the state
    unchanged instead of raising."""
    prof = _jax_profiler()
    if prof is None:
        return
    if state == "run" and not _state["running"]:
        logdir = os.path.splitext(_state["filename"])[0] + "_trace"
        try:
            prof.start_trace(logdir)
        except Exception as e:
            _warn_once("start", f"profiler start_trace failed ({e}); "
                                "device profiling is a no-op")
            return
        _state["running"] = True
        _state["logdir"] = logdir
    elif state == "stop" and _state["running"]:
        try:
            prof.stop_trace()
        except Exception as e:
            _warn_once("stop", f"profiler stop_trace failed ({e})")
        _state["running"] = False


def dump_profile():
    """Write the chrome-trace JSON to the configured filename.

    Returns the filename (reference contract: the file the user set via
    profiler_set_config exists and holds trace-event JSON after this
    call). The raw xplane/TensorBoard artifacts stay in the side logdir
    for deeper analysis.
    """
    if _state["running"]:
        profiler_set_state("stop")
    logdir = _state.get("logdir")
    if not logdir:
        return None
    fname = _state["filename"]
    traces = sorted(glob.glob(
        os.path.join(logdir, "**", "*.trace.json.gz"), recursive=True
    ))
    if traces:
        with gzip.open(traces[-1], "rb") as src, open(fname, "wb") as dst:
            shutil.copyfileobj(src, dst)
        return fname
    return None


class trace_annotation:
    """Context manager naming a region in the device trace
    (maps to jax.profiler.TraceAnnotation). A no-op (warn once) when jax
    profiling is unavailable, so instrumented user code keeps running."""

    def __init__(self, name):
        self.name = name
        self._ann = None
        prof = _jax_profiler()
        ann_cls = getattr(prof, "TraceAnnotation", None) if prof else None
        if ann_cls is None:
            if prof is not None:
                _warn_once("annotation", "jax profiler has no "
                                         "TraceAnnotation; annotations are "
                                         "no-ops")
            return
        try:
            self._ann = ann_cls(name)
        except Exception as e:
            _warn_once("annotation", f"TraceAnnotation failed ({e}); "
                                     "annotations are no-ops")

    def __enter__(self):
        if self._ann is None:
            return self
        return self._ann.__enter__()

    def __exit__(self, *a):
        if self._ann is None:
            return False
        return self._ann.__exit__(*a)


def _maybe_autostart():
    from . import env as _env

    try:
        if _env.get("MXNET_PROFILER_AUTOSTART"):
            profiler_set_config(mode=_env.get("MXNET_PROFILER_MODE"))
            profiler_set_state("run")
    except Exception as e:
        # autostart is a convenience; a broken profiler must not turn
        # `import mxnet_tpu` into a crash
        _warn_once("autostart", f"profiler autostart failed ({e})")


_maybe_autostart()
