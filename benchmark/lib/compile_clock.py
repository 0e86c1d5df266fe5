"""Compile events as jax's own monitoring reports them.

Copied from ``chip_smoke.py:CompileClock`` (PR 21) so that a program PR
cannot move the yardstick; extended to keep the time of every event, so a
compile inside the measured window is seen with its instant and not only
counted.
"""

from __future__ import annotations

import time


class CompileClock:
    """Backend compiles and persistent-cache traffic since construction."""

    def __init__(self):
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_writes = 0
        # (perf_counter at the event's end, seconds it took)
        self.compiles = []
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_kw):
        if name.endswith("backend_compile_duration"):
            self.compile_s += secs
            self.compiles.append((time.perf_counter(), secs))

    def _on_event(self, name, **_kw):
        if name.endswith("/cache_hits"):
            self.cache_hits += 1
        elif name.endswith("/cache_misses"):  # recorded when an entry is written
            self.cache_writes += 1

    def mark(self):
        """(compile events, cache writes) so far: warm-up ends on a cycle
        that leaves it unchanged."""
        return len(self.compiles), self.cache_writes

    def events_between(self, t0, t1):
        """[(seconds after t0, duration)] of compiles that ended in [t0, t1]."""
        return [(t - t0, d) for t, d in self.compiles if t0 <= t <= t1]
