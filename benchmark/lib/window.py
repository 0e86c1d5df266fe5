"""The measured window: warm-up that ends on a condition, then fenced
slices of whole steps, and the arithmetic over them.

Nothing here touches jax or the program: the drivers hand in a ``fence``
callable and call :meth:`SliceWindow.step` once per retired dispatch, so
the state machine is tested on the CPU with a fake clock.
"""

from __future__ import annotations

import statistics
import time

WARMUP, WINDOW, TRACE, DONE = "warmup", "window", "trace", "done"


class WarmupNeverSettled(RuntimeError):
    """No whole cycle ran without a compile inside the allowed cycles."""


def slice_rates(slices):
    """Units per second of each slice; ``slices`` is [(units, seconds)]."""
    return [u / s for u, s in slices]


def summarize(slices):
    """The whole-window rate, the median of the slices' rates and the stall
    report. The slices tile the window, so ``mean_rate`` is all the work
    over all the time: that is the end-to-end rate, and a stall moves it.
    The median is the steadier diagnostic beside it. A slice is reported as
    stalled when its rate is more than 1% under the median (steady slices
    agree to 0.1% on the v5e)."""
    rates = slice_rates(slices)
    median = statistics.median(rates)
    units = sum(u for u, _ in slices)
    seconds = sum(s for _, s in slices)
    stalled = [i for i, r in enumerate(rates) if r < 0.99 * median]
    return {
        "median_rate": median,
        "mean_rate": units / seconds,
        "rates": rates,
        "units": units,
        "seconds": seconds,
        "stalled_slices": stalled,
    }


class SliceWindow:
    """Warm-up, window and (optionally) one traced slice, in whole steps.

    ``step()`` is called after every step the loop has dispatched. At a
    boundary it fences, reads the clock only after the fence, and moves on:

    * warm-up runs in cycles of ``cycle_steps`` steps and ends after the
      first cycle, later than the first, in which ``compile_events()`` did
      not change; the fence at that cycle's end is where set-up ends;
    * the window is cut into slices of ``slice_steps`` steps and ends at the
      first slice boundary at which ``seconds`` have passed and at least
      ``min_slices`` slices are complete;
    * with ``trace_steps`` one further slice runs between ``trace_start``
      and ``trace_stop``.

    ``units_of(first_step, n)`` gives the work (samples, tokens) of the
    ``n`` window steps from ``first_step`` on.
    """

    def __init__(self, *, seconds, slice_steps, cycle_steps, fence,
                 compile_events, units_of, min_slices=10, max_cycles=8,
                 trace_steps=0, trace_start=None, trace_stop=None,
                 read_loss=None, clock=time.perf_counter):
        self.seconds = float(seconds)
        self.slice_steps = int(slice_steps)
        self.cycle_steps = int(cycle_steps)
        self.min_slices = int(min_slices)
        self.max_cycles = int(max_cycles)
        self.trace_steps = int(trace_steps)
        self._fence = fence
        self._compile_events = compile_events
        self._units_of = units_of
        self._trace_start = trace_start
        self._trace_stop = trace_stop
        self._read_loss = read_loss
        self._clock = clock

        self.phase = WARMUP
        self.fences = 0
        self.cycles = 0
        self.warmup_steps = 0
        self.slices = []      # (units, seconds)
        self.losses = []      # one per slice fence, where read_loss is given
        self.steps = 0        # window steps retired
        self.t_warm = None    # clock at the fence that ended warm-up
        self.t_end = None     # clock at the fence that ended the window
        self.trace_slice = None  # (steps, seconds)
        self._in_phase = 0
        self._mark = compile_events()
        self._t_slice = None

    @property
    def first_cycle(self):
        """True during the first warm-up cycle, where a driver reads its
        loss at every step so that every shape of that program is warm."""
        return self.phase == WARMUP and self.cycles == 0

    def fence(self):
        self._fence()
        self.fences += 1
        return self._clock()

    def step(self):
        """Account one dispatched step; returns the phase now in force."""
        self._in_phase += 1
        if self.phase == WARMUP:
            self.warmup_steps += 1
            if self._in_phase == self.cycle_steps:
                self._end_cycle()
        elif self.phase == WINDOW:
            if self._in_phase == self.slice_steps:
                self._end_slice()
        elif self.phase == TRACE:
            if self._in_phase == self.trace_steps:
                now = self.fence()
                self.trace_slice = (self.trace_steps, now - self._t_slice)
                if self._trace_stop is not None:
                    self._trace_stop()
                self.phase = DONE
        return self.phase

    def _end_cycle(self):
        now = self.fence()
        if self._read_loss is not None:
            self._read_loss()  # its program is part of every later cycle
            now = self._clock()
        events = self._compile_events()
        settled = self.cycles >= 1 and events == self._mark
        self.cycles += 1
        self._mark = events
        self._in_phase = 0
        if settled:
            self.phase = WINDOW
            self.t_warm = self._t_slice = now
        elif self.cycles >= self.max_cycles:
            raise WarmupNeverSettled(
                f"{self.cycles} warm-up cycles of {self.cycle_steps} steps "
                f"and the last still compiled: {events}")

    def _end_slice(self):
        now = self.fence()
        first = self.steps
        self.steps += self.slice_steps
        self.slices.append((self._units_of(first, self.slice_steps),
                            now - self._t_slice))
        if self._read_loss is not None:
            # inside the next slice's time: the slices tile the window
            self.losses.append(self._read_loss())
        self._t_slice = now
        self._in_phase = 0
        if (now - self.t_warm >= self.seconds
                and len(self.slices) >= self.min_slices):
            self.t_end = now
            if self.trace_steps:
                self.phase = TRACE
                if self._trace_start is not None:
                    self._trace_start()
                self._fence()
                self._t_slice = self._clock()
            else:
                self.phase = DONE
