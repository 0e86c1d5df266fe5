"""The slice arithmetic and the warm-up/window state machine, on a fake
clock: no jax, no program."""

import pytest

from benchmark.lib import window as wn


def test_one_stalled_slice_moves_the_window_rate_and_not_the_median():
    steady = [(20 * 256, 1.84)] * 11
    stalled = list(steady)
    stalled[4] = (20 * 256, 1.84 + 2.0)  # a two-second stall in one slice
    a, b = wn.summarize(steady), wn.summarize(stalled)
    assert b["median_rate"] == pytest.approx(a["median_rate"])
    assert b["mean_rate"] < 0.92 * a["mean_rate"]  # the end-to-end rate
    assert b["mean_rate"] == pytest.approx(11 * 20 * 256 / (11 * 1.84 + 2.0))
    assert b["stalled_slices"] == [4] and a["stalled_slices"] == []
    assert b["units"] == a["units"] == 11 * 20 * 256


def test_gc_log_times_the_collections_inside_an_interval():
    import gc
    import time

    from benchmark.lib.harness import GcLog

    log = GcLog()
    lo = time.perf_counter()
    gc.collect()
    hi = time.perf_counter()
    log.close()
    recorded = len(log.events)
    gc.collect()
    assert len(log.events) == recorded  # closed: no longer listening
    got = log.between(lo, hi)
    assert got["collections"] >= 1 and got["seconds"] > 0
    assert any(gen == 2 for gen, _, _ in got["longest"])
    assert log.between(hi + 1, hi + 2)["collections"] == 0


class FakeRun:
    """A loop whose steps take ``step_s`` and whose first ``compiling``
    warm-up steps each compile one program."""

    def __init__(self, step_s=0.1, compiling=3):
        self.now, self.step_s = 0.0, step_s
        self.compiles, self.left = 0, compiling
        self.fenced_at, self.traced = [], []

    def dispatch(self):
        self.now += self.step_s
        if self.left:
            self.left -= 1
            self.compiles += 1


def make(run, **kw):
    args = dict(seconds=2.0, slice_steps=4, cycle_steps=2, min_slices=3,
                fence=lambda: run.fenced_at.append(run.now),
                compile_events=lambda: (run.compiles, 0),
                units_of=lambda first, n: n * 8, clock=lambda: run.now)
    args.update(kw)
    return wn.SliceWindow(**args)


def drive(run, win, limit=10_000):
    for _ in range(limit):
        run.dispatch()
        if win.step() == wn.DONE:
            return
    raise AssertionError("window never ended")


def test_warmup_ends_on_the_first_cycle_without_a_compile():
    run = FakeRun(compiling=3)
    win = make(run)
    drive(run, win)
    # cycles of 2 steps: compiles in cycle 1 (2) and cycle 2 (1); cycle 3 is
    # the first clean one, so warm-up is 3 cycles = 6 steps
    assert win.cycles == 3 and win.warmup_steps == 6
    assert win.t_warm == pytest.approx(0.6)


def test_window_is_whole_slices_each_ended_by_a_fence():
    run = FakeRun(compiling=0)
    win = make(run)
    drive(run, win)
    assert win.cycles == 2  # the first cycle never counts as settled
    # 2 s at 0.4 s a slice: ends at the first boundary at or past 2 s
    assert len(win.slices) == 5 and win.steps == 20
    assert all(u == 32 and s == pytest.approx(0.4) for u, s in win.slices)
    assert win.fences == win.cycles + len(win.slices)
    assert win.t_end - win.t_warm == pytest.approx(2.0)


def test_min_slices_outlasts_seconds():
    run = FakeRun(compiling=0)
    win = make(run, seconds=0.1, min_slices=4)
    drive(run, win)
    assert len(win.slices) == 4


def test_warmup_that_never_settles_raises():
    run = FakeRun(compiling=10_000)
    win = make(run, max_cycles=4)
    with pytest.raises(wn.WarmupNeverSettled):
        drive(run, win)


def test_traced_slice_follows_the_window():
    run = FakeRun(compiling=0)
    win = make(run, trace_steps=3, trace_start=lambda: run.traced.append("on"),
               trace_stop=lambda: run.traced.append("off"))
    drive(run, win)
    assert run.traced == ["on", "off"]
    assert win.trace_slice == (3, pytest.approx(0.3))
    assert win.steps == 20  # traced steps are not window steps


def test_losses_are_read_at_every_slice_fence():
    run = FakeRun(compiling=0)
    seen = []
    win = make(run, read_loss=lambda: seen.append(run.now) or 1.0)
    drive(run, win)
    assert len(win.losses) == len(win.slices) == 5
