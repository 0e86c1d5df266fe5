"""Runtime concurrency sanitizer (mxnet_tpu.analysis.sanitizer).

Three pins the PR-15 acceptance names: a seeded two-thread ABBA cycle is
detected (deterministically — barrier-sequenced, no sleeps, no actual
deadlock), a consistently-ordered run stays clean (no false positives),
and the instrumented fast path stays within a small constant factor of a
bare lock. Plus the plumbing: install/uninstall round-trips
``threading.Lock``, and Condition/Event built while installed keep
working (the Condition ``wait`` protocol against the wrapped RLock).
"""

import threading
import time

import pytest

from mxnet_tpu.analysis import sanitizer


@pytest.fixture()
def armed():
    """Sanitizer installed with clean state; always restored."""
    sanitizer.install()
    sanitizer.reset()
    try:
        yield sanitizer
    finally:
        sanitizer.uninstall()
        sanitizer.reset()


# ---------------------------------------------------------------- ABBA

def test_detects_seeded_abba_cycle(armed):
    """T1 takes A then B; T2 takes B then A. Sequenced by a barrier so
    the two orders never overlap — no deadlock ever happens, but the
    order graph sees A->B then B->A and must report the cycle with both
    stacks."""
    lock_a = threading.Lock()
    lock_b = threading.Lock()
    gate = threading.Barrier(2, timeout=30)
    done = threading.Barrier(2, timeout=30)

    def t1():
        with lock_a:
            with lock_b:
                pass
        gate.wait()   # hand the stage to T2 only after releasing both
        done.wait()

    def t2():
        gate.wait()
        with lock_b:
            with lock_a:  # closes the cycle: B->A after A->B
                pass
        done.wait()

    threads = [threading.Thread(target=t1, name="san-t1"),
               threading.Thread(target=t2, name="san-t2")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()

    rep = sanitizer.report()
    assert len(rep["cycles"]) == 1, sanitizer.format_report(rep)
    cyc = rep["cycles"][0]
    assert cyc["thread"] == "san-t2"
    # both stacks present and pointing at this file
    assert "test_sanitizer" in cyc["closing_stack"]
    assert "test_sanitizer" in cyc["reverse_stack"]
    # the report renders without blowing up
    assert "ABBA cycle" in sanitizer.format_report(rep)


def test_cycle_reported_once_not_per_acquire(armed):
    """The same ABBA pair re-executed N times yields ONE report — cycle
    keys are deduplicated, so a hot loop cannot flood the report."""
    lock_a = threading.Lock()
    lock_b = threading.Lock()

    def take(first, second):
        with first:
            with second:
                pass

    take(lock_a, lock_b)
    for _ in range(5):
        t = threading.Thread(target=take, args=(lock_b, lock_a))
        t.start()
        t.join(timeout=30)
    assert len(sanitizer.report()["cycles"]) == 1


def test_three_lock_cycle_detected(armed):
    """A->B, B->C, C->A: the cycle spans three locks and only closes on
    the third edge."""
    a, b, c = threading.Lock(), threading.Lock(), threading.Lock()

    def take(first, second):
        with first:
            with second:
                pass

    take(a, b)
    take(b, c)
    assert sanitizer.report()["cycles"] == []
    t = threading.Thread(target=take, args=(c, a))
    t.start()
    t.join(timeout=30)
    rep = sanitizer.report()
    assert len(rep["cycles"]) == 1, sanitizer.format_report(rep)


# ------------------------------------------------------ no false alarms

def test_consistent_order_stays_clean(armed):
    """Many threads, same A-before-B discipline: edges accumulate, no
    cycle is ever reported."""
    lock_a = threading.Lock()
    lock_b = threading.Lock()
    start = threading.Barrier(4, timeout=30)

    def worker():
        start.wait()
        for _ in range(50):
            with lock_a:
                with lock_b:
                    pass

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()

    rep = sanitizer.report()
    assert rep["cycles"] == []
    assert rep["edges"] >= 1


def test_condition_and_event_roundtrip_clean(armed):
    """Condition/Event built while installed run a real producer/consumer
    hand-off; the Condition wait protocol must drive the instrumented
    RLock correctly (release on wait, reacquire on wake) and report
    nothing."""
    cond = threading.Condition()
    evt = threading.Event()
    box = []

    def consumer():
        with cond:
            while not box:
                cond.wait(timeout=30)
        evt.set()

    t = threading.Thread(target=consumer)
    t.start()
    with cond:
        box.append(1)
        cond.notify()
    assert evt.wait(timeout=30)
    t.join(timeout=30)
    assert sanitizer.report()["cycles"] == []


def test_rlock_reentry_is_not_a_cycle(armed):
    """Recursive RLock acquisition must not self-edge."""
    r = threading.RLock()
    with r:
        with r:
            pass
    rep = sanitizer.report()
    assert rep["cycles"] == []


@pytest.mark.parametrize("inside", ["building a lock", "noting an edge"])
def test_finalizer_inside_the_sanitizers_own_section_cannot_deadlock(
        armed, monkeypatch, inside):
    """A garbage collection can start at any allocation, also under the
    sanitizer's own mutex, and its finalizers take and build sanitized
    locks on the same thread (``MXRecordIO.__del__`` closing a decode
    pool inside ``RLock()``: the hang of the whole tier-1 run that PR 26
    found). Stood in for by a hook that does what such a finalizer does."""
    outer, inner = threading.Lock(), threading.Lock()

    ran = []

    def finalizer():
        if ran:
            return
        ran.append(True)
        with outer:
            with inner:        # a first-seen edge: takes the mutex
                pass
        threading.Lock()       # and so does building a lock

    if inside == "building a lock":
        def hooked(site=sanitizer._site):
            finalizer()
            return site()
        monkeypatch.setattr(sanitizer, "_site", hooked)
        work = threading.Lock
    else:
        first, second = threading.Lock(), threading.Lock()

        def hooked(frm, to, walk=sanitizer._path_exists):
            finalizer()
            return walk(frm, to)
        monkeypatch.setattr(sanitizer, "_path_exists", hooked)

        def work():
            with first:
                with second:
                    pass

    t = threading.Thread(target=work, daemon=True)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    assert sanitizer.report()["cycles"] == []


# ------------------------------------------------------------ plumbing

def test_install_uninstall_roundtrip():
    orig = threading.Lock
    sanitizer.install()
    try:
        assert threading.Lock is not orig
        assert sanitizer.installed()
        lk = threading.Lock()
        with lk:
            assert lk.locked()
        assert not lk.locked()
    finally:
        sanitizer.uninstall()
        sanitizer.reset()
    assert threading.Lock is orig
    assert not sanitizer.installed()


def test_hold_tracking_reports_long_hold(armed, monkeypatch):
    monkeypatch.setenv("MXNET_SANITIZER_HOLD_MS", "5")
    # re-arm so the threshold is picked up
    sanitizer.uninstall()
    sanitizer.install()
    lk = threading.Lock()
    with lk:
        time.sleep(0.02)
    rep = sanitizer.report()
    assert rep["long_holds"], sanitizer.format_report(rep)
    assert rep["long_holds"][0]["held_ms"] >= 5


# ------------------------------------------------------------- overhead

def test_overhead_smoke(monkeypatch):
    """Steady-state sanitized acquire/release stays on the fast path its
    cost bound (within 10x of a bare lock) is designed for: once a pair's
    edge has been seen, no stack is captured and the order graph is not
    walked (its mutex is never taken). Counted, not timed."""
    calls = []

    def counted(name):
        real = getattr(sanitizer, name)

        def call(*args, **kwargs):
            calls.append(name)
            return real(*args, **kwargs)
        monkeypatch.setattr(sanitizer, name, call)

    counted("_stack")
    counted("_record_edges")
    sanitizer.install()
    try:
        outer, inner = threading.Lock(), threading.Lock()

        def cycle():
            with outer:
                with inner:
                    pass

        cycle()             # the first-seen edge outer -> inner
        assert calls == ["_stack", "_record_edges"]
        del calls[:]
        for _ in range(20_000):
            cycle()
        assert not calls
    finally:
        sanitizer.uninstall()
        sanitizer.reset()
