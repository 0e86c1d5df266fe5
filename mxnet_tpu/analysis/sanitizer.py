# graftlint: allow=env-registry(the sanitizer must stay importable and armable with the framework absent or sabotaged — the standalone lint/test harness loads it before mxnet_tpu.env exists, so its two MXNET_SANITIZER* gates are read raw; both stay declared in the registry and documented in docs/env_var.md)
"""Runtime concurrency sanitizer: ThreadSanitizer-flavoured lock-order
watching for the threaded planes.

The static pass (:mod:`analysis.checkers.lock_discipline`) proves what
it can from the AST; this module catches what only execution shows —
lock orders taken through callbacks, thread interleavings the call graph
over-approximates away, third-party locks (``queue.Queue``'s internal
mutex) the AST never names. It is the dynamic half of the PR-15 pairing:
RacerD-style inference before the run, ThreadSanitizer-style
happens-before evidence during it.

How it works: :func:`install` monkey-patches ``threading.Lock`` and
``threading.RLock`` with instrumented wrappers (``Condition``, ``Event``
and ``queue.Queue`` construct their internals from those names at call
time, so they become instrumented transitively). Every wrapper acquire
records the lock against the calling thread's held stack; the first time
lock *B* is taken while *A* is held, the edge ``A→B`` enters a
process-wide lock-order graph with the acquiring stack attached. An
acquisition that would close a cycle in that graph is the ABBA signal —
reported immediately with **both** stacks (the one that recorded the
reverse path and the one closing the cycle), without needing the
deadlock to actually strike. With ``MXNET_SANITIZER_HOLD_MS`` set > 0, a
lock held longer than that many milliseconds is reported with its
acquire stack (the "who is starving the plane" probe).

Cost model: the fast path (uncontended acquire, all edges already seen)
is one real acquire, one thread-local append, one dict probe per held
lock. Stacks are captured only on first-seen edges and — when hold
tracking is armed — at acquire; steady-state overhead is bounded and
verified by the overhead smoke in ``tests/test_sanitizer.py``.

Gates (read raw — see the file pragma above):

- ``MXNET_SANITIZER=1`` arms :func:`maybe_install` (the conftest fixture
  for ``sanitize``-marked suites uses opt-out semantics instead:
  installed unless ``MXNET_SANITIZER=0``);
- ``MXNET_SANITIZER_HOLD_MS=<n>`` additionally reports locks held longer
  than *n* ms.

This module imports nothing from the framework — stdlib only — so the
lint CLI and the test harness can load it with jax sabotaged.
"""

from __future__ import annotations

import os
import sys
import threading
import traceback
from time import monotonic

_thread = __import__("_thread")
_allocate = _thread.allocate_lock

__all__ = [
    "Lock", "RLock", "Condition", "Event", "install", "uninstall",
    "installed", "maybe_install", "report", "reset", "enabled",
    "hold_threshold_ms",
]


def enabled():
    """True when ``MXNET_SANITIZER=1`` asks for process-wide arming."""
    return os.environ.get("MXNET_SANITIZER", "") == "1"


def hold_threshold_ms():
    """Held-too-long threshold in ms; 0 disables hold tracking."""
    try:
        return float(os.environ.get("MXNET_SANITIZER_HOLD_MS", "0") or 0)
    except ValueError:
        return 0.0


# --------------------------------------------------------------------------
# process-wide state
# --------------------------------------------------------------------------

class _TLS(threading.local):
    """Per-thread held-lock stack, auto-initialised on first touch so
    the acquire fast path is a single attribute read."""

    def __init__(self):
        self.held = []
        self.noting = False  # inside _note_acquired's walk of the graph


class _State:
    """One per process. ``mutex`` is a BARE ``_thread`` lock — the
    sanitizer must never watch its own bookkeeping. It is reentrant: any
    allocation under it can start a garbage collection, whose finalizers
    (``MXRecordIO.__del__`` closing a decode pool) take and build
    sanitized locks on this same thread."""

    def __init__(self):
        self.mutex = _thread.RLock()
        self.edges = {}        # a_id -> {b_id: formatted stack (str)}
        self.names = {}        # lock id -> "site (kind#n)"
        self.cycles = []       # report dicts
        self.long_holds = []   # report dicts
        self.seen_cycle_keys = set()
        self.counter = 0


_state = _State()
_tls = _TLS()
#: hold-tracking threshold, cached as a module global at install() time —
#: the acquire/release fast paths test it on every operation.
_hold_ms = 0.0


def _stack(skip=2):
    return "".join(traceback.format_stack(
        sys._getframe(skip), limit=12))


def _site():
    """'file:line' of the frame constructing the lock, skipping the
    sanitizer's own frames and threading.py internals."""
    f = sys._getframe(2)
    while f is not None:
        fn = f.f_code.co_filename
        if "analysis/sanitizer" not in fn.replace("\\", "/") \
                and not fn.endswith("threading.py") \
                and not fn.endswith("queue.py"):
            return f"{os.path.basename(fn)}:{f.f_lineno}"
        f = f.f_back
    return "<unknown>"


def _path_exists(frm, to):
    """DFS: is ``to`` reachable from ``frm`` in the order graph? Caller
    holds ``_state.mutex``."""
    stack, seen = [frm], {frm}
    while stack:
        at = stack.pop()
        if at == to:
            return True
        for nxt in _state.edges.get(at, ()):
            if nxt not in seen:
                seen.add(nxt)
                stack.append(nxt)
    return False


def _reverse_path(frm, to):
    """One ``frm``→…→``to`` path (list of ids). Caller holds the mutex."""
    stack = [(frm, [frm])]
    seen = {frm}
    while stack:
        at, path = stack.pop()
        if at == to:
            return path
        for nxt in _state.edges.get(at, ()):
            if nxt not in seen:
                seen.add(nxt)
                stack.append((nxt, path + [nxt]))
    return [frm, to]


def _note_acquired(lock, held):
    """Record order edges held[i] → lock; detect cycles on first-seen
    edges only (a seen edge was already checked)."""
    lid = lock._san_id
    new_edges = [h._san_id for h in held
                 if lid not in _state.edges.get(h._san_id, ())]
    if not new_edges or _tls.noting:
        # noting: a finalizer run by a collection inside the walk below;
        # the graph is mid-iteration, so its edge goes unrecorded
        return
    acquiring_stack = _stack(3)
    _tls.noting = True
    try:
        _record_edges(lid, new_edges, acquiring_stack)
    finally:
        _tls.noting = False


def _record_edges(lid, new_edges, acquiring_stack):
    with _state.mutex:
        for hid in new_edges:
            bucket = _state.edges.setdefault(hid, {})
            if lid in bucket:      # raced with another thread: now seen
                continue
            # adding hid->lid closes a cycle iff lid already reaches hid
            if _path_exists(lid, hid):
                path = _reverse_path(lid, hid)
                key = frozenset(path) | {lid, hid}
                if key not in _state.seen_cycle_keys:
                    _state.seen_cycle_keys.add(key)
                    names = [_state.names.get(i, "?") for i in
                             path + [lid]]
                    rev_stack = _state.edges[path[0]].get(
                        path[1], "<stack unavailable>") \
                        if len(path) > 1 else "<stack unavailable>"
                    _state.cycles.append({
                        "locks": names,
                        "thread": threading.current_thread().name,
                        "closing_edge":
                            f"{_state.names.get(hid, '?')} -> "
                            f"{_state.names.get(lid, '?')}",
                        "closing_stack": acquiring_stack,
                        "reverse_stack": rev_stack,
                    })
            bucket[lid] = acquiring_stack


def _note_released(lock):
    t0 = lock._san_t0
    if t0 is not None:
        lock._san_t0 = None
        held_for = (monotonic() - t0) * 1000.0
        if held_for >= _hold_ms:
            with _state.mutex:
                _state.long_holds.append({
                    "lock": _state.names.get(lock._san_id, "?"),
                    "held_ms": round(held_for, 3),
                    "thread": threading.current_thread().name,
                    "acquire_stack": lock._san_acq_stack
                    or "<stack unavailable>",
                })


# --------------------------------------------------------------------------
# instrumented primitives
# --------------------------------------------------------------------------

class _SanLockBase:
    __slots__ = ("_lock", "_san_id", "_san_t0", "_san_acq_stack")
    _san_kind = "Lock"

    def __init__(self):
        self._lock = _allocate()
        with _state.mutex:
            _state.counter += 1
            self._san_id = _state.counter
            _state.names[self._san_id] = \
                f"{_site()} ({self._san_kind}#{self._san_id})"
        self._san_t0 = None
        self._san_acq_stack = None

    def _san_push(self):
        held = _tls.held
        if held:
            _note_acquired(self, held)
        held.append(self)
        if _hold_ms:
            self._san_t0 = monotonic()
            self._san_acq_stack = _stack(3)

    def _san_pop(self):
        if _hold_ms:
            _note_released(self)
        held = _tls.held
        if held and held[-1] is self:  # LIFO discipline: common case
            held.pop()
        else:
            try:
                held.remove(self)
            except ValueError:
                pass  # released on a different thread than acquired

    def __repr__(self):
        return (f"<sanitized {self._san_kind} "
                f"{_state.names.get(self._san_id, '?')} "
                f"locked={self.locked()}>")


class _SanLock(_SanLockBase):
    """Instrumented non-reentrant lock (``threading.Lock`` stand-in).
    ``acquire``/``release`` inline the held-stack bookkeeping — this
    pair is the sanitizer's hot path and pays for every lock in the
    process while installed."""

    __slots__ = ()

    def acquire(self, blocking=True, timeout=-1):
        rc = self._lock.acquire(blocking, timeout)
        if rc:
            held = _tls.held
            if held:
                _note_acquired(self, held)
            held.append(self)
            if _hold_ms:
                self._san_t0 = monotonic()
                self._san_acq_stack = _stack(2)
        return rc

    acquire_lock = acquire

    def release(self):
        if _hold_ms:
            _note_released(self)
        held = _tls.held
        if held and held[-1] is self:
            held.pop()
        else:
            try:
                held.remove(self)
            except ValueError:
                pass
        self._lock.release()

    release_lock = release

    def locked(self):
        return self._lock.locked()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()


class _SanRLock(_SanLockBase):
    """Instrumented reentrant lock (``threading.RLock`` stand-in), with
    the ``_release_save``/``_acquire_restore``/``_is_owned`` trio so
    ``threading.Condition`` drives it correctly through ``wait()``."""

    __slots__ = ("_owner", "_count")
    _san_kind = "RLock"

    def __init__(self):
        super().__init__()
        self._owner = None
        self._count = 0

    def acquire(self, blocking=True, timeout=-1):
        me = _thread.get_ident()
        if self._owner == me:
            self._count += 1
            return True
        rc = self._lock.acquire(blocking, timeout)
        if rc:
            self._owner = me
            self._count = 1
            self._san_push()
        return rc

    __enter__ = acquire

    def release(self):
        if self._owner != _thread.get_ident():
            raise RuntimeError("cannot release un-acquired lock")
        self._count -= 1
        if self._count == 0:
            self._owner = None
            self._san_pop()
            self._lock.release()

    def __exit__(self, *exc):
        self.release()

    def locked(self):
        return self._lock.locked()

    # Condition protocol ---------------------------------------------
    def _release_save(self):
        if self._owner != _thread.get_ident():
            raise RuntimeError("cannot release un-acquired lock")
        count, self._count = self._count, 0
        self._owner = None
        self._san_pop()
        self._lock.release()
        return count

    def _acquire_restore(self, count):
        self._lock.acquire()
        self._owner = _thread.get_ident()
        self._count = count
        self._san_push()

    def _is_owned(self):
        return self._owner == _thread.get_ident()


def Lock():
    """Factory: an instrumented ``threading.Lock``."""
    return _SanLock()


def RLock():
    """Factory: an instrumented ``threading.RLock``."""
    return _SanRLock()


def Condition(lock=None):
    """A real ``threading.Condition`` over an instrumented lock."""
    return _orig["Condition"](lock if lock is not None else RLock())


def Event():
    """A real ``threading.Event``; its internal lock is instrumented
    while :func:`install` is active (transitively via the patch)."""
    return _orig["Event"]()


# --------------------------------------------------------------------------
# install / report
# --------------------------------------------------------------------------

_orig = {
    "Lock": threading.Lock,
    "RLock": threading.RLock,
    "Condition": threading.Condition,
    "Event": threading.Event,
}
_installed = False


def install():
    """Monkey-patch ``threading.Lock``/``RLock`` with the instrumented
    factories. ``Condition``/``Event``/``queue.Queue`` construct their
    internals from these names at call time, so they come along for
    free. Idempotent."""
    global _installed, _hold_ms
    if _installed:
        return
    _hold_ms = hold_threshold_ms()
    threading.Lock = Lock
    threading.RLock = RLock
    _installed = True


def uninstall():
    """Restore the real primitives. Locks created while installed stay
    instrumented (they are self-contained wrappers)."""
    global _installed
    if not _installed:
        return
    threading.Lock = _orig["Lock"]
    threading.RLock = _orig["RLock"]
    _installed = False


def installed():
    return _installed


def maybe_install():
    """Arm iff ``MXNET_SANITIZER=1``; returns whether armed."""
    if enabled():
        install()
    return _installed


def report():
    """Snapshot of everything observed so far."""
    with _state.mutex:
        return {
            "installed": _installed,
            "locks": _state.counter,
            "edges": sum(len(v) for v in _state.edges.values()),
            "cycles": list(_state.cycles),
            "long_holds": list(_state.long_holds),
        }


def reset():
    """Drop the order graph and all findings (locks keep their ids)."""
    with _state.mutex:
        _state.edges.clear()
        _state.cycles.clear()
        _state.long_holds.clear()
        _state.seen_cycle_keys.clear()


def format_report(rep=None):
    """Human-readable rendering of :func:`report` for assertion
    messages and post-mortems."""
    rep = rep or report()
    lines = [f"sanitizer: {rep['locks']} locks, {rep['edges']} order "
             f"edges, {len(rep['cycles'])} cycles, "
             f"{len(rep['long_holds'])} long holds"]
    for c in rep["cycles"]:
        lines.append(f"\nABBA cycle on thread {c['thread']}: "
                     + " -> ".join(c["locks"]))
        lines.append(f"closing edge {c['closing_edge']} acquired at:")
        lines.append(c["closing_stack"])
        lines.append("reverse edge first recorded at:")
        lines.append(c["reverse_stack"])
    for h in rep["long_holds"]:
        lines.append(f"\nlock {h['lock']} held {h['held_ms']}ms by "
                     f"{h['thread']}; acquired at:")
        lines.append(h["acquire_stack"] or "<stack unavailable>")
    return "\n".join(lines)
