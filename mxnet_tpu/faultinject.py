"""Deterministic, env-driven fault injection for the robustness tests.

Every fault the fault-tolerance subsystem claims to survive is injectable
here, scriptable from the environment so subprocess tests can arrange a
fault without patching framework code:

==============================  =============================================
``MXNET_FI_CRASH_AT_BATCH``     ``os._exit`` (no cleanup, like a kill -9)
                                when the process-global train-batch ordinal
                                reaches this value (0-based; -1 = off).
``MXNET_FI_NAN_BATCHES``        comma-separated batch ordinals whose input
                                data is replaced by NaN — the natural way to
                                produce a non-finite gradient inside the
                                fused train step.
``MXNET_FI_ITER_RAISE_BATCHES`` batch ordinals at which :class:`FlakyIter`
                                raises a transient ``IOError`` ONCE (the
                                retry then succeeds) — exercises
                                ``io.RetryingIter``.
``MXNET_FI_CORRUPT_CKPT``       ``truncate`` or ``garbage``: damage the
                                params file of every checkpoint right after
                                it commits — exercises digest verification
                                and previous-checkpoint fallback.
``MXNET_FI_CKPT_KILL_PHASE``    ``os._exit`` at a named phase INSIDE the
                                checkpoint commit sequence:
                                ``mid-shard-write`` (shard data written,
                                digest/commit record not),
                                ``pre-manifest`` (rank files durable,
                                manifest absent),
                                ``post-manifest-pre-rename`` (complete tmp
                                dir, never renamed in), and ``mid-LATEST``
                                (commit renamed in, LATEST still stale) —
                                the four torn states a mid-save SIGKILL
                                can leave. Exercises two-phase commit +
                                newest-valid-wins recovery.
``MXNET_FI_ATTEMPT``            which launcher attempt the injections apply
                                to (compared against ``MXNET_NUM_RESTARTS``;
                                default 0 = first life only, so a restarted
                                job trains clean).
``MXNET_FI_EXIT_CODE``          exit code for the injected crash
                                (default 17).
==============================  =============================================

Decode-pool faults (chaos harness for ``mxnet_tpu/io_plane``; separate
gate like the serving faults, same attempt/rank scoping):

==================================  =========================================
``MXNET_FI_IO_CRASH_BATCHES``       comma-separated batch ordinals whose
                                    decode raises a non-data error inside
                                    the pool worker ONCE — kills that worker
                                    thread, driving supervisor restart +
                                    shard reassignment.
``MXNET_FI_IO_HANG_BATCHES``        batch ordinals whose decode sleeps
                                    ``MXNET_FI_IO_HANG_MS`` ONCE — watchdog
                                    fuel for ``MXNET_IO_WORKER_TIMEOUT_MS``.
==================================  =========================================

Elastic-kvstore faults (chaos harness for the ``MXNET_KV_TRANSPORT=tcp``
plane in ``kvstore_elastic.py``; separate gate, same attempt scoping —
kill/delay carry their OWN rank selector since the point is faulting one
member of a live group):

==================================  =========================================
``MXNET_FI_KV_KILL_RANK``           with ``MXNET_FI_KV_KILL_AT_BATCH``:
                                    ``os._exit`` on the worker whose
                                    ``MXNET_PROC_ID`` equals this rank when
                                    ITS train-batch ordinal reaches the
                                    value (a mid-epoch machine death; the
                                    membership sweeper must declare it and
                                    survivors reshard to dp−1).
``MXNET_FI_KV_DELAY_MS``            sleep this long before every gradient
                                    push on the rank named by
                                    ``MXNET_FI_KV_DELAY_RANK`` (-1 = all) —
                                    straggler fuel for bounded staleness
                                    and backup-worker drop-slowest.
``MXNET_FI_KV_DROP_EVERY``          silently drop every Nth client frame
                                    before it is sent (a lost packet — the
                                    hardened RPC layer must retry, not
                                    hang).
``MXNET_FI_KV_CORRUPT_EVERY``       flip a byte in every Nth client frame
                                    on the wire — the server must DETECT
                                    it (crc32/HMAC), reject the frame with
                                    a counter, and the clean resend must
                                    succeed. Never absorbed.
==================================  =========================================

Serving-path faults (the chaos harness for ``mxnet_tpu/serving``; same
``MXNET_FI_ATTEMPT``/``MXNET_FI_RANK`` gating, read per call so a test
can kill and revive a replica at runtime by mutating ``os.environ``):

==================================  =========================================
``MXNET_FI_SERVE_RAISE_REPLICA``    comma-separated replica ids whose
                                    forward raises (kill replica R — drives
                                    circuit-breaker open + batch failover).
``MXNET_FI_SERVE_LATENCY_MS``       sleep this long inside the replica
                                    forward (tail-latency / watchdog /
                                    hedging fuel), on the replica named by
                                    ``MXNET_FI_SERVE_LATENCY_REPLICA``
                                    (-1 = every replica).
``MXNET_FI_SERVE_FAIL_EVERY``       fail every Nth serving batch attempt
                                    (process-global ordinal, any replica) —
                                    the intermittent-fault mode failover
                                    must absorb without client errors.
``MXNET_FI_SERVE_RELOAD_CORRUPT``   comma-separated replica ids whose hot
                                    reload raises mid-swap — exercises
                                    per-replica ejection (a reload failure
                                    on one replica must not poison the
                                    pool).
==================================  =========================================

All hooks are no-ops (one cheap env check) when nothing is configured;
``Module.fit`` disables train-window fusion while injection is active so
batch ordinals stay exact.
"""

from __future__ import annotations

import os
import threading

from . import env as _env
from . import telemetry as _tm
from .base import MXNetError
from .io import DataIter

_lock = threading.Lock()
_batch_ordinal = -1  # process-global count of train batches seen by fit
_serve_ordinal = 0   # process-global count of serving batch attempts
_io_fired = set()    # (kind, ordinal) decode-pool injections already fired
_kv_batch = -1       # train-batch ordinal for the kv kill schedule
_kv_frame = 0        # process-global count of elastic kvstore frames sent


def _csv_ints(name):
    raw = _env.get(name)
    out = set()
    for part in raw.split(","):
        part = part.strip()
        if part:
            try:
                out.add(int(part))
            except ValueError:
                raise MXNetError(f"{name}: {part!r} is not an integer")
    return out


def _attempt_matches():
    want = _env.get("MXNET_FI_ATTEMPT")
    if want < 0:
        return True  # -1: every attempt
    return _env.get("MXNET_NUM_RESTARTS") == want


def _rank_matches():
    want = _env.get("MXNET_FI_RANK")
    if want < 0:
        return True  # any rank
    return _env.get("MXNET_PROC_ID") == want


def active():
    """True when any fault is configured for THIS launcher attempt+rank."""
    if not any(_env.raw(k) for k in (
            "MXNET_FI_CRASH_AT_BATCH", "MXNET_FI_NAN_BATCHES",
            "MXNET_FI_ITER_RAISE_BATCHES", "MXNET_FI_CORRUPT_CKPT",
            "MXNET_FI_CKPT_KILL_PHASE")):
        return False
    return _attempt_matches() and _rank_matches()


def reset():
    """Rewind the process-global batch ordinals (tests only)."""
    global _batch_ordinal, _serve_ordinal, _kv_batch, _kv_frame
    with _lock:
        _batch_ordinal = -1
        _serve_ordinal = 0
        _io_fired.clear()
        _kv_batch = -1
        _kv_frame = 0


def kv_active():
    """True when any elastic-kvstore fault is configured for THIS launcher
    attempt (separate from :func:`active` — kv chaos must not flip fit's
    window-fusion opt-out; rank scoping is per-fault, not global)."""
    if not any(_env.raw(k) for k in (
            "MXNET_FI_KV_KILL_AT_BATCH", "MXNET_FI_KV_DELAY_MS",
            "MXNET_FI_KV_DROP_EVERY", "MXNET_FI_KV_CORRUPT_EVERY")):
        return False
    return _attempt_matches()


def _kv_on_train_batch():
    """The kv kill schedule: a worker death mid-epoch, exercised from
    ``Module.fit``'s per-batch hook. Own ordinal (``active()``'s counter
    only advances when the classic fault family is on)."""
    global _kv_batch
    if not kv_active():
        return
    kill_at = _env.get("MXNET_FI_KV_KILL_AT_BATCH")
    if kill_at < 0:
        return
    with _lock:
        _kv_batch += 1
        ordinal = _kv_batch
    if _env.get("MXNET_PROC_ID") == _env.get("MXNET_FI_KV_KILL_RANK") \
            and ordinal == kill_at:
        # a machine death mid-round: no LEAVE, no atexit — the membership
        # sweeper has to find out the hard way (heartbeat silence)
        print(f"faultinject: KV-KILL rank {_env.get('MXNET_PROC_ID')} at "
              f"train batch {ordinal}", flush=True)
        os._exit(_env.get("MXNET_FI_EXIT_CODE"))


def kv_delay():
    """Straggler injection: called before every elastic gradient push;
    sleeps ``MXNET_FI_KV_DELAY_MS`` on the configured rank. The delayed
    worker keeps heartbeating — it is SLOW, not dead, which is exactly the
    case bounded staleness / drop-slowest must absorb without a reshard."""
    if not kv_active():
        return
    ms = _env.get("MXNET_FI_KV_DELAY_MS")
    if ms <= 0:
        return
    who = _env.get("MXNET_FI_KV_DELAY_RANK")
    if who >= 0 and who != _env.get("MXNET_PROC_ID"):
        return
    _tm.counter("faultinject.kv_delay").inc()
    import time

    time.sleep(ms / 1e3)


def kv_frame_fault():
    """Per-frame wire fault: returns ``"drop"``, ``"corrupt"`` or None for
    the frame about to be sent (process-global frame ordinal). A retry
    resends on a fresh ordinal, so chaos at every-Nth never livelocks."""
    if not kv_active():
        return None
    drop = _env.get("MXNET_FI_KV_DROP_EVERY")
    corrupt = _env.get("MXNET_FI_KV_CORRUPT_EVERY")
    if drop <= 0 and corrupt <= 0:
        return None
    global _kv_frame
    with _lock:
        _kv_frame += 1
        ordinal = _kv_frame
    if drop > 0 and ordinal % drop == 0:
        _tm.counter("faultinject.kv_drop").inc()
        return "drop"
    if corrupt > 0 and ordinal % corrupt == 0:
        _tm.counter("faultinject.kv_corrupt").inc()
        return "corrupt"
    return None


def kv_corrupt_bytes(frame):
    """Flip one mid-frame byte — damage the server MUST detect via the
    crc32/HMAC trailer and reject, never absorb into the model."""
    buf = bytearray(frame)
    buf[len(buf) // 2] ^= 0xFF
    return bytes(buf)


def on_train_batch(data_batch):
    """Per-batch hook in ``Module.fit``: advances the global batch ordinal
    and fires any crash/NaN injection scheduled for it. Returns the
    (possibly corrupted) batch."""
    global _batch_ordinal
    _kv_on_train_batch()
    if not active():
        return data_batch
    with _lock:
        _batch_ordinal += 1
        ordinal = _batch_ordinal
    crash_at = _env.get("MXNET_FI_CRASH_AT_BATCH")
    if crash_at >= 0 and ordinal == crash_at:
        # a real machine death: no atexit, no flushes beyond this print
        print(f"faultinject: CRASH at train batch {ordinal}", flush=True)
        os._exit(_env.get("MXNET_FI_EXIT_CODE"))
    if ordinal in _csv_ints("MXNET_FI_NAN_BATCHES"):
        _tm.counter("faultinject.nan_batch").inc()
        _poison_batch(data_batch)
    return data_batch


def _poison_batch(data_batch):
    """Replace every float data array of the batch with NaNs (labels stay —
    integer label encodings have no NaN). Shape/dtype metadata only: no
    device read, so injection itself never perturbs the sync counters the
    guard tests assert on."""
    import numpy as np

    from .ndarray import array

    poisoned = []
    for arr in data_batch.data or []:
        dtype = np.dtype(getattr(arr, "dtype", np.float32))
        if np.issubdtype(dtype, np.floating):
            poisoned.append(
                array(np.full(tuple(arr.shape), np.nan, dtype)))
        else:
            poisoned.append(arr)
    data_batch.data = poisoned
    data_batch.staged = False  # re-stage: the arrays are new
    return data_batch


def io_plane_active():
    """True when any decode-pool fault is configured for THIS launcher
    attempt+rank (separate from :func:`active` — io chaos must not flip
    fit's window-fusion opt-out)."""
    if not any(_env.raw(k) for k in (
            "MXNET_FI_IO_CRASH_BATCHES", "MXNET_FI_IO_HANG_BATCHES")):
        return False
    return _attempt_matches() and _rank_matches()


def _io_fire_once(kind, ordinal):
    """(decode-pool) True the first time this (kind, ordinal) fires."""
    with _lock:
        if (kind, ordinal) in _io_fired:
            return False
        _io_fired.add((kind, ordinal))
        return True


def on_io_decode(ordinal):
    """Hook at the top of every decode-pool worker task (``ordinal`` =
    batch ordinal within the epoch). May sleep (hung worker — watchdog
    fuel) or raise a non-:class:`MXNetError` (worker death — supervisor
    restart fuel). Each injection fires ONCE per ordinal so the retried
    decode after reassignment succeeds and the epoch completes."""
    if not io_plane_active():
        return
    if ordinal in _csv_ints("MXNET_FI_IO_HANG_BATCHES") \
            and _io_fire_once("hang", ordinal):
        _tm.counter("faultinject.io_hang").inc()
        import time

        time.sleep(_env.get("MXNET_FI_IO_HANG_MS") / 1e3)
    if ordinal in _csv_ints("MXNET_FI_IO_CRASH_BATCHES") \
            and _io_fire_once("crash", ordinal):
        _tm.counter("faultinject.io_crash").inc()
        # deliberately NOT MXNetError: a data error is delivered in
        # order; this models the worker itself dying
        raise RuntimeError(
            f"faultinject: injected decode-worker crash at batch {ordinal}")


def serving_active():
    """True when any serving-path fault is configured for THIS launcher
    attempt+rank (separate from :func:`active` — serving faults must not
    flip fit's window-fusion opt-out)."""
    if not any(_env.raw(k) for k in (
            "MXNET_FI_SERVE_RAISE_REPLICA", "MXNET_FI_SERVE_LATENCY_MS",
            "MXNET_FI_SERVE_FAIL_EVERY", "MXNET_FI_SERVE_RELOAD_CORRUPT")):
        return False
    return _attempt_matches() and _rank_matches()


def on_serving_forward(replica_id):
    """Per-batch hook inside ``serving.Replica._call`` (under the replica
    lock, exactly where a real device fault would land): may sleep
    (inject-latency), raise (kill replica R / fail every Nth batch), or
    do nothing. Env is re-read per call so chaos tests flip faults on and
    off at runtime."""
    global _serve_ordinal
    if not serving_active():
        return
    lat = _env.get("MXNET_FI_SERVE_LATENCY_MS")
    if lat > 0:
        who = _env.get("MXNET_FI_SERVE_LATENCY_REPLICA")
        if who < 0 or who == replica_id:
            _tm.counter("faultinject.serve_latency").inc()
            import time

            time.sleep(lat / 1e3)
    if replica_id in _csv_ints("MXNET_FI_SERVE_RAISE_REPLICA"):
        _tm.counter("faultinject.serve_raise").inc()
        raise MXNetError(
            f"faultinject: injected forward failure on replica "
            f"{replica_id}")
    every = _env.get("MXNET_FI_SERVE_FAIL_EVERY")
    if every > 0:
        with _lock:
            _serve_ordinal += 1
            ordinal = _serve_ordinal
        if ordinal % every == 0:
            _tm.counter("faultinject.serve_raise").inc()
            raise MXNetError(
                f"faultinject: injected failure at serving batch "
                f"{ordinal} (every {every})")


def on_serving_reload(replica_id):
    """Hook at the top of ``ModelServer._reload_replica``: an injected
    raise models a corrupt per-replica weight transfer — the server must
    eject that replica and keep the pool serving."""
    if not serving_active():
        return
    if replica_id in _csv_ints("MXNET_FI_SERVE_RELOAD_CORRUPT"):
        _tm.counter("faultinject.serve_reload_corrupt").inc()
        raise MXNetError(
            f"faultinject: injected reload corruption on replica "
            f"{replica_id}")


def ckpt_kill(phase):
    """Called by CheckpointManager at each named point of the commit
    sequence: ``os._exit`` (a kill -9, mid-save) when
    ``MXNET_FI_CKPT_KILL_PHASE`` names this phase for this attempt+rank.
    The chaos tests assert that whatever torn state each phase leaves,
    the newest previously-valid commit still loads."""
    want = _env.get("MXNET_FI_CKPT_KILL_PHASE")
    if not want or want != phase:
        return
    if not _attempt_matches() or not _rank_matches():
        return
    print(f"faultinject: CKPT-KILL at phase {phase}", flush=True)
    os._exit(_env.get("MXNET_FI_EXIT_CODE"))


def post_checkpoint_commit(params_path):
    """Called by CheckpointManager right after a checkpoint commits:
    optionally damages the just-written params file (simulating later disk
    corruption / a torn replica) so the NEXT load must fall back."""
    mode = _env.get("MXNET_FI_CORRUPT_CKPT")
    if not mode or not _attempt_matches() or not _rank_matches():
        return
    corrupt_file(params_path, mode)
    _tm.counter("faultinject.corrupt_ckpt").inc()


def corrupt_file(path, mode="truncate"):
    """Damage ``path`` in place: ``truncate`` keeps the first half,
    ``garbage`` flips bytes in the middle. Direct test helper."""
    size = os.path.getsize(path)
    if mode == "truncate":
        with open(path, "rb+") as f:
            f.truncate(max(1, size // 2))
    elif mode == "garbage":
        with open(path, "rb+") as f:
            f.seek(size // 2)
            f.write(b"\xde\xad\xbe\xef" * 8)
    else:
        raise MXNetError(f"corrupt_file: unknown mode {mode!r}")
    return path


class FlakyIter(DataIter):
    """Wraps a DataIter; raises a transient ``IOError`` the first time each
    configured batch ordinal (per epoch position) is requested. A retry of
    the same ``next()`` succeeds and yields the batch that would have been
    returned — the contract ``io.RetryingIter`` restores."""

    def __init__(self, data_iter, raise_at=None):
        super().__init__(getattr(data_iter, "batch_size", 0))
        self._iter = data_iter
        self._raise_at = (set(raise_at) if raise_at is not None
                          else _csv_ints("MXNET_FI_ITER_RAISE_BATCHES"))
        self._pos = -1
        self._raised = set()

    @property
    def provide_data(self):
        return self._iter.provide_data

    @property
    def provide_label(self):
        return self._iter.provide_label

    def reset(self):
        self._pos = -1
        self._raised.clear()
        self._iter.reset()

    def next(self):
        nxt = self._pos + 1
        if nxt in self._raise_at and nxt not in self._raised:
            self._raised.add(nxt)
            _tm.counter("faultinject.iter_raise").inc()
            raise IOError(f"faultinject: transient read error at batch {nxt}")
        batch = self._iter.next()  # raises StopIteration at the end
        self._pos = nxt
        return batch
