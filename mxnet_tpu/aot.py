"""AOT compilation and dispatch subsystem.

Host dispatch *around* the XLA computation is time the device idles, and a
fresh process pays full XLA compilation for every graph signature. This
module is the standard JAX production answer, in three coordinated pieces:

1. **AOT dispatch** (:class:`AOTProgram`) — every executor program
   (forward, train step, the fused donating train update) is
   ``lower().compile()``d to a concrete executable on first call and
   invoked directly from then on: no re-trace machinery, no per-call jit
   cache lookup or argument re-inference in the steady-state hot loop.
   :meth:`AOTProgram._resolve` is the one place where an executable is
   looked up, lowered, compiled and stored. A program that donates nothing
   falls back (permanently, per program) to the plain jitted callable on
   any AOT failure, so semantics never depend on the fast path; every
   fallback is counted (``aot.compile_fallback`` / ``aot.exec_fallback``)
   so a run can assert it stayed on the fast path. A program that donates
   has no second try: its failures raise (:class:`DonatedCallError` once
   the executable was called).

2. **Persistent executable cache** (:func:`load` / :func:`store`) — compiled
   executables serialize to ``MXNET_AOT_CACHE_DIR`` when ``MXNET_AOT_CACHE``
   is set, keyed by a digest of the program signature (symbol graph, shapes,
   dtypes, grad_req, pack layout) plus an environment fingerprint
   (jax/jaxlib/framework versions, backend platform + device kind + device
   count, XLA compiler options). A second process then binds and runs with
   ``executor.jit_compile == 0`` — warm starts skip XLA entirely. Backends
   without executable serialization degrade gracefully to trace-and-compile
   (``aot.serialize_unsupported`` counts the refusals).

3. **Adaptive train-window scheduler** (:class:`TrainWindowScheduler`) —
   ``MXNET_TRAIN_WINDOW=auto`` picks the fused-K step depth of
   ``Module.train_window`` from measured telemetry instead of a hand-tuned
   constant: probe batches run single-step while the ``fit.*`` phase spans
   (PR 2) accumulate, then :func:`choose_train_window` converts the
   dispatch-vs-residual ratio into a window depth. Dispatch-bound loops
   (every execute costs host time the device waits through) get deep
   windows; device/data-bound loops stay at K=1, where a window
   buys nothing and costs metric granularity. The same profile co-tunes
   the pipelined *dispatch depth* (``MXNET_DISPATCH_DEPTH``,
   :func:`choose_dispatch_depth`): how many windows ``Module.fit`` keeps
   in flight before fencing on the oldest boundary.

Telemetry: counters ``aot.cache_hit`` / ``aot.cache_miss`` /
``aot.cache_store`` / ``aot.deserialize_error`` / ``aot.serialize_unsupported``
/ ``aot.compile_fallback`` / ``aot.exec_fallback``, spans ``aot.deserialize`` / ``aot.serialize``, and
the ``fit.train_window_k`` gauge reporting the scheduler's decision.
"""

from __future__ import annotations

import hashlib
import math
import os
import pickle
import threading
import weakref
from typing import NamedTuple

from . import env as _env
from . import telemetry as _tm
from .base import MXNetError

_CACHE_FORMAT = 2  # bump to invalidate every persisted executable
_SUFFIX = ".aotx"

__all__ = [
    "AOTProgram", "DonatedCallError", "ProgramMemory", "memory_table",
    "cache_enabled", "cache_dir", "digest", "load", "store",
    "supports_serialization", "choose_train_window", "train_window_setting",
    "choose_dispatch_depth", "dispatch_depth_setting",
    "TrainWindowScheduler",
]


# --- persistent executable cache -------------------------------------------

def cache_enabled():
    """True when compiled executables persist to / load from disk."""
    return bool(_env.get("MXNET_AOT_CACHE"))


def cache_dir():
    """The on-disk executable cache directory (created on first store)."""
    return os.path.expanduser(_env.get("MXNET_AOT_CACHE_DIR"))


_src_lock = threading.Lock()
_src_digest = None


def _source_digest():
    """Content hash of the framework's python sources — the "library
    version" part of the cache key for a repo that ships from source: any
    op-semantics change invalidates persisted executables."""
    global _src_digest
    with _src_lock:
        if _src_digest is None:
            h = hashlib.sha256()
            pkg = os.path.dirname(os.path.abspath(__file__))
            for root, dirs, files in sorted(os.walk(pkg)):
                dirs.sort()
                for fname in sorted(files):
                    if not fname.endswith(".py"):
                        continue
                    path = os.path.join(root, fname)
                    h.update(os.path.relpath(path, pkg).encode())
                    with open(path, "rb") as f:
                        h.update(f.read())
            _src_digest = h.hexdigest()
    return _src_digest


def _fingerprint():
    """Environment half of every cache key: an executable is only valid for
    the exact compiler + backend topology that produced it — including the
    configured mesh layout (``MXNET_MESH``): a dp2,pp4 program and a dp8
    program share neither partitioning nor collectives."""
    import jax
    import jaxlib

    from .base import __version__

    devs = jax.devices()
    return (
        _CACHE_FORMAT, __version__, jax.__version__, jaxlib.__version__,
        _source_digest(), jax.default_backend(), len(devs),
        getattr(devs[0], "device_kind", ""),
        str(_env.get("MXNET_MESH") or ""),
        # compiler/layout knobs: flags or conv layout change the emitted
        # program wholesale, so cached executables must never cross them
        str(_env.get("MXNET_XLA_FLAGS") or ""),
        str(_env.get("MXNET_CONV_LAYOUT") or "auto"),
    )


def digest(*parts):
    """Stable hex digest of ``parts`` + the environment fingerprint.

    Parts must render deterministically under ``repr`` (tuples of
    primitives; callers pre-render PyTreeDefs and reject mesh objects)."""
    payload = repr((_fingerprint(), parts)).encode()
    return hashlib.sha256(payload).hexdigest()


_probe_lock = threading.Lock()
_probe_result = None


def supports_serialization():
    """Whether this backend can serialize compiled executables (probed once
    with a trivial program; a backend that cannot makes every
    :func:`store` count ``aot.serialize_unsupported``)."""
    global _probe_result
    with _probe_lock:
        if _probe_result is None:
            try:
                import jax
                from jax.experimental import serialize_executable as _se

                compiled = jax.jit(lambda x: x + 1).lower(
                    jax.ShapeDtypeStruct((), "float32")).compile()
                payload, in_tree, out_tree = _se.serialize(compiled)
                _se.deserialize_and_load(payload, in_tree, out_tree)
                _probe_result = True
            except Exception:
                _probe_result = False
    return _probe_result


def _path_for(key_digest):
    return os.path.join(cache_dir(), key_digest + _SUFFIX)


def load(key_digest):
    """The deserialized executable for ``key_digest``, or None.

    Counts ``aot.cache_hit``/``aot.cache_miss``; a corrupt or
    incompatible entry counts ``aot.deserialize_error``, is removed, and
    reads as a miss (the caller then compiles and overwrites it)."""
    if key_digest is None or not cache_enabled():
        return None
    path = _path_for(key_digest)
    if not os.path.exists(path):
        _tm.counter("aot.cache_miss").inc()
        return None
    try:
        # reading an executable back is the compile layer's work too
        with _tm.span("executor.compile", source="aot_cache"), \
                _tm.span("aot.deserialize"):
            with open(path, "rb") as f:
                blob = pickle.load(f)
            import jax
            from jax.experimental import serialize_executable as _se

            # for the devices it was compiled for: jax's default is every
            # device of the backend, and a one-device program loaded so
            # wants a shard of each argument on all of them
            by_id = {d.id: d for d in jax.devices()}
            loaded = _se.deserialize_and_load(
                blob["payload"], blob["in_tree"], blob["out_tree"],
                execution_devices=[by_id[i] for i in blob["devices"]],
            )
    except Exception:
        _tm.counter("aot.deserialize_error").inc()
        try:
            os.remove(path)
        except OSError:
            pass
        _tm.counter("aot.cache_miss").inc()
        return None
    _tm.counter("aot.cache_hit").inc()
    return loaded


def store(key_digest, compiled):
    """Serialize ``compiled`` under ``key_digest`` (atomic rename so a
    concurrent reader never sees a torn file). Returns True on success;
    backends that cannot serialize count ``aot.serialize_unsupported``."""
    if key_digest is None or not cache_enabled():
        return False
    try:
        with _tm.span("aot.serialize"):
            from jax.experimental import serialize_executable as _se

            payload, in_tree, out_tree = _se.serialize(compiled)
            devices = compiled.runtime_executable().local_devices()
            blob = pickle.dumps({
                "format": _CACHE_FORMAT, "payload": payload,
                "in_tree": in_tree, "out_tree": out_tree,
                "devices": [d.id for d in devices],
            })
    except Exception:
        _tm.counter("aot.serialize_unsupported").inc()
        return False
    try:
        d = cache_dir()
        os.makedirs(d, exist_ok=True)
        tmp = os.path.join(d, f".{key_digest}.{os.getpid()}.tmp")
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, _path_for(key_digest))
    except OSError:
        return False
    _tm.counter("aot.cache_store").inc()
    return True


# --- AOT program wrapper ----------------------------------------------------

class DonatedCallError(MXNetError):
    """A donating executable failed after it was called: the buffers it
    was given are consumed, so the call can be neither retried nor rolled
    back, and what they held must be restored from outside. Where the
    backend ran out of memory, ``memory`` is :func:`memory_table`'s text
    (else empty) and the message ends in it."""

    def __init__(self, message, memory=""):
        super().__init__(message + memory)
        self.memory = memory


class ProgramMemory(NamedTuple):
    """What one executable needs of its device, in bytes, as XLA's
    ``memory_analysis()`` gives it (on a mesh: one device's share)."""
    argument: int  # every argument, donated or not
    output: int    # every output, those written over a donated argument too
    alias: int     # the outputs that reuse a donated argument
    temp: int      # XLA's temporaries: activations, residuals, scratch
    code: int      # generated code

    @property
    def kept_output(self):
        """What a launch allocates anew and hands its caller."""
        return self.output - self.alias

    @property
    def footprint(self):
        return self.argument + self.kept_output + self.temp + self.code


def _memory_of(executable):
    """``executable``'s :class:`ProgramMemory`, or None where the backend
    (or a deserialised executable) gives no analysis: never a zero that
    reads as a measurement. One host call, no device work."""
    try:
        m = executable.memory_analysis()
        return ProgramMemory(
            int(m.argument_size_in_bytes), int(m.output_size_in_bytes),
            int(m.alias_size_in_bytes), int(m.temp_size_in_bytes),
            int(m.generated_code_size_in_bytes))
    except Exception:
        return None


# every AOTProgram that holds an executable, for memory_table(); weak, so a
# dropped executor's programs leave with it
_resolved = weakref.WeakSet()


class AOTProgram:
    """A jitted program dispatched through its ahead-of-time executable.

    Callable with exactly the wrapped jit function's signature. The first
    call resolves the executable: persistent cache (deserialize) if keyed,
    else ``lower().compile()`` from the concrete arguments (optionally
    persisting the result). Steady-state calls invoke the executable
    directly — the jit re-dispatch machinery (cache lookup, argument
    re-inference) costs real milliseconds per step at executor argument
    counts.

    A program that donates nothing never fails for being ahead of time: any
    AOT failure falls back permanently to the jit callable, and a failed
    *executable* call is retried through jit. ``donates=True`` says the
    program consumes arguments, and then there is no second try: a trace or
    compile failure raises to the caller with every buffer alive, and a
    failure of the executable's call raises :class:`DonatedCallError`.
    ``on_compile(lowered, compiled, args)`` is called after each real
    compile (not after a cache read, which lowers nothing).

    ``label`` names the program in :func:`memory_table` (the caller knows
    the kind); ``memory`` is the resolved executable's
    :class:`ProgramMemory` (None before, and where it gives no analysis)
    and ``launches`` counts the calls of its executable.
    """

    __slots__ = ("jit_fn", "key_digest", "executable", "donates",
                 "on_compile", "label", "memory", "_launches", "_counter",
                 "_span", "_fallback", "_lock", "__weakref__")

    def __init__(self, jit_fn, key_digest=None,
                 compile_counter="aot.trace_compile",
                 compile_span="aot.compile", donates=False, on_compile=None,
                 label=None):
        self.jit_fn = jit_fn
        self.key_digest = key_digest
        self.executable = None
        self.label = label
        self.memory = None
        # this program's own, in no registry: launches come from any thread
        self._launches = _tm.Counter("launches")
        self.donates = donates
        self.on_compile = on_compile
        self._counter = compile_counter
        self._span = compile_span
        self._fallback = False
        self._lock = threading.Lock()

    @property
    def launches(self):
        return self._launches.value

    def _resolve(self, args):
        with self._lock:
            if self.executable is not None or self._fallback:
                return self.executable
            executable = load(self.key_digest)
            if executable is None:
                try:
                    _tm.counter(self._counter).inc()  # graftlint: allow=telemetry-catalog(forwards a constructor-chosen literal: executor.jit_compile, executor.fused_plan_compile or aot.trace_compile, all catalogued)
                    with _tm.span(self._span):  # graftlint: allow=telemetry-catalog(forwards a constructor-chosen literal: executor.jit_build or aot.compile, both catalogued)
                        with _tm.span("executor.trace_lower"):
                            lowered = self.jit_fn.lower(*args)
                        # jax's persistent cache answers inside compile():
                        # a read is this layer's work too
                        with _tm.span("executor.compile"):
                            executable = lowered.compile()
                except Exception:
                    if self.donates:
                        raise  # nothing was donated; the jit path would
                    # tracing raised (e.g. a graph-contract error) or AOT
                    # lowering is unsupported here: let the jit path
                    # surface the same behaviour
                    _tm.counter("aot.compile_fallback").inc()
                    self._fallback = True
                    return None
                if self.on_compile is not None:
                    self.on_compile(lowered, executable, args)
                store(self.key_digest, executable)
            # in hand, by a compile or a read from either cache: keep with
            # it what it needs of the device (one host call, at set-up)
            self.executable = executable
            self.memory = _memory_of(executable)
            _resolved.add(self)
            return executable

    def ensure_compiled(self, args):
        """Resolve the executable (load or compile) without executing.
        ``args`` may be concrete arrays or ShapeDtypeStructs."""
        self._resolve(args)
        return self.executable is not None

    def __call__(self, *args):
        exe = self.executable
        if exe is None:
            if not self._fallback:
                exe = self._resolve(args)
            if exe is None:
                return self.jit_fn(*args)
        self._launches.inc()
        try:
            with _tm.span("executor.launch"):
                return exe(*args)
        except Exception as e:
            if self.donates:
                # out of memory: say what holds it, while it still does
                full = "RESOURCE_EXHAUSTED" in str(e)
                raise DonatedCallError(
                    "a donating executable failed after it was called; the "
                    "buffers donated to it are consumed",
                    memory="\n" + memory_table()["text"] if full else "",
                ) from e
            # aval mismatch (an argument changed device/layout in a way the
            # executable rejects) — the jit path handles it; stop using AOT
            # for this program rather than paying a failed call per step
            _tm.counter("aot.exec_fallback").inc()
            with self._lock:
                self.executable = None
                self._fallback = True
            return self.jit_fn(*args)


# --- the memory table --------------------------------------------------------

_DEVICE_STATS = ("bytes_in_use", "bytes_reserved", "peak_bytes_in_use",
                 "peak_bytes_reserved", "bytes_limit")


def memory_table():
    """What holds the devices' memory, from the host alone.

    ``programs``: one row for every :class:`AOTProgram` of this process
    that holds an executable, heaviest first: ``label``, the five numbers
    of its :class:`ProgramMemory` and ``kept_output_bytes`` /
    ``footprint_bytes`` (every one None where the executable gave no
    analysis), ``launches``. ``devices``: each local device's own
    ``memory_stats()`` (None where the backend keeps none: the CPU).
    ``text``: both, in GiB (MiB where nothing reaches one), for people
    and for the message of an out-of-memory error. A program's arguments
    and kept outputs are its caller's arrays, counted in a device's
    ``bytes_in_use``; its temporaries are the executable's own, which the
    TPU holds in ``bytes_reserved`` while the program is loaded
    (docs/observability.md, "Where the memory goes").
    """
    import jax

    programs = []
    for prog in list(_resolved):
        m = prog.memory
        row = {"label": prog.label or "unlabelled", "launches": prog.launches}
        for field in ProgramMemory._fields + ("kept_output", "footprint"):
            row[field + "_bytes"] = None if m is None else getattr(m, field)
        programs.append(row)
    programs.sort(key=lambda r: (-(r["footprint_bytes"] or 0), r["label"]))
    devices = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        devices.append({"device": str(d), **{
            k: stats.get(k) for k in _DEVICE_STATS}})

    # GiB where anything shown reaches one (a chip's programs), MiB below
    # (a test's): no row of a small model reads 0.000
    shown = [r["footprint_bytes"] or 0 for r in programs] + [
        d["bytes_limit"] or 0 for d in devices]
    unit, size = ("GiB", 1 << 30) if max(shown, default=0) >> 30 else (
        "MiB", 1 << 20)

    def cell(v):
        return "None" if v is None else f"{v / size:.3f}"

    cols = ("argument", "kept_output", "alias", "temp", "code", "footprint")
    lines = [f"programs resolved in this process ({unit} a device; "
             "kept_output = output - alias):",
             "  " + " ".join(f"{c:>11}" for c in cols) + "  launches  label"]
    lines += ["  " + " ".join(f"{cell(r[c + '_bytes']):>11}" for c in cols)
              + f"  {r['launches']:>8}  {r['label']}" for r in programs]
    lines.append(f"devices ({unit}; memory_stats()):")
    lines += [f"  {d['device']}: " + ", ".join(
        f"{k} {cell(d[k])}" for k in _DEVICE_STATS) for d in devices]
    return {"programs": programs, "devices": devices,
            "text": "\n".join(lines)}


# --- adaptive train-window scheduler ---------------------------------------

def train_window_setting():
    """Parsed ``MXNET_TRAIN_WINDOW``: None (off), an int K > 1, or 'auto'."""
    raw = str(_env.get("MXNET_TRAIN_WINDOW")).strip().lower()
    if raw in ("", "0", "1", "off", "none", "false"):
        return None
    if raw == "auto":
        return "auto"
    try:
        k = int(raw)
    except ValueError:
        return None
    return k if k > 1 else None


def dispatch_depth_setting():
    """Parsed ``MXNET_DISPATCH_DEPTH``: 'auto' or an int >= 1."""
    raw = str(_env.get("MXNET_DISPATCH_DEPTH")).strip().lower()
    if raw in ("", "auto"):
        return "auto"
    try:
        d = int(raw)
    except ValueError:
        return "auto"
    return max(1, d)


def choose_dispatch_depth(dispatch_us, residual_us, max_depth=4):
    """Windows to keep in flight, from a measured per-step host profile.

    Depth 2 (double buffering) is the baseline pipeline answer: while
    window N executes on device, the host assembles and dispatches N+1,
    so the device never idles across a window boundary. A deeper queue
    only helps when the host's per-step work is dominated by dispatch
    itself (``dispatch_us`` > the residual): bursts of host time can
    then bubble a 2-deep queue, and one extra window of slack absorbs
    them. Depth never exceeds
    ``max_depth`` — every in-flight window pins K staged batches of
    device memory.
    """
    host = max(dispatch_us, 0.0) + max(residual_us, 0.0)
    if host <= 0:
        return 2
    share = max(dispatch_us, 0.0) / host
    return max(2, min(int(max_depth), 2 + int(share > 0.5)))


def choose_train_window(dispatch_us, residual_us, max_k=32,
                        overhead_budget=0.1):
    """Window depth K from a measured per-step host profile.

    ``dispatch_us``: average host time per step spent dispatching the train
    step (the ``fit.dispatch`` span). ``residual_us``: average host time
    per step spent everywhere else in the loop (data wait, metric,
    callbacks — the time a deeper window cannot recover). A window of K
    amortizes the per-dispatch cost to ``dispatch/K`` per step; K is the
    smallest depth that brings it under ``overhead_budget`` of the
    residual. Dispatch-bound profiles therefore get deep windows and
    device/data-bound profiles (dispatch already small next to the
    residual) get K=1.
    """
    if dispatch_us <= 0:
        return 1
    if residual_us <= 0:
        return max_k
    k = math.ceil(dispatch_us / (overhead_budget * residual_us))
    return max(1, min(int(k), int(max_k)))


class TrainWindowScheduler:
    """Drives ``Module.fit``'s fused-K step depth (``MXNET_TRAIN_WINDOW``).

    Fixed integer setting: every dispatch uses that K. ``auto``: the first
    ``SKIP_BATCHES`` steps are ignored (they carry compile time), the next
    ``PROBE_BATCHES`` run single-step while the ``fit.*`` phase histograms
    accumulate, then :func:`choose_train_window` locks K for the rest of
    training (lr schedules and metric updates move to window granularity,
    matching ``train_window`` semantics). A telemetry ``reset()`` during
    the probe (a caller discarding its compile epoch) restarts it. The decision
    is published on the ``fit.train_window_k`` gauge.

    The scheduler also owns the pipelined-dispatch depth (how many
    windows fit keeps in flight, ``MXNET_DISPATCH_DEPTH``): auto co-tunes
    (K, depth) from the same dispatch-vs-residual profile — depth >= 2
    whenever windows engage (:func:`choose_dispatch_depth`), and K then
    relaxes because the in-flight overlap already hides the per-window
    round trip. ``cap_depth`` lets fit force depth 1 for policies whose
    boundaries must fence (see docs/architecture.md, boundary-fence classes); the
    ``fit.dispatch_depth`` gauge reports the operative value either way.
    """

    SKIP_BATCHES = 2
    PROBE_BATCHES = 8
    _PHASES = ("fit.dispatch", "fit.data_wait", "fit.metric", "fit.callback")

    def __init__(self, setting, max_k=32, depth_setting=None):
        self.max_k = max_k
        self.auto = setting == "auto"
        self.k = 1 if self.auto else int(setting)
        self._decided = not self.auto
        self._batches = 0
        self._skipped = not self.auto
        self._base = {}
        self._depth_setting = (dispatch_depth_setting()
                               if depth_setting is None else depth_setting)
        self._depth_cap_reason = None
        self.depth = self._resolve_depth(None, None)
        _tm.gauge("fit.train_window_k").set(self.k)
        _tm.gauge("fit.dispatch_depth").set(self.depth)

    @staticmethod
    def from_env(module, monitor=None):
        """A scheduler for this fit run, or None when windows don't apply
        (env unset, module without train_window, or a monitor installed —
        monitored steps must stay per-batch and unfused)."""
        setting = train_window_setting()
        if setting is None or monitor is not None:
            return None
        if not callable(getattr(module, "train_window", None)):
            return None
        return TrainWindowScheduler(setting)

    def _resolve_depth(self, dispatch_us, residual_us):
        """Dispatch depth for the current K (+ optional measured profile).
        Policy caps win, then K<=1 forces 1 (no windows means no pipeline,
        whatever the env says — the per-batch loop pipelines through data
        prefetch), then a fixed env setting, then auto: 2 as the
        unprofiled window default, :func:`choose_dispatch_depth` once the
        probe measured the dispatch-vs-residual split."""
        if self._depth_cap_reason is not None:
            return 1
        if self.k <= 1:
            # no windows, no pipeline — even a fixed MXNET_DISPATCH_DEPTH
            # must not make the gauge claim a depth the per-batch loop
            # cannot deliver (an operator would chase a phantom
            # re-serialization)
            return 1
        if self._depth_setting != "auto":
            return int(self._depth_setting)
        if dispatch_us is None:
            return 2
        return choose_dispatch_depth(dispatch_us, residual_us)

    def cap_depth(self, reason):
        """Cap the dispatch depth at 1 — every window boundary fences —
        and record why. Used by fit for policies whose boundary semantics
        need a drained pipeline (MXNET_NONFINITE_GUARD=rollback); the
        ``fit.dispatch_depth`` gauge reports the capped value so a trace
        reader knows the depth is a policy decision, not a regression."""
        self._depth_cap_reason = str(reason)
        self.depth = 1
        _tm.gauge("fit.dispatch_depth").set(1)
        return self

    @property
    def depth_cap_reason(self):
        """Why the depth is capped at 1, or None."""
        return self._depth_cap_reason

    def _rebase(self):
        for name in self._PHASES:
            h = _tm.histogram(name)  # graftlint: allow=telemetry-catalog(reads the existing fit.* phase histograms enumerated in _PHASES; mints no names)
            self._base[name] = (h.count, h.sum)
        self._batches = 0

    def observe(self, n):
        """Record that ``n`` batches were dispatched since the last call."""
        self._batches += n

    def next_k(self):
        """The window depth for the next dispatch (decides when the probe
        completes)."""
        if self._decided:
            # re-assert the decision gauges: a telemetry reset (bench's
            # compile-epoch reset) zeroes them, and the steady state is
            # exactly what the post-reset snapshot must report
            _tm.gauge("fit.train_window_k").set(self.k)
            _tm.gauge("fit.dispatch_depth").set(self.depth)
            return self.k
        if not self._skipped:
            if self._batches >= self.SKIP_BATCHES:
                self._skipped = True
                self._rebase()
            return 1
        if self._batches < self.PROBE_BATCHES:
            return 1
        deltas = {}
        reset_seen = False
        for name, (c0, s0) in self._base.items():
            h = _tm.histogram(name)  # graftlint: allow=telemetry-catalog(reads the fit.* phase histograms rebased from _PHASES; mints no names)
            dc_, ds_ = h.count - c0, h.sum - s0
            # ANY negative delta means telemetry was reset mid-probe
            # (bench's compile-epoch reset) — a residual computed from a
            # mix of pre/post-reset sums would read as 0 and lock max_k
            # on a loop that may be device-bound
            if dc_ < 0 or ds_ < 0:
                reset_seen = True
            deltas[name] = (dc_, ds_)
        dc, ds = deltas["fit.dispatch"]
        if reset_seen or dc <= 0:
            # restart the probe from the zeroed instruments
            self._rebase()
            return 1
        residual = sum(s for n, (_c, s) in deltas.items()
                       if n != "fit.dispatch")
        dispatch_us, residual_us = ds / dc, residual / dc
        self.k = choose_train_window(dispatch_us, residual_us, self.max_k)
        self.depth = self._resolve_depth(dispatch_us, residual_us)
        if self.k > 1 and self.depth > 1:
            # co-tuning: with >= 2 windows in flight the per-window round
            # trip overlaps device execution, so K only has to amortize
            # the host's own dispatch work — the overhead budget relaxes
            # by the depth factor and K shrinks (shorter windows = finer
            # metric/callback granularity at the same throughput)
            self.k = max(2, choose_train_window(
                dispatch_us, residual_us, self.max_k,
                overhead_budget=0.1 * self.depth))
        self._decided = True
        _tm.gauge("fit.train_window_k").set(self.k)
        _tm.gauge("fit.dispatch_depth").set(self.depth)
        return self.k
