"""What every driver shares: finding files by name, the device stamp, the
telemetry reader, the profiler trace around one slice, the result line.

The harness has no list of cells, configurations, traffic mixes or metrics:
``BENCHMARK.json`` names them and each is a file found by that name.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


class BenchError(Exception):
    """The run cannot give a result; exit non-zero, print no metric."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path, name):
    """A module from a file whose name need not be an identifier."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def find_cell(name, root=ROOT):
    """(benchmark, workload, config entry, config, traffic) for a cell."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(have: {sorted(cells)})")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(root, entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return bench, cell, entry, config, traffic


def config_module(kind, config_name):
    """``configs/<name>.py`` (the builder) or ``reference/<name>.py``."""
    path = os.path.join(HERE, kind, config_name + ".py")
    return load_module(path, f"benchmark_{kind}_{config_name}")


def load_driver(name):
    return load_module(os.path.join(HERE, "drivers", name + ".py"),
                       f"benchmark_driver_{name}")


def layer_readers():
    """{metric name: module} of every file under ``layers/``."""
    out = {}
    folder = os.path.join(HERE, "layers")
    for fn in sorted(os.listdir(folder)):
        if fn.endswith(".py") and not fn.startswith("_"):
            mod = load_module(os.path.join(folder, fn),
                              "benchmark_layer_" + fn[:-3])
            out[mod.NAME] = mod
    return out


def metrics_of(bench, cell_name, group):
    """Names of the ``group`` metrics that ``cell_name`` reports."""
    return [m["name"] for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def peaks_of(device_kind):
    table = load_json(HERE, "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"benchmark/peaks.json: add it with its source")
    return table[device_kind]


def require_tpu(jax, chips):
    """The devices of this run, or BenchError on anything but enough TPUs."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise BenchError(f"needs {chips} TPU chip(s); jax found {len(devs)} "
                         f"x {devs[0].platform} ({devs[0].device_kind})")
    return devs[:chips]


def device_stamp(devices):
    """Platform, kind, count and the peak HBM of the fullest chip, with its
    two parts beside it: the allocator's high-water mark
    (``peak_bytes_in_use``: parameters, optimizer state, batches, outputs)
    plus what the runtime holds RESERVED for the loaded programs' scratch
    (``peak_bytes_reserved``). On the v5e a program's temporaries are in
    the second and not in the first: the ResNet step's ``memory_analysis``
    gives 5.16 GiB of temporaries and the chip reads 5.12 GiB reserved
    (rehearsal and my chip run, PR 23). After every window ``bytes_reserved``
    equals its peak: the reserve does not move once the programs are
    loaded, so the sum of the two peaks is the peak of the sum."""
    peak = (0, 0)
    for d in devices:
        stats = d.memory_stats() or {}
        parts = (int(stats.get("peak_bytes_in_use", 0)),
                 int(stats.get("peak_bytes_reserved", 0)))
        peak = max(peak, parts, key=sum)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": sum(peak),
            "memory_peak_in_use_bytes": peak[0],
            "memory_peak_reserved_bytes": peak[1]}


# --- telemetry -------------------------------------------------------------

def tm_leaf(snapshot, name):
    """The instrument ``name`` in a ``telemetry.snapshot()`` tree."""
    node = snapshot
    for part in name.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    if isinstance(node, dict) and "" in node:
        node = node[""]
    return node


def tm_delta(before, after, name, field=None):
    """Change of a counter (``field`` None) or of a histogram's ``sum`` /
    ``count`` between two snapshots; 0 where the instrument is absent."""
    def read(snap):
        leaf = tm_leaf(snap, name)
        if leaf is None:
            return 0
        if field is None:
            return leaf if not isinstance(leaf, dict) else 0
        return leaf.get(field, 0) if isinstance(leaf, dict) else 0
    return read(after) - read(before)


SYNC_COUNTERS = ("ndarray.asnumpy", "ndarray.wait_to_read")


class SyncLedger:
    """Host syncs the harness itself causes through the program's counted
    API, so that they can be taken out of the program's count."""

    def __init__(self, tm):
        self._tm = tm
        self.own = 0

    def total(self):
        return sum(self._tm.counter(n).value for n in SYNC_COUNTERS)

    def __enter__(self):
        self._at = self.total()
        return self

    def __exit__(self, *exc):
        self.own += self.total() - self._at
        return False


class GcLog:
    """Every collection of Python's collector from now on, timed on
    ``time.perf_counter``: a host pause the program's spans do not name."""

    def __init__(self):
        self.events = []  # (generation, start, seconds)
        self._start = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            self.events.append((info["generation"], self._start,
                                time.perf_counter() - self._start))

    def close(self):
        gc.callbacks.remove(self._on)

    def between(self, lo, hi, n=5):
        """Count and summed seconds of the collections that began inside
        [lo, hi], and the ``n`` longest as [generation, seconds after lo,
        seconds]."""
        inside = [e for e in self.events if lo <= e[1] <= hi]
        longest = sorted(inside, key=lambda e: -e[2])[:n]
        return {"collections": len(inside),
                "seconds": sum(e[2] for e in inside),
                "longest": [[g, t - lo, d] for g, t, d in longest]}


# --- the profiler around one slice ------------------------------------------

class Tracer:
    """Starts and stops jax's profiler into a directory of the checkout and
    reduces what it wrote. Off (every call a no-op) without ``--trace 1``."""

    def __init__(self, jax, on, workload):
        self.jax, self.on = jax, on
        self.dir = os.path.join(ROOT, "benchmark_out", "trace", workload)
        self.result = None
        self.layout = None
        self._span = None

    def start(self):
        if not self.on:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        self.jax.profiler.start_trace(self.dir)
        self._span = self.annotate("bench.traced_slice")
        self._span.__enter__()

    def stop(self):
        if not self.on:
            return
        self._span.__exit__(None, None, None)
        self.jax.profiler.stop_trace()

    def annotate(self, name):
        return self.jax.profiler.TraceAnnotation(name)

    def reduce(self):
        from . import trace as tr

        path = tr.find_xplane(self.dir)
        if path is None:
            raise BenchError(f"the profiler wrote no .xplane.pb in {self.dir}")
        ops, spans, self.layout = tr.load(path)
        try:
            self.result = tr.reduce(ops, spans, "bench.traced_slice")
        except tr.ClocksDisagree as e:
            raise BenchError(str(e)) from e
        if self.result is None or self.result["busy_s"] <= 0:
            raise BenchError("the trace holds no device operation; planes: "
                             f"{sorted({p for p, _, _ in self.layout})}")
        shutil.rmtree(self.dir, ignore_errors=True)
        return self.result


def new_run(*, args, seconds, bench, cell, config, traffic, devices, peaks,
            mx, jax, ctx_of, t_start):
    """What a driver is handed: run.py builds it for the TPU, the tests for
    the CPU (``ctx_of=mx.cpu``), and nothing else differs between them."""
    from .compile_clock import CompileClock

    return {
        "args": args, "seconds": float(seconds), "bench": bench, "cell": cell,
        "config": config, "traffic": traffic, "devices": devices,
        "peaks": peaks, "mx": mx, "jax": jax, "ctx_of": ctx_of,
        "t_start": t_start, "clock": CompileClock(),
        "builder": config_module("configs", cell["config"]),
        "tracer": Tracer(jax, bool(args.trace), cell["name"]),
    }


def emit(line):
    print(json.dumps(line), flush=True)
