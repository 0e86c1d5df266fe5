"""Test configuration: force the CPU backend with 8 virtual devices.

Unit tests run on XLA:CPU with an 8-device virtual mesh so multi-chip
semantics are testable without hardware (SURVEY.md §4 implication); the
chip itself is exercised by ``chip_smoke.py``, never by pytest. Must happen
before the jax backend initialises.
"""

import json
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
# Every test builds its subprocess env from os.environ (or inherits it), so
# pinning the platform HERE, once, keeps every spawned child — compiled
# C/C++ clients with embedded CPython included — on the host backend too:
# an accelerator belongs to one process at a time, and a child that reached
# for one the pytest process holds would fail or hang.
os.environ["JAX_PLATFORMS"] = "cpu"
# unit tests must not read (or populate) a developer's warm executable
# cache — subprocess cache-contract tests opt back in with their own dir
os.environ.pop("MXNET_AOT_CACHE", None)
# ...nor jax's persistent compilation cache, which the package otherwise
# places at <checkout>/.jax_cache: a suite whose compiles depend on what an
# earlier run left on disk is not hermetic (tests/test_compile_cache.py
# opts back in, in subprocesses with directories of its own)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax
import pytest

jax.config.update("jax_platforms", "cpu")


_DIST_PROBE = None  # None = not probed yet; True/False = cached verdict

# Seconds each test file takes, written by ``tools/test_durations.py`` from
# a tier-1 run's junit. ``--dist loadfile`` hands files to the workers in
# collection order, and alphabetical order starts the heaviest last: six
# workers ended at 1448 s where their 7024 s of tests, evenly loaded, are
# 1171 (PR 49). Heaviest first, a file's own items together and in their
# order; ``tests/test_collection_order.py`` holds the table to the files.
DURATIONS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "durations.json")


def _file_seconds():
    with open(DURATIONS) as f:
        return json.load(f)


def _dist_collectives_supported():
    """Probe (once per session): can this backend execute a CROSS-PROCESS
    collective? XLA:CPU cannot ("Multiprocess computations aren't
    implemented on the CPU backend") — the 8-device virtual mesh above is
    single-process only. Spawn a real 2-rank dist_sync allreduce through
    tools/launch.py (the exact op the dist tests exercise) and see if it
    completes; TPU/GPU pods pass, CPU-only hosts skip."""
    global _DIST_PROBE
    if _DIST_PROBE is not None:
        return _DIST_PROBE
    import socket
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    worker = (
        "import os; os.environ['JAX_PLATFORMS'] = "
        "os.environ.get('JAX_PLATFORMS', 'cpu');"
        "import mxnet_tpu as mx;"
        "kv = mx.kv.create('dist_sync');"
        "a = mx.nd.ones((2,)); kv.init(0, a); kv.push(0, a);"
        "out = mx.nd.zeros((2,)); kv.pull(0, out=out);"
        "print('DIST-PROBE OK', float(out.asnumpy().sum()), flush=True)"
    )
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # ranks get their own un-virtualized jax
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, os.path.join(root, "tools", "launch.py"),
           "-n", "2", "--launcher", "local", "--port", str(port),
           sys.executable, "-c", worker]
    try:
        proc = subprocess.run(cmd, env=env, capture_output=True,
                              text=True, timeout=120)
        _DIST_PROBE = (proc.returncode == 0
                       and proc.stdout.count("DIST-PROBE OK") >= 2)
    except (subprocess.TimeoutExpired, OSError):
        _DIST_PROBE = False
    return _DIST_PROBE


def pytest_collection_modifyitems(config, items):
    """Skip capability-gated tests on backends missing the capability:
    @pytest.mark.aot_serialization when compiled executables cannot
    serialize (probed via mxnet_tpu.aot), @pytest.mark.dist_multiprocess
    when cross-process collectives cannot execute (probed via a 2-rank
    launch). Then the files that take longest go first
    (``durations.json``): a stable sort, the same in every xdist worker."""
    import pytest

    marked = [item for item in items
              if "aot_serialization" in item.keywords]
    if marked:
        from mxnet_tpu import aot

        if not aot.supports_serialization():
            skip = pytest.mark.skip(
                reason="backend cannot serialize compiled executables")
            for item in marked:
                item.add_marker(skip)

    dist_marked = [item for item in items
                   if "dist_multiprocess" in item.keywords]
    if dist_marked and not _dist_collectives_supported():
        skip = pytest.mark.skip(
            reason="backend cannot execute multiprocess collectives "
                   "(XLA:CPU); probed via a 2-rank dist_sync allreduce")
        for item in dist_marked:
            item.add_marker(skip)

    seconds = _file_seconds()
    items.sort(key=lambda item: -seconds.get(item.path.name, 0))


@pytest.fixture(autouse=True)
def _sanitize_marked(request):
    """Run `sanitize`-marked tests under the runtime lock-order sanitizer
    (mxnet_tpu.analysis.sanitizer): threading.Lock/RLock are swapped for
    instrumented wrappers for the duration of the test, and any ABBA
    cycle observed in the process-wide lock-order graph fails the test
    with both acquisition stacks. Opt out with MXNET_SANITIZER=0 (the
    tier-1 default is ON for marked suites)."""
    if request.node.get_closest_marker("sanitize") is None \
            or os.environ.get("MXNET_SANITIZER", "1") == "0":
        yield
        return

    from mxnet_tpu.analysis import sanitizer

    sanitizer.install()
    sanitizer.reset()
    try:
        yield
    finally:
        rep = sanitizer.report()
        sanitizer.uninstall()
        sanitizer.reset()
    if rep["cycles"]:
        pytest.fail("runtime sanitizer observed lock-order cycle(s):\n"
                    + sanitizer.format_report(rep), pytrace=False)
