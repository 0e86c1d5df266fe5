"""Test configuration: force the CPU backend with 8 virtual devices.

Unit tests run on XLA:CPU with an 8-device virtual mesh so multi-chip
semantics are testable without hardware (SURVEY.md §4 implication); the
chip itself is exercised by ``chip_smoke.py``, never by pytest. Must happen
before the jax backend initialises.
"""

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

# Seconds a test file took in the driver's tier-1 run on PR 49's tree (its
# junit times summed a file; every file over 100 s; ``test_keye_vl2.py`` a
# builder's reading under six workers at PR 51, 159 s where
# ``test_olmoe.py`` read 150, on this table's scale). ``--dist loadfile``
# hands files to the workers in collection order, and alphabetical order
# starts the heaviest last: six workers ended at 1448 s where their 7024 s of
# tests, evenly loaded, are 1171. Heaviest first, a file's own items together
# and in their order; a file not named here keeps its place after them.
_FILE_SECONDS = {
    "test_qwen3_next.py": 714, "test_kimi_linear.py": 702,
    "test_trinity.py": 666, "test_zaya.py": 556, "test_bench_smoke.py": 523,
    "test_kanana2.py": 389, "test_causal_conv_kernels.py": 285,
    "test_recompute_residuals.py": 274, "test_olmoe.py": 209,
    "test_keye_vl2.py": 200, "test_moe_routing.py": 194, "test_gated_delta_kernels.py": 189,
    "test_gated_delta_channel.py": 166, "test_flash_attention.py": 164,
    "test_model_zoo.py": 136, "test_elastic_checkpoint.py": 130,
    "test_grouped_matmul.py": 115, "test_ouro.py": 115,
    "test_gated_delta_channel_kernels.py": 87, "test_sdar.py": 80,
    "test_selected_kernels.py": 57, "test_diffusion_kernels.py": 35,
    "test_fit_publishes_nothing.py": 26, "test_memory_ledger.py": 10,
}


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
    launch). Then the files that take longest go first (``_FILE_SECONDS``):
    a stable sort, the same in every xdist worker."""
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

    items.sort(key=lambda item: -_FILE_SECONDS.get(item.path.name, 0))


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
