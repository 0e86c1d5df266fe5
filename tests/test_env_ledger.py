"""Environment-robustness ledger: a bare nested interpreter — spawned the
way the compiled C clients spawn embedded CPython, with NONE of the test
process's environment — must reach a working ``import jax`` promptly.

The failure this fences is a HANG at backend start-up: an interpreter whose
environment makes it reach for a device another process holds (an
accelerator belongs to one process at a time) waits instead of starting.
conftest.py pins the pytest process and its children to the host backend;
THIS test pins the contract from the other side — an interpreter with a
minimal, explicitly-constructed environment initialises jax on CPU within
the budget, so environment drift of that kind fails the suite instead of
hanging the C-client tests.
"""

import os
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.lint  # rides with the static-invariant suite

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: seconds a bare interpreter gets to import + use jax on CPU. Importing
#: jax cold takes a few seconds; the failure mode being fenced is a HANG
#: (waiting on a device it cannot have), which is minutes — the gap is
#: unambiguous.
IMPORT_BUDGET_S = 120


def _bare_env(**extra):
    """The environment a C client's embedded interpreter effectively has:
    PATH/HOME only — no MXNET_*, no TPU_*, no other JAX_* inherited."""
    env = {
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "HOME": os.environ.get("HOME", "/tmp"),
        "JAX_PLATFORMS": "cpu",
    }
    env.update(extra)
    return env


def _timed_run(code, env):
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=IMPORT_BUDGET_S)
    return proc, time.monotonic() - t0


def test_bare_interpreter_reaches_jax_within_budget():
    code = (
        "import jax, jax.numpy as jnp\n"
        "print(int(jnp.add(20, 22)), jax.default_backend())\n"
    )
    try:
        proc, elapsed = _timed_run(code, _bare_env())
    except subprocess.TimeoutExpired:
        pytest.fail(
            f"bare interpreter did not reach `import jax` within "
            f"{IMPORT_BUDGET_S} s — a nested interpreter with a bare "
            "environment hangs at backend init")
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split()[0] == "42"
    assert elapsed < IMPORT_BUDGET_S


def test_bare_interpreter_imports_the_framework():
    """Same fence one layer up: ``import mxnet_tpu`` (what the C shim's
    embedded interpreter actually runs) from a bare env must work — it
    must not require launcher-exported rank/coordinator state."""
    code = "import mxnet_tpu as mx; print(mx.nd.array([1.0])[0:1].shape)"
    try:
        proc, _ = _timed_run(code, _bare_env(PYTHONPATH=ROOT))
    except subprocess.TimeoutExpired:
        pytest.fail(
            f"bare `import mxnet_tpu` exceeded {IMPORT_BUDGET_S} s — "
            "package import is blocking on environment it must not need")
    assert proc.returncode == 0, proc.stderr
    assert "(1,)" in proc.stdout
