"""What the chip bring-up relies on, as far as a CPU can check it.

* jax's persistent compilation cache is placed from OUTSIDE when
  ``JAX_COMPILATION_CACHE_DIR`` is set, and otherwise at one fixed path
  derived from the package's own location (``<checkout>/.jax_cache``) — the
  same path in every process, so a second run finds what the first compiled.
* An accelerator context never lands on the host: ``mx.tpu(0)`` raises on
  the CPU backend, and ``chip_smoke.py`` refuses to run without a TPU.
* The fused window the chip runs donates what it says it donates
  (``tools/hlo_audit.py``), and ``__graft_entry__.entry()`` traces.

The cache tests run in subprocesses: conftest switches the cache off for the
pytest process itself (a hermetic suite does not read a developer's cache).
"""

import json
import os
import subprocess
import sys
import time

import pytest

import mxnet_tpu as mx

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHECKOUT_CACHE = os.path.join(_ROOT, ".jax_cache")

_TINY_STEP = """
import numpy as np
import jax
import mxnet_tpu as mx

d = mx.sym.Variable("data")
net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(d, num_hidden=4), name="softmax")
it = mx.io.NDArrayIter(np.ones((8, 6), np.float32), np.zeros((8,), np.float32),
                       batch_size=8)
mx.mod.Module(net, context=mx.cpu()).fit(it, num_epoch=1)
print("CACHE_DIR=" + str(jax.config.jax_compilation_cache_dir))
"""

_PRINT_DIR = """
import jax
import mxnet_tpu
print("CACHE_DIR=" + str(jax.config.jax_compilation_cache_dir))
"""

def _run(code, **env_overrides):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [_ROOT, env.get("PYTHONPATH")]))
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_COMPILATION_CACHE"] = "true"  # conftest turned it off
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_overrides)
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd="/",
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return dict(l.split("=", 1) for l in proc.stdout.splitlines()
                if "=" in l and l.split("=", 1)[0].isupper())


def _listing(path):
    return sorted(os.listdir(path)) if os.path.isdir(path) else None


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    outside = tmp_path / "cache"
    before = _listing(_CHECKOUT_CACHE)
    used = _run(_TINY_STEP, JAX_COMPILATION_CACHE_DIR=str(outside))
    assert used["CACHE_DIR"] == str(outside)
    assert _listing(outside), "the train step cached nothing"
    assert _listing(_CHECKOUT_CACHE) == before, (
        "entries were written under the checkout although "
        "JAX_COMPILATION_CACHE_DIR names another directory")


def test_compile_cache_default_is_one_fixed_checkout_path():
    assert _run(_PRINT_DIR)["CACHE_DIR"] == _CHECKOUT_CACHE
    assert _run(_PRINT_DIR)["CACHE_DIR"] == _CHECKOUT_CACHE


def test_accelerator_context_raises_on_the_cpu_backend():
    for ctx in (mx.tpu(0), mx.gpu(0)):
        with pytest.raises(mx.MXNetError, match="no accelerator"):
            ctx.jax_device()
    assert mx.num_gpus() == 0
    assert mx.cpu(0).jax_device().platform == "cpu"


def test_chip_smoke_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    tic = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "chip_smoke.py")], env=env,
        cwd=_ROOT, capture_output=True, text=True, timeout=60)
    assert time.monotonic() - tic < 60
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1 and "cpu" in lines[0], proc.stdout
    assert '"ok"' not in proc.stdout


def test_hlo_audit_fused_window_clean(tmp_path):
    """tools/hlo_audit.py on the fused resnet-18 window program: every
    donated buffer must be aliased in the compiled executable (zero
    un-aliased donations, zero silently dropped marks) and the bf16
    recipe must show no stray f32 upcasts beyond the per-step gradient
    promotions the master-weight design requires."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_AOT_CACHE="0")
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [_ROOT, env.get("PYTHONPATH")]))
    out = str(tmp_path / "verdict.json")
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "hlo_audit.py"),
         "--layers", "18", "--batch", "2", "--window", "2", "--json", out],
        capture_output=True, text=True, env=env, timeout=900, cwd=_ROOT,
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    with open(out) as f:
        verdict = json.load(f)
    assert verdict["ok"] is True, verdict
    assert verdict["unaliased_donations"] == [], verdict
    assert verdict["dropped_donations"] == 0, verdict
    assert verdict["donated_args"] > 0, verdict
    assert verdict["aliased_args"] + verdict["donor_args"] \
        == verdict["donated_args"], verdict
    assert verdict["stray_upcasts"] == {}, verdict


def test_graft_entry_single_chip_compiles():
    """entry() returns a jittable forward; eval_shape validates the trace
    without paying device compile time."""
    import jax

    sys.path.insert(0, _ROOT)
    import __graft_entry__ as g

    fn, (args, auxs) = g.entry()
    out = jax.eval_shape(fn, args, auxs)
    assert tuple(out.shape) == (8, 1000)
