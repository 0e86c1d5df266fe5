"""Crash-consistent checkpointing: atomic commits, manifest digests,
corruption fallback, retention, auto-resume, and the kill-resume
end-to-end path (subprocess hard-killed mid-epoch by faultinject, then
relaunched and provably resumed from the last committed checkpoint)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import checkpoint as ckpt
from mxnet_tpu import faultinject as fi
from mxnet_tpu import telemetry as tm

# the async writer thread hands checkpoints off under a condition: run
# the suite under the runtime lock-order sanitizer in tier-1
pytestmark = pytest.mark.sanitize

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _mlp():
    data = mx.sym.Variable("data")
    h = mx.sym.Activation(
        mx.sym.FullyConnected(data, num_hidden=8, name="fc1"),
        act_type="relu")
    return mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(h, num_hidden=4, name="fc2"), name="softmax")


def _fit_module(tmpdir, num_epoch=2, **fit_kwargs):
    rng = np.random.RandomState(0)
    X = rng.randn(32, 10).astype(np.float32)
    Y = rng.randint(0, 4, (32,)).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=8)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=num_epoch,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            **fit_kwargs)
    return mod, it


# --- atomic primitives ------------------------------------------------------

def test_atomic_path_commits_and_aborts(tmp_path):
    target = tmp_path / "file.bin"
    with ckpt.atomic_path(str(target)) as tmp:
        with open(tmp, "wb") as f:
            f.write(b"hello")
    assert target.read_bytes() == b"hello"
    # failure mid-write: final file untouched, temp cleaned up
    with pytest.raises(RuntimeError):
        with ckpt.atomic_path(str(target)) as tmp:
            with open(tmp, "wb") as f:
                f.write(b"torn")
            raise RuntimeError("crash mid-write")
    assert target.read_bytes() == b"hello"
    assert [p for p in os.listdir(tmp_path) if p.startswith(".tmp-")] == []


def test_module_save_checkpoint_is_atomic(tmp_path, monkeypatch):
    """The legacy callback path (module_checkpoint/do_checkpoint) rides the
    atomic writer: no torn .params even if nd save explodes mid-file."""
    mod, _ = _fit_module(tmp_path)
    prefix = str(tmp_path / "legacy")
    cb = mx.callback.module_checkpoint(mod, prefix)
    cb(0)  # epoch 0 fires with period=1
    assert os.path.exists(prefix + "-0001.params")
    sym, arg, aux = mx.model.load_checkpoint(prefix, 1)
    assert "fc1_weight" in arg

    import mxnet_tpu.ndarray as nd_mod

    def boom(fname, data):
        with open(fname, "wb") as f:
            f.write(b"partial garbage")
        raise IOError("disk full mid-write")

    monkeypatch.setattr(nd_mod, "save", boom)
    monkeypatch.setattr(mx.nd, "save", boom)
    with pytest.raises(IOError):
        mod.save_checkpoint(prefix, 2)
    # the torn write never reached the final filename
    assert not os.path.exists(prefix + "-0002.params")


def test_load_checkpoint_rejects_unknown_prefix(tmp_path):
    """Satellite: keys outside arg:/aux: raise instead of silently
    dropping parameters."""
    bad = {"arg:w": mx.nd.array(np.ones(2, np.float32)),
           "oops:v": mx.nd.array(np.ones(2, np.float32))}
    sym = _mlp()
    prefix = str(tmp_path / "model")
    sym.save(prefix + "-symbol.json")
    mx.nd.save(prefix + "-0001.params", bad)
    with pytest.raises(ValueError, match="arg:"):
        mx.model.load_checkpoint(prefix, 1)


# --- manifested checkpoints -------------------------------------------------

def test_manifest_contents_and_digests(tmp_path):
    d = str(tmp_path / "ckpts")
    _fit_module(tmp_path, num_epoch=2,
                checkpoint=mx.CheckpointConfig(d, period=1))
    names = sorted(n for n in os.listdir(d) if n.startswith("ckpt-"))
    assert names, "no checkpoint written"
    latest = open(os.path.join(d, "LATEST")).read().strip()
    assert latest == names[-1]
    with open(os.path.join(d, latest, "manifest.json")) as f:
        m = json.load(f)
    assert m["next_epoch"] == 2 and m["next_batch"] == 0
    assert m["optimizer"]["num_update"] == 8  # 4 batches x 2 epochs
    for fname, meta in m["files"].items():
        p = os.path.join(d, latest, fname)
        assert os.path.getsize(p) == meta["bytes"]
        assert ckpt.sha256_file(p) == meta["sha256"]
    # format v2: per-process shard containers instead of one replicated
    # params blob; optimizer state rides its own shard file per rank
    assert m["format"] == 2
    assert "shard-00000.params" in m["files"]
    assert "shard-00000.opt" in m["files"]
    assert "commit-00000.json" in m["files"]
    # every logical parameter is described and fully covered by shards
    assert "fc1_weight" in m["params"]
    assert m["params"]["fc1_weight"]["kind"] == "arg"
    ckpt._verify_coverage(m)
    # per-parameter optimizer state templates (restore is by name)
    assert "fc1_weight" in m["opt_states"]
    assert m["rng_key"] is not None and m["env"]


def test_keep_n_retention(tmp_path):
    d = str(tmp_path / "ckpts")
    _fit_module(tmp_path, num_epoch=5,
                checkpoint=mx.CheckpointConfig(d, period=1, keep_n=2))
    names = sorted(n for n in os.listdir(d) if n.startswith("ckpt-"))
    assert names == ["ckpt-e00004-b00000000", "ckpt-e00005-b00000000"]


def test_truncated_checkpoint_falls_back(tmp_path, caplog):
    """A torn/corrupted newest checkpoint is never loaded: digest
    verification rejects it and load returns the previous valid one."""
    d = str(tmp_path / "ckpts")
    _fit_module(tmp_path, num_epoch=3,
                checkpoint=mx.CheckpointConfig(d, period=1, keep_n=3))
    names = sorted(n for n in os.listdir(d) if n.startswith("ckpt-"))
    fi.corrupt_file(os.path.join(d, names[-1], "shard-00000.params"),
                    "truncate")
    c0 = tm.counter("checkpoint.corrupt").value
    with caplog.at_level("WARNING"):
        loaded = ckpt.load_latest(d)
    assert loaded is not None and loaded.path.endswith(names[-2])
    assert tm.counter("checkpoint.corrupt").value == c0 + 1
    assert any("corrupt" in r.message for r in caplog.records)

    # garbage (bit-flip) corruption is also caught by the sha256
    fi.corrupt_file(os.path.join(d, names[-2], "shard-00000.params"),
                    "garbage")
    loaded = ckpt.load_latest(d)
    assert loaded is not None and loaded.path.endswith(names[-3])

    # every checkpoint corrupt -> None, not a crash
    fi.corrupt_file(os.path.join(d, names[-3], "shard-00000.params"),
                    "truncate")
    assert ckpt.load_latest(d) is None


def test_env_driven_corruption_injection(tmp_path, monkeypatch):
    """MXNET_FI_CORRUPT_CKPT damages each params file right after commit;
    digest-verified load must skip them all (fault-injection driven)."""
    d = str(tmp_path / "ckpts")
    monkeypatch.setenv("MXNET_FI_CORRUPT_CKPT", "truncate")
    try:
        _fit_module(tmp_path, num_epoch=2,
                    checkpoint=mx.CheckpointConfig(d, period=1))
    finally:
        monkeypatch.delenv("MXNET_FI_CORRUPT_CKPT")
    assert sorted(n for n in os.listdir(d) if n.startswith("ckpt-"))
    assert ckpt.load_latest(d) is None  # all damaged -> all rejected


def test_fit_resume_continues_from_checkpoint(tmp_path):
    """In-process resume: a second fit over the same directory starts at
    the checkpointed epoch with identical params."""
    d = str(tmp_path / "ckpts")
    mod1, it = _fit_module(tmp_path, num_epoch=2,
                           checkpoint=mx.CheckpointConfig(d))
    w1 = mod1._exec_group._exec.arg_dict["fc1_weight"].asnumpy().copy()
    u1 = mod1._optimizer.num_update

    mod2 = mx.mod.Module(_mlp(), context=mx.cpu())
    it.reset()
    c0 = tm.counter("checkpoint.resume").value
    # num_epoch equals the checkpointed epoch -> resume, then nothing to do
    mod2.fit(it, num_epoch=2,
             optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
             checkpoint=mx.CheckpointConfig(d))
    assert tm.counter("checkpoint.resume").value == c0 + 1
    w2 = mod2._exec_group._exec.arg_dict["fc1_weight"].asnumpy()
    np.testing.assert_array_equal(w1, w2)
    assert mod2._optimizer.num_update == u1


def test_batch_tick_fires_on_period_crossing(tmp_path):
    """Window dispatch advances nbatch by K per tick; saves must fire on
    CROSSING a batch_period boundary, not on exact divisibility."""
    saves = []

    class Spy(ckpt.CheckpointManager):
        def save(self, next_epoch, next_batch, epoch=None, nbatch=None):
            saves.append((next_epoch, next_batch))

    mgr = Spy(mx.CheckpointConfig(str(tmp_path), batch_period=10))
    for nbatch in range(8, 81, 8):  # K=8 windows: 8,16,24,...,80
        mgr.batch_tick(0, nbatch)
    assert saves == [(0, 16), (0, 24), (0, 32), (0, 40), (0, 56),
                     (0, 64), (0, 72), (0, 80)]
    # a new epoch resets the mark
    saves.clear()
    mgr.batch_tick(1, 8)
    mgr.batch_tick(1, 16)
    assert saves == [(1, 16)]


# --- kill-resume end-to-end -------------------------------------------------

def _run_worker(env, timeout=240):
    e = dict(os.environ)
    e["PYTHONPATH"] = os.pathsep.join(
        filter(None, [_ROOT, e.get("PYTHONPATH")]))
    e["JAX_PLATFORMS"] = "cpu"
    e.pop("XLA_FLAGS", None)
    e.update(env)
    return subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tests",
                                      "ckpt_resume_worker.py")],
        capture_output=True, text=True, env=e, timeout=timeout, cwd=_ROOT,
    )


def test_kill_resume_single_host(tmp_path):
    """Acceptance: a training job hard-killed mid-epoch (fault-injected
    os._exit) relaunches and PROVABLY resumes from the last checkpoint —
    epoch/batch cursor and optimizer update count match the manifest —
    then converges."""
    d = str(tmp_path / "ckpts")
    base = {
        "MXNET_CHECKPOINT_DIR": d,
        "MXNET_CHECKPOINT_BATCH_PERIOD": "3",
        "MXNET_CHECKPOINT_KEEP": "4",
    }
    # first life: die at global batch 20 (epoch 2, batch 4 of 8)
    r1 = _run_worker({**base, "MXNET_FI_CRASH_AT_BATCH": "20"})
    out1 = r1.stdout + r1.stderr
    assert r1.returncode == 17, out1[-3000:]
    assert "faultinject: CRASH at train batch 20" in out1, out1[-3000:]
    assert "RESUME epoch=-1" in out1  # first life started fresh

    # the manifest the relaunch must resume from
    loaded = ckpt.load_latest(d)
    assert loaded is not None
    exp_e, exp_b = loaded.next_epoch, loaded.next_batch
    exp_updates = loaded.manifest["optimizer"]["num_update"]
    # crash at global batch 20 with batch_period 3 -> last commit covers
    # epoch 2 batch 3 = 19 trained batches
    assert (exp_e, exp_b) == (2, 3) and exp_updates == 19

    # second life (launcher convention: MXNET_NUM_RESTARTS=1 disarms the
    # injection via MXNET_FI_ATTEMPT=0 default)
    r2 = _run_worker({**base, "MXNET_FI_CRASH_AT_BATCH": "20",
                      "MXNET_NUM_RESTARTS": "1"})
    out2 = r2.stdout + r2.stderr
    assert r2.returncode == 0, out2[-3000:]
    assert f"RESUME epoch={exp_e} batch={exp_b} " \
           f"num_update={exp_updates}" in out2, out2[-3000:]
    assert "Resuming from checkpoint" in out2
    done = [l for l in out2.splitlines() if l.startswith("TRAIN-DONE")]
    assert done, out2[-3000:]
    acc = float(done[0].split("acc=")[1].split()[0])
    assert acc > 0.8, f"post-resume training stuck at {acc}"
    # resumed run trained exactly the REMAINING batches: 6*8 total
    final_update = int(done[0].split("final_update=")[1])
    assert final_update == 48
