"""Elastic sharded checkpoints (format v2): mesh-aware save, cross-topology
restore, resume consensus, async bounded-stall writes, and the
kill-during-save chaos matrix.

The cross-topology oracle is an uninterrupted run: params + optimizer
state (momentum) saved under one GraftMesh must restore under a DIFFERENT
mesh — re-staged pipelines included — and training forward from the
restore must land exactly where the uninterrupted source run lands.
"""

import contextlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import checkpoint as ckpt
from mxnet_tpu import parallel
from mxnet_tpu import telemetry as tm
from mxnet_tpu.parallel.mesh import GraftMesh
from mxnet_tpu.test_utils import assert_almost_equal

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

BATCH, DIM, HID, NCLS = 16, 8, 12, 5


# --------------------------------------------------------------------------
# one logical chain (st0_fc -> st1_fc -> st2_fc -> st_last_fc), staged
# three ways: 4 pipeline stages, 2 pipeline stages, or one plain module.
# Param names are identical across stagings — that's what makes a
# checkpoint written under one topology meaningful under another.
# --------------------------------------------------------------------------

def _four_stage_syms():
    syms = []
    for i in range(3):
        data = mx.sym.Variable("data")
        fc = mx.sym.FullyConnected(data, num_hidden=HID, name=f"st{i}_fc")
        syms.append(mx.sym.Activation(fc, act_type="tanh",
                                      name=f"st{i}_act"))
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=NCLS, name="st_last_fc")
    syms.append(mx.sym.SoftmaxOutput(fc, name="softmax"))
    return syms


def _two_stage_syms():
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=HID, name="st0_fc")
    h = mx.sym.Activation(h, act_type="tanh", name="st0_act")
    h = mx.sym.FullyConnected(h, num_hidden=HID, name="st1_fc")
    s0 = mx.sym.Activation(h, act_type="tanh", name="st1_act")
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=HID, name="st2_fc")
    h = mx.sym.Activation(h, act_type="tanh", name="st2_act")
    h = mx.sym.FullyConnected(h, num_hidden=NCLS, name="st_last_fc")
    s1 = mx.sym.SoftmaxOutput(h, name="softmax")
    return [s0, s1]


def _chain_sym():
    h = mx.sym.Variable("data")
    for i in range(3):
        h = mx.sym.FullyConnected(h, num_hidden=HID, name=f"st{i}_fc")
        h = mx.sym.Activation(h, act_type="tanh", name=f"st{i}_act")
    h = mx.sym.FullyConnected(h, num_hidden=NCLS, name="st_last_fc")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _seq_from_syms(mesh, syms):
    seq = mx.mod.SequentialModule()
    for i, s in enumerate(syms[:-1]):
        seq.add(mx.mod.Module(s, data_names=("data",), label_names=None),
                auto_wiring=i > 0)
    seq.add(mx.mod.Module(syms[-1], data_names=("data",),
                          label_names=("softmax_label",)),
            take_labels=True, auto_wiring=True)
    with parallel.with_mesh(mesh):
        seq.bind(data_shapes=[("data", (BATCH, DIM))],
                 label_shapes=[("softmax_label", (BATCH,))])
    seq.init_params(initializer=mx.init.Uniform(0.5))
    return seq


def _plain_module(mesh=None):
    mod = mx.mod.Module(_chain_sym(), context=mx.cpu())
    cm = parallel.with_mesh(mesh) if mesh is not None \
        else contextlib.nullcontext()
    with cm:
        mod.bind(data_shapes=[("data", (BATCH, DIM))],
                 label_shapes=[("softmax_label", (BATCH,))])
    mod.init_params(initializer=mx.init.Uniform(0.5))
    return mod


def _build_on(spec):
    """(module, mesh) staged appropriately for `spec` (None = single dev)."""
    if spec is None:
        return _plain_module(), None
    gm = GraftMesh.from_spec(spec)
    if "pp4" in spec:
        return _seq_from_syms(gm, _four_stage_syms()), gm
    if "pp2" in spec:
        return _seq_from_syms(gm, _two_stage_syms()), gm
    return _plain_module(gm), gm


_OPT = {"learning_rate": 0.1, "momentum": 0.9}


def _batch(rs):
    data = mx.nd.array(rs.randn(BATCH, DIM).astype(np.float32))
    label = mx.nd.array(rs.randint(0, NCLS, (BATCH,)).astype(np.float32))
    return mx.io.DataBatch(data=[data], label=[label])


def _train(mod, batches):
    for b in batches:
        mod.forward(b, is_train=True)
        mod.backward()
        mod.update()


def _params_numpy(mod):
    args, auxs = mod.get_params()
    return ({k: v.asnumpy() for k, v in args.items()},
            {k: v.asnumpy() for k, v in auxs.items()})


def _save_from(mod, mesh, cfg):
    mgr = ckpt.CheckpointManager(cfg, module=mod)
    cm = parallel.with_mesh(mesh) if mesh is not None \
        else contextlib.nullcontext()
    with cm:
        return mgr.save(next_epoch=1, next_batch=0)


# --------------------------------------------------------------------------
# cross-topology resume parity
# --------------------------------------------------------------------------

@pytest.mark.parametrize("target", ["dp4,pp2", "dp2,tp2,pp2", "dp8", None],
                         ids=["dp4pp2", "dp2tp2pp2", "dp8", "single"])
def test_cross_topology_resume_parity_from_composed(tmp_path, target):
    """A checkpoint written under dp2,pp4 (4-stage packed pipeline)
    restores — params AND momentum — under re-staged 2-stage pipelines,
    pure-dp, and a single device; training forward from the restore
    matches the uninterrupted source run."""
    rs = np.random.RandomState(21)
    batches = [_batch(rs) for _ in range(4)]
    src, gm_src = _build_on("dp2,pp4")
    src.init_optimizer(optimizer="sgd", optimizer_params=_OPT)
    _train(src, batches[:2])
    cfg = mx.CheckpointConfig(str(tmp_path / "ckpts"))
    path = _save_from(src, gm_src, cfg)
    with open(os.path.join(path, "manifest.json")) as f:
        m = json.load(f)
    assert m["format"] == 2
    assert m["mesh"]["spec"] == "dp2,pp4"
    assert m["params"]["st0_fc_weight"]["kind"] == "arg"
    # the 4-stage packing wrote real per-stage slice metadata
    assert m["stage_slices"] is not None
    assert m["stage_slices"]["st_last_fc_weight"]["stage"] == 3

    # uninterrupted oracle: the source keeps training
    _train(src, batches[2:])
    oracle_args, _ = _params_numpy(src)

    tgt, gm_tgt = _build_on(target)
    tgt.init_optimizer(optimizer="sgd", optimizer_params=_OPT)
    loaded = ckpt.load_latest(cfg.dir)
    assert loaded is not None
    assert loaded.opt_states_by_name, "v2 restores optimizer state by name"
    mgr = ckpt.CheckpointManager(cfg, module=tgt)
    mgr.restore(loaded)
    _train(tgt, batches[2:])
    got_args, _ = _params_numpy(tgt)
    assert set(oracle_args) == set(got_args)
    for n in oracle_args:
        assert_almost_equal(got_args[n], oracle_args[n],
                            rtol=1e-4, atol=1e-5, names=(f"tgt:{n}", n))


def test_single_device_checkpoint_resumes_on_composed_mesh(tmp_path):
    """The other direction: written on one device, restored into a
    dp2,pp4 packed pipeline (params re-place + re-pack; momentum follows
    by name across the module split)."""
    rs = np.random.RandomState(33)
    batches = [_batch(rs) for _ in range(4)]
    src, _ = _build_on(None)
    src.init_optimizer(optimizer="sgd", optimizer_params=_OPT)
    _train(src, batches[:2])
    cfg = mx.CheckpointConfig(str(tmp_path / "ckpts"))
    _save_from(src, None, cfg)
    _train(src, batches[2:])
    oracle_args, _ = _params_numpy(src)

    tgt, _ = _build_on("dp2,pp4")
    tgt.init_optimizer(optimizer="sgd", optimizer_params=_OPT)
    loaded = ckpt.load_latest(cfg.dir)
    mgr = ckpt.CheckpointManager(cfg, module=tgt)
    mgr.restore(loaded)
    _train(tgt, batches[2:])
    got_args, _ = _params_numpy(tgt)
    for n in oracle_args:
        assert_almost_equal(got_args[n], oracle_args[n],
                            rtol=1e-4, atol=1e-5, names=(f"pp:{n}", n))


def test_packed_stage_rows_roundtrip(tmp_path):
    """Packed GPipe rows round-trip through the elastic loader: the rows
    rebuilt from restored child executors equal the rows the source held
    at save time."""
    rs = np.random.RandomState(5)
    src, gm = _build_on("dp2,pp4")
    src.init_optimizer(optimizer="sgd", optimizer_params=_OPT)
    src._pp_engine.retain_packed = True
    b = _batch(rs)
    _train(src, [b])
    src.forward(b, is_train=False)  # repack from the trained executors
    before = {dt: np.asarray(v) for dt, v in
              src._pp_engine._packed_params.items()}
    cfg = mx.CheckpointConfig(str(tmp_path / "ckpts"))
    _save_from(src, gm, cfg)

    tgt, _ = _build_on("dp2,pp4")
    mgr = ckpt.CheckpointManager(cfg, module=tgt)
    loaded = ckpt.load_latest(cfg.dir)
    mgr.restore(loaded)
    tgt.init_optimizer(optimizer="sgd", optimizer_params=_OPT)
    tgt._pp_engine.retain_packed = True
    tgt.forward(b, is_train=False)
    after = {dt: np.asarray(v) for dt, v in
             tgt._pp_engine._packed_params.items()}
    assert set(before) == set(after)
    for dt in before:
        assert_almost_equal(after[dt], before[dt], rtol=1e-6, atol=1e-7,
                            names=(f"restored:{dt}", f"saved:{dt}"))


# --------------------------------------------------------------------------
# format / loader mechanics
# --------------------------------------------------------------------------

def test_v1_format_directory_still_loads(tmp_path):
    """Backward compatibility: a format-1 directory (replicated single
    params file) loads through the v1 path untouched."""
    d = tmp_path / "ckpts"
    c = d / "ckpt-e00001-b00000000"
    os.makedirs(c)
    w = np.arange(20, dtype=np.float32).reshape(4, 5)
    s = np.ones(3, np.float32)
    mx.nd.save(str(c / "params"),
               {"arg:w": mx.nd.array(w), "aux:s": mx.nd.array(s)})
    files = {"params": {"sha256": ckpt.sha256_file(str(c / "params")),
                        "bytes": os.path.getsize(str(c / "params"))}}
    manifest = {"format": 1, "next_epoch": 1, "next_batch": 0,
                "epoch": 0, "nbatch": None, "files": files,
                "rng_key": None, "optimizer": None, "env": None}
    with open(c / "manifest.json", "w") as f:
        json.dump(manifest, f)
    (d / "LATEST").write_text("ckpt-e00001-b00000000\n")

    loaded = ckpt.load_latest(str(d))
    assert loaded is not None and loaded.manifest["format"] == 1
    np.testing.assert_array_equal(loaded.arg_params["w"].asnumpy(), w)
    np.testing.assert_array_equal(loaded.aux_params["s"].asnumpy(), s)
    assert loaded.opt_states_by_name is None
    assert loaded.next_epoch == 1


def test_stale_latest_pointer_is_ignored(tmp_path):
    """A crash between commit-rename and the LATEST update leaves LATEST
    stale; the loader must still find the newest valid commit (names are
    ordered, the pointer is only a hint)."""
    rs = np.random.RandomState(2)
    mod, _ = _build_on(None)
    mod.init_optimizer(optimizer="sgd", optimizer_params=_OPT)
    cfg = mx.CheckpointConfig(str(tmp_path / "ckpts"))
    mgr = ckpt.CheckpointManager(cfg, module=mod)
    mgr.save(next_epoch=1, next_batch=0)
    _train(mod, [_batch(rs)])
    mgr.save(next_epoch=2, next_batch=0)
    # simulate the mid-LATEST torn state
    (tmp_path / "ckpts" / "LATEST").write_text("ckpt-e00001-b00000000\n")
    loaded = ckpt.load_latest(cfg.dir)
    assert loaded.next_epoch == 2


def test_shard_coverage_gap_is_corrupt(tmp_path):
    """A manifest whose shard pieces don't cover a parameter is rejected
    (geometric check, before any array maths)."""
    mod, _ = _build_on(None)
    cfg = mx.CheckpointConfig(str(tmp_path / "ckpts"))
    mgr = ckpt.CheckpointManager(cfg, module=mod)
    path = mgr.save(next_epoch=1, next_batch=0)
    mpath = os.path.join(path, "manifest.json")
    with open(mpath) as f:
        m = json.load(f)
    drop = next(k for k, v in m["shards"].items()
                if v["name"] == "st0_fc_weight")
    del m["shards"][drop]
    with open(mpath, "w") as f:
        json.dump(m, f)
    # digest of the manifest itself is not recorded (it IS the record),
    # so only the coverage check can catch this
    with pytest.raises(ckpt.CheckpointCorrupt, match="cover"):
        ckpt.verify_dir(path)
    assert ckpt.load_latest(cfg.dir) is None


# --------------------------------------------------------------------------
# resume consensus plumbing (single-process semantics; the dist path runs
# the same code with rank>0 reconstructing the broadcast cursor)
# --------------------------------------------------------------------------

def test_broadcast_ints_local_identity():
    kv = mx.kv.create("local")
    assert kv.broadcast_ints([3, 14, 15]) == [3, 14, 15]


def test_decide_resume_matches_load_latest_locally(tmp_path):
    mod, _ = _build_on(None)
    cfg = mx.CheckpointConfig(str(tmp_path / "ckpts"))
    mgr = ckpt.CheckpointManager(cfg, module=mod)
    assert mgr.decide_resume() is None
    mgr.save(next_epoch=1, next_batch=0)
    a = mgr.decide_resume()
    b = mgr.load_latest()
    assert a is not None and a.path == b.path


# --------------------------------------------------------------------------
# async writer: the training pause is the snapshot, not the write
# --------------------------------------------------------------------------

def _fit_small(tmp_path, num_epoch, checkpoint):
    rng = np.random.RandomState(0)
    X = rng.randn(32, 10).astype(np.float32)
    Y = rng.randint(0, 4, (32,)).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=8)
    data = mx.sym.Variable("data")
    h = mx.sym.Activation(
        mx.sym.FullyConnected(data, num_hidden=8, name="fc1"),
        act_type="relu")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(h, num_hidden=4, name="fc2"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=num_epoch,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            checkpoint=checkpoint)
    return mod


def test_async_write_bounds_stall_to_snapshot(tmp_path, monkeypatch):
    """MXNET_CKPT_ASYNC=1: every save pauses training only for the
    checkpoint.snapshot span; commits happen on the writer thread under
    checkpoint.write_async, never under the foreground checkpoint.write
    span — and the commits still all land (fit drains on exit)."""
    monkeypatch.setenv("MXNET_CKPT_ASYNC", "1")
    d = str(tmp_path / "ckpts")
    saves0 = tm.counter("checkpoint.save").value
    snap0 = tm.histogram("checkpoint.snapshot").count
    async0 = tm.histogram("checkpoint.write_async").count
    sync0 = tm.histogram("checkpoint.write").count
    _fit_small(tmp_path, num_epoch=3,
               checkpoint=mx.CheckpointConfig(d, period=1))
    saves = tm.counter("checkpoint.save").value - saves0
    assert saves == 3
    assert tm.histogram("checkpoint.snapshot").count - snap0 == saves
    assert tm.histogram("checkpoint.write_async").count - async0 == saves
    assert tm.histogram("checkpoint.write").count == sync0, \
        "async mode must not write on the training thread"
    loaded = ckpt.load_latest(d)
    assert loaded is not None and loaded.next_epoch == 3
    ckpt.verify_dir(loaded.path)


def test_async_resume_sees_inflight_commit(tmp_path, monkeypatch):
    """load_latest on a manager with an in-flight async write drains
    first — rollback/resume must never read a half-landed directory."""
    monkeypatch.setenv("MXNET_CKPT_ASYNC", "1")
    mod, _ = _build_on(None)
    mod.init_optimizer(optimizer="sgd", optimizer_params=_OPT)
    cfg = mx.CheckpointConfig(str(tmp_path / "ckpts"))
    mgr = ckpt.CheckpointManager(cfg, module=mod)
    try:
        mgr.save(next_epoch=1, next_batch=0)
        loaded = mgr.load_latest()
        assert loaded is not None and loaded.next_epoch == 1
    finally:
        mgr.finalize()


# --------------------------------------------------------------------------
# kill-during-save chaos matrix (subprocess; every injected phase)
# --------------------------------------------------------------------------

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


@pytest.mark.parametrize("phase", ["mid-shard-write", "pre-manifest",
                                   "post-manifest-pre-rename",
                                   "mid-LATEST"])
def test_sigkill_at_every_save_phase_never_loses_newest_commit(
        tmp_path, phase):
    """The chaos acceptance: life 1 dies mid-training (commits exist),
    life 2 is killed INSIDE its first save at `phase`, and whatever torn
    state that leaves, the newest previously-valid commit still loads —
    then life 3 resumes from it and finishes with the exact total update
    count of an uninterrupted run."""
    d = str(tmp_path / "ckpts")
    base = {
        "MXNET_CHECKPOINT_DIR": d,
        "MXNET_CHECKPOINT_BATCH_PERIOD": "3",
        "MXNET_CHECKPOINT_KEEP": "4",
    }
    # life 1: dies at batch 20 having committed through (epoch 2, batch 3)
    r1 = _run_worker({**base, "MXNET_FI_CRASH_AT_BATCH": "20"})
    assert r1.returncode == 17, (r1.stdout + r1.stderr)[-3000:]
    pre = ckpt.load_latest(d)
    assert pre is not None
    pre_cursor = (pre.next_epoch, pre.next_batch)
    assert pre_cursor == (2, 3)

    # life 2: resumes, then dies INSIDE its first save at `phase`
    r2 = _run_worker({**base, "MXNET_FI_CKPT_KILL_PHASE": phase})
    out2 = r2.stdout + r2.stderr
    assert r2.returncode == 17, out2[-3000:]
    assert f"faultinject: CKPT-KILL at phase {phase}" in out2, out2[-3000:]

    # invariant: whatever `phase` tore, the newest VALID commit is intact
    # and no older than what life 2 started from
    post = ckpt.load_latest(d)
    assert post is not None, f"phase {phase} lost every checkpoint"
    ckpt.verify_dir(post.path)
    post_cursor = (post.next_epoch, post.next_batch)
    assert post_cursor >= pre_cursor, \
        f"phase {phase}: {post_cursor} regressed below {pre_cursor}"

    # life 3 (no injection): resumes and completes with the
    # uninterrupted run's exact total update count
    r3 = _run_worker(dict(base))
    out3 = r3.stdout + r3.stderr
    assert r3.returncode == 0, out3[-3000:]
    assert f"RESUME epoch={post.next_epoch} batch={post.next_batch}" \
        in out3, out3[-3000:]
    done = [l for l in out3.splitlines() if l.startswith("TRAIN-DONE")]
    assert done, out3[-3000:]
    assert int(done[0].split("final_update=")[1]) == 48
    acc = float(done[0].split("acc=")[1].split()[0])
    assert acc > 0.8, f"post-chaos training stuck at {acc}"


# --------------------------------------------------------------------------
# tools/ckpt.py CLI
# --------------------------------------------------------------------------

def test_ckpt_cli_inspect_verify_reshard(tmp_path):
    """The offline CLI: inspect summarizes, verify digests (exit 1 on
    corruption), reshard consolidates a composed-mesh checkpoint into a
    single-shard commit that the elastic loader accepts."""
    src, gm = _build_on("dp2,pp4")
    src.init_optimizer(optimizer="sgd", optimizer_params=_OPT)
    cfg = mx.CheckpointConfig(str(tmp_path / "ckpts"))
    path = _save_from(src, gm, cfg)

    cli = [sys.executable, os.path.join(_ROOT, "tools", "ckpt.py")]
    env = dict(os.environ)
    env["PYTHONPATH"] = _ROOT + os.pathsep + env.get("PYTHONPATH", "")

    r = subprocess.run(cli + ["inspect", cfg.dir], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "format:    v2" in r.stdout and "dp2,pp4" in r.stdout
    assert "st0_fc_weight" in r.stdout

    r = subprocess.run(cli + ["verify", cfg.dir], capture_output=True,
                       text=True, env=env, timeout=240)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    assert r.stdout.startswith("OK")

    out = str(tmp_path / "resharded")
    r = subprocess.run(cli + ["reshard", cfg.dir, "--out", out,
                              "--mesh", "dp8"],
                       capture_output=True, text=True, env=env,
                       timeout=240)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    loaded = ckpt.load_latest(out)
    assert loaded is not None and loaded.manifest["mesh"]["spec"] == "dp8"
    want, _ = _params_numpy(src)
    for n, arr in want.items():
        np.testing.assert_allclose(loaded.arg_params[n].asnumpy(), arr,
                                   rtol=1e-6)

    # corruption is an exit-1 CORRUPT verdict, not a silent OK
    shard = os.path.join(path, "shard-00000.params")
    with open(shard, "r+b") as f:
        f.seek(0)
        f.write(b"\xde\xad")
    r = subprocess.run(cli + ["verify", path], capture_output=True,
                       text=True, env=env, timeout=240)
    assert r.returncode == 1 and "CORRUPT" in r.stdout
