"""Composed 3D parallelism over one GraftMesh (ROADMAP item 3).

dp×pp and dp×tp×pp train steps as ONE program: GPipe stages on pp rank
sets, batch sharded over the dp sub-axis inside every microbatch, packed
per-stage parameter rows sharded over each stage's dp(×tp) rank set, and
gradients reduced over dp *within* the rank set. The oracle is serial
equivalence — outputs, gradients and post-update parameters must match the
identical chain trained as one plain single-device Module — plus the
placement contract (each device holds ~total/(S·dp·tp) packed bytes) and
the unchanged-fast-path contracts (fused K-step window, AOT cache, zero
per-window host syncs) on a composed mesh.
"""

import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel
from mxnet_tpu import telemetry as tm
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel.mesh import GraftMesh, parse_mesh_spec, _reset_env_mesh
from mxnet_tpu.test_utils import assert_almost_equal

BATCH, DIM, HID, NCLS = 16, 8, 12, 5


# --------------------------------------------------------------------------
# mesh spec / GraftMesh construction
# --------------------------------------------------------------------------

def test_parse_mesh_spec():
    assert parse_mesh_spec("dp2,pp4") == {"dp": 2, "pp": 4}
    assert parse_mesh_spec("pp4,dp2") == {"dp": 2, "pp": 4}  # canonical order
    assert parse_mesh_spec("dp2xtp2xpp2") == {"dp": 2, "tp": 2, "pp": 2}
    assert parse_mesh_spec("auto", devices=list(range(8))) == {"dp": 8}
    assert parse_mesh_spec("dp*,pp4", devices=list(range(8))) == \
        {"dp": 2, "pp": 4}
    assert parse_mesh_spec("tp2,dp", devices=list(range(8))) == \
        {"dp": 4, "tp": 2}
    with pytest.raises(MXNetError):
        parse_mesh_spec("zz4")
    with pytest.raises(MXNetError):
        parse_mesh_spec("dp2,dp4")
    with pytest.raises(MXNetError):
        parse_mesh_spec("dp*,pp*", devices=list(range(8)))
    with pytest.raises(MXNetError):
        parse_mesh_spec("")
    with pytest.raises(MXNetError, match="strand"):
        # a wildcard must absorb EVERY remaining device, not floor-divide
        parse_mesh_spec("pp3,dp*", devices=list(range(8)))
    with pytest.raises(MXNetError, match="bad size"):
        parse_mesh_spec("dp2*,pp4")  # malformed size token, typed error


def test_graft_mesh_axes_and_shardings():
    gm = GraftMesh.from_spec("dp2,pp4")
    assert gm.spec == "dp2,pp4"
    assert gm.dp == 2 and gm.pp == 4 and gm.tp == 1 and gm.sp == 1
    assert gm.has("dp") and not gm.has("tp")
    assert str(gm.batch_sharding().spec) == "PartitionSpec('dp',)"
    assert str(gm.replicated().spec) == "PartitionSpec()"
    # wrapping is cache-transparent: same mesh -> equal + same hash
    assert parallel.as_graft(gm.mesh) == gm
    assert hash(parallel.as_graft(gm.mesh)) == hash(gm)
    # cache token is a process-stable rendering
    tok = gm.cache_token()
    assert tok[0] == "dp2,pp4" and len(tok[1]) == 8


# --------------------------------------------------------------------------
# module graph builders (heterogeneous chain; loss head on the last stage)
# --------------------------------------------------------------------------

def _stage_syms(n_mid):
    syms = []
    for i in range(n_mid):
        data = mx.sym.Variable("data")
        fc = mx.sym.FullyConnected(data, num_hidden=HID, name=f"st{i}_fc")
        syms.append(mx.sym.Activation(fc, act_type="tanh",
                                      name=f"st{i}_act"))
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=NCLS, name="st_last_fc")
    syms.append(mx.sym.SoftmaxOutput(fc, name="softmax"))
    return syms


def _chain_sym(n_mid):
    h = mx.sym.Variable("data")
    for i in range(n_mid):
        h = mx.sym.FullyConnected(h, num_hidden=HID, name=f"st{i}_fc")
        h = mx.sym.Activation(h, act_type="tanh", name=f"st{i}_act")
    h = mx.sym.FullyConnected(h, num_hidden=NCLS, name="st_last_fc")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _build_seq(mesh, n_mid):
    syms = _stage_syms(n_mid)
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


def _oracle_for(seq, n_mid):
    ref = mx.mod.Module(_chain_sym(n_mid), context=mx.cpu())
    ref.bind(data_shapes=[("data", (BATCH, DIM))],
             label_shapes=[("softmax_label", (BATCH,))])
    args, auxs = seq.get_params()
    ref.init_params(arg_params={k: v.copy() for k, v in args.items()},
                    aux_params={k: v.copy() for k, v in auxs.items()},
                    initializer=None)
    return ref


def _batch(rs):
    data = mx.nd.array(rs.randn(BATCH, DIM).astype(np.float32))
    label = mx.nd.array(rs.randint(0, NCLS, (BATCH,)).astype(np.float32))
    return mx.io.DataBatch(data=[data], label=[label])


def _assert_parity(seq, ref, rs, steps=2):
    """Train both for `steps` SGD steps; outputs, gradients and params
    must match the single-device serial oracle."""
    seq.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    ref.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    for _ in range(steps):
        batch = _batch(rs)
        seq.forward(batch, is_train=True)
        seq.backward()
        ref.forward(batch, is_train=True)
        ref.backward()
        assert_almost_equal(seq.get_outputs()[0].asnumpy(),
                            ref.get_outputs()[0].asnumpy(),
                            rtol=1e-5, atol=1e-6)
        ref_grads = {n: g.asnumpy() for n, g in
                     ref._exec_group._exec.grad_dict.items()
                     if g is not None}
        for info in seq._pp_engine.infos:
            for (u, n) in info.param_entries:
                g = info.units[u].exec_.grad_dict[n].asnumpy()
                assert_almost_equal(g, ref_grads[n], rtol=1e-4, atol=1e-5,
                                    names=(f"pp:{n}", f"serial:{n}"))
        seq.update()
        ref.update()
    a_pp, _ = seq.get_params()
    a_ref, _ = ref.get_params()
    for n in a_ref:
        assert_almost_equal(a_pp[n].asnumpy(), a_ref[n].asnumpy(),
                            rtol=1e-4, atol=1e-5, names=(n, n))


# --------------------------------------------------------------------------
# composed train-step parity
# --------------------------------------------------------------------------

def test_dp_pp_train_step_matches_serial_oracle():
    rs = np.random.RandomState(7)
    gm = GraftMesh.from_spec("dp2,pp4")
    seq = _build_seq(gm, n_mid=3)
    eng = seq._pp_engine
    assert eng is not None and eng.S == 4 and eng.dp_size == 2
    assert not eng.homogeneous
    dp_reduce0 = tm.counter("parallel.dp_reduce").value
    _assert_parity(seq, _oracle_for(seq, 3), rs)
    # the composed program carried the gradient reduction over the dp
    # sub-axis within each stage's rank set (counter per ISSUE: "asserted
    # via HLO or counter"; the grad parity above is the numeric evidence —
    # a missing dp-sum would halve every gradient)
    assert tm.counter("parallel.dp_reduce").value > dp_reduce0


def test_dp_tp_pp_train_step_matches_serial_oracle():
    rs = np.random.RandomState(11)
    gm = GraftMesh.from_spec("dp2,tp2,pp2")
    seq = _build_seq(gm, n_mid=1)
    eng = seq._pp_engine
    assert eng is not None and eng.S == 2
    assert eng.dp_size == 2 and eng.tp_size == 2
    _assert_parity(seq, _oracle_for(seq, 1), rs)


def test_homogeneous_dp_pp_matches_serial():
    """Stacked (homogeneous) lowering under a dp sub-axis: grads psum over
    dp explicitly; parity against the serial chain."""
    rs = np.random.RandomState(3)
    gm = GraftMesh.from_spec("dp2,pp4")
    syms = []
    for i in range(4):
        data = mx.sym.Variable("data")
        fc = mx.sym.FullyConnected(data, num_hidden=DIM, name=f"blk{i}_fc")
        syms.append(mx.sym.Activation(fc, act_type="tanh",
                                      name=f"blk{i}_act"))
    seq = mx.mod.SequentialModule()
    for i, s in enumerate(syms):
        seq.add(mx.mod.Module(s, data_names=("data",), label_names=None),
                auto_wiring=i > 0)
    with parallel.with_mesh(gm):
        seq.bind(data_shapes=[("data", (BATCH, DIM))], for_training=False)
    seq.init_params(initializer=mx.init.Uniform(0.5))
    assert seq._pp_engine is not None and seq._pp_engine.homogeneous
    assert seq._pp_engine.dp_size == 2

    h = mx.sym.Variable("data")
    for i in range(4):
        h = mx.sym.FullyConnected(h, num_hidden=DIM, name=f"blk{i}_fc")
        h = mx.sym.Activation(h, act_type="tanh", name=f"blk{i}_act")
    ref = mx.mod.Module(h, context=mx.cpu(), label_names=None)
    ref.bind(data_shapes=[("data", (BATCH, DIM))], for_training=False)
    args, _ = seq.get_params()
    ref.init_params(arg_params={k: v.copy() for k, v in args.items()},
                    aux_params=None, initializer=None)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rs.randn(BATCH, DIM).astype(np.float32))],
        label=None)
    seq.forward(batch, is_train=False)
    ref.forward(batch, is_train=False)
    assert_almost_equal(seq.get_outputs()[0].asnumpy(),
                        ref.get_outputs()[0].asnumpy(),
                        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("spec", ["dp2,pp2", "dp2,tp2,pp2"])
def test_dp_pp_batchnorm_aux_matches_group_granular_serial(spec):
    """BN under dp×pp (and dp×tp×pp): each (microbatch tick × dp shard)
    group normalizes by its own batch statistics, and the masked per-tick
    aux updates are averaged over ticks AND pmean-ed over the stage's
    rank set (identical tp contributions divide out). The oracle runs
    each group through the serial chain from the step-start aux and
    averages the EMA updates — the dp-extension of the pure-pp
    group-granular semantics the seed pins (and the reference's own
    non-sync multi-device BN behavior)."""
    rs = np.random.RandomState(5)
    gm = GraftMesh.from_spec(spec)
    d0 = mx.sym.Variable("data")
    fc0 = mx.sym.FullyConnected(d0, num_hidden=HID, name="b0_fc")
    bn0 = mx.sym.BatchNorm(fc0, name="b0_bn", fix_gamma=False,
                           momentum=0.9)
    s0 = mx.sym.Activation(bn0, act_type="tanh", name="b0_act")
    d1 = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(d1, num_hidden=NCLS, name="b1_fc")
    s1 = mx.sym.SoftmaxOutput(fc1, name="softmax")
    seq = mx.mod.SequentialModule()
    seq.add(mx.mod.Module(s0, data_names=("data",), label_names=None))
    seq.add(mx.mod.Module(s1, data_names=("data",),
                          label_names=("softmax_label",)),
            take_labels=True, auto_wiring=True)
    with parallel.with_mesh(gm):
        seq.bind(data_shapes=[("data", (BATCH, DIM))],
                 label_shapes=[("softmax_label", (BATCH,))])
    seq.init_params(initializer=mx.init.Uniform(0.5))

    h = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(h, num_hidden=HID, name="b0_fc")
    h = mx.sym.BatchNorm(h, name="b0_bn", fix_gamma=False, momentum=0.9)
    h = mx.sym.Activation(h, act_type="tanh", name="b0_act")
    h = mx.sym.FullyConnected(h, num_hidden=NCLS, name="b1_fc")
    h = mx.sym.SoftmaxOutput(h, name="softmax")
    ref = mx.mod.Module(h, context=mx.cpu())
    M, dp = seq._pp_engine.M, seq._pp_engine.dp_size
    grp = BATCH // (M * dp)
    ref.bind(data_shapes=[("data", (grp, DIM))],
             label_shapes=[("softmax_label", (grp,))])
    args, auxs = seq.get_params()
    args = {k: v.copy() for k, v in args.items()}
    auxs = {k: v.copy() for k, v in auxs.items()}

    xs = rs.randn(BATCH, DIM).astype(np.float32)
    ys = rs.randint(0, NCLS, (BATCH,)).astype(np.float32)
    seq.forward(mx.io.DataBatch(data=[mx.nd.array(xs)],
                                label=[mx.nd.array(ys)]), is_train=True)
    out_pp = seq.get_outputs()[0].asnumpy()
    _, aux_pp = seq.get_params()

    # oracle over the M·dp independent normalization groups: microbatch m
    # spans rows [m·(B/M), (m+1)·(B/M)); the dp shard r takes its r-th
    # contiguous slice of that microbatch
    mean_sum = None
    var_sum = None
    for m in range(M):
        for r in range(dp):
            lo = m * (BATCH // M) + r * grp
            rows = slice(lo, lo + grp)
            ref.set_params({k: v.copy() for k, v in args.items()},
                           {k: v.copy() for k, v in auxs.items()})
            ref.forward(mx.io.DataBatch(
                data=[mx.nd.array(xs[rows])],
                label=[mx.nd.array(ys[rows])]), is_train=True)
            assert_almost_equal(ref.get_outputs()[0].asnumpy(),
                                out_pp[rows], rtol=1e-4, atol=1e-5,
                                names=(f"serial[{m},{r}]", "pp"))
            # read aux straight off the oracle's executor (get_params
            # would return the set_params snapshot)
            aux_exec = ref._exec_group._exec.aux_dict
            mm = aux_exec["b0_bn_moving_mean"].asnumpy().copy()
            mv = aux_exec["b0_bn_moving_var"].asnumpy().copy()
            mean_sum = mm if mean_sum is None else mean_sum + mm
            var_sum = mv if var_sum is None else var_sum + mv
    n = M * dp
    assert_almost_equal(aux_pp["b0_bn_moving_mean"].asnumpy(),
                        mean_sum / n, rtol=1e-4, atol=1e-6)
    assert_almost_equal(aux_pp["b0_bn_moving_var"].asnumpy(),
                        var_sum / n, rtol=1e-4, atol=1e-6)


# --------------------------------------------------------------------------
# per-stage per-device placement
# --------------------------------------------------------------------------

@pytest.mark.parametrize("spec,shard", [("dp2,pp4", 2), ("dp2,tp2,pp2", 4)])
def test_packed_rows_hold_one_rank_set_slice_per_device(spec, shard):
    """Each device holds ~total/(S·dp·tp) packed parameter bytes: row i of
    the (S, Lmax) buffer lives on pp rank set i, split over its dp(×tp)
    sub-mesh."""
    gm = GraftMesh.from_spec(spec)
    seq = _build_seq(gm, n_mid=3 if gm.pp == 4 else 1)
    eng = seq._pp_engine
    eng.retain_packed = True
    rs = np.random.RandomState(0)
    seq.forward(_batch(rs), is_train=True)
    assert eng._packed_params, "composed mode must pack rows"
    S = eng.S
    for dt, buf in eng._packed_params.items():
        total = buf.size * buf.dtype.itemsize
        per_dev = total // (S * shard)
        shapes = {s.data.shape for s in buf.addressable_shards}
        assert shapes == {(buf.shape[0] // S, buf.shape[1] // shard)}, (
            f"{dt}: shards {shapes}, want row/(dp·tp) slices")
        for s in buf.addressable_shards:
            got = s.data.size * buf.dtype.itemsize
            assert got == per_dev, f"{dt}: device holds {got}B != {per_dev}B"
    # the placement gauge reports the same number
    gauge = tm.gauge("parallel.packed_bytes_per_device").value
    assert gauge > 0


# --------------------------------------------------------------------------
# fused window / AOT / no-host-sync invariants on a composed mesh
# --------------------------------------------------------------------------

def _plain_module_on(gm):
    sym = _chain_sym(1)
    mod = mx.mod.Module(sym, context=mx.cpu())
    with parallel.with_mesh(gm):
        mod.bind(data_shapes=[("data", (BATCH, DIM))],
                 label_shapes=[("softmax_label", (BATCH,))])
        mod.init_params(initializer=mx.init.Uniform(0.5))
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.05})
    return mod


def test_fused_window_invariants_on_composed_mesh():
    """The K-step fused train window runs unchanged over a dp×pp mesh: one
    compile, then zero XLA compiles AND zero host syncs per window
    (counter-verified), with the dp batch sharding intact."""
    rs = np.random.RandomState(9)
    gm = GraftMesh.from_spec("dp2,pp4")
    mod = _plain_module_on(gm)
    exe = mod._exec_group._exec
    assert str(exe.arg_dict["data"]._data.sharding.spec) == \
        "PartitionSpec('dp',)"

    def window(n=2):
        with parallel.with_mesh(gm):
            mod.train_window(_batch(rs), n_steps=n)
            mod.get_outputs()[0].wait_to_read()

    window()  # compile
    compiles0 = tm.counter("executor.jit_compile").value
    sync0 = (tm.counter("ndarray.asnumpy").value,
             tm.counter("ndarray.wait_to_read").value)
    window()
    window()
    assert tm.counter("executor.jit_compile").value == compiles0, \
        "steady-state composed windows must not recompile"
    sync1 = (tm.counter("ndarray.asnumpy").value,
             tm.counter("ndarray.wait_to_read").value)
    # the two wait_to_read fences above are the caller's own sync points;
    # the window dispatch itself must add no host syncs
    assert sync1[0] == sync0[0], "composed window forced an asnumpy sync"
    assert sync1[1] - sync0[1] <= 2, \
        f"composed window added host syncs: {sync1[1] - sync0[1]}"


@pytest.mark.aot_serialization
def test_aot_cache_hit_on_composed_mesh(tmp_path, monkeypatch):
    """Mesh-sharded programs persist to the AOT executable cache keyed by
    the GraftMesh spec + device assignment: a second bind of the same
    graph on the same composed mesh loads the executable (cache_hit) and
    performs zero XLA compiles."""
    monkeypatch.setenv("MXNET_AOT_CACHE", "1")
    monkeypatch.setenv("MXNET_AOT_CACHE_DIR", str(tmp_path))
    rs = np.random.RandomState(4)
    gm = GraftMesh.from_spec("dp2,pp4")

    mod_a = _plain_module_on(gm)
    with parallel.with_mesh(gm):
        mod_a.train_window(_batch(rs), n_steps=2)
        mod_a.get_outputs()[0].wait_to_read()
    stored = tm.counter("aot.cache_store").value
    assert stored > 0, "composed-mesh program did not persist"

    hits0 = tm.counter("aot.cache_hit").value
    compiles0 = tm.counter("executor.jit_compile").value
    mod_b = _plain_module_on(gm)
    with parallel.with_mesh(gm):
        mod_b.train_window(_batch(rs), n_steps=2)
        mod_b.get_outputs()[0].wait_to_read()
    assert tm.counter("aot.cache_hit").value > hits0, \
        "second composed-mesh bind missed the executable cache"
    assert tm.counter("executor.jit_compile").value == compiles0, \
        "second composed-mesh bind recompiled"


# --------------------------------------------------------------------------
# MXNET_MESH environment construction
# --------------------------------------------------------------------------

def test_mesh_from_env_binds_executor_group(monkeypatch):
    monkeypatch.setenv("MXNET_MESH", "dp8")
    _reset_env_mesh()
    try:
        mod = mx.mod.Module(_chain_sym(1), context=mx.cpu())
        mod.bind(data_shapes=[("data", (BATCH, DIM))],
                 label_shapes=[("softmax_label", (BATCH,))])
        mod.init_params(initializer=mx.init.Uniform(0.5))
        exe = mod._exec_group._exec
        assert str(exe.arg_dict["data"]._data.sharding.spec) == \
            "PartitionSpec('dp',)"
        assert mod._exec_group._dp_size == 8
        mod.forward(_batch(np.random.RandomState(0)), is_train=False)
        mod.get_outputs()[0].wait_to_read()
    finally:
        _reset_env_mesh()


def test_mesh_from_env_lowers_sequential_module(monkeypatch):
    monkeypatch.setenv("MXNET_MESH", "dp2,pp4")
    _reset_env_mesh()
    try:
        syms = _stage_syms(3)
        seq = mx.mod.SequentialModule()
        for i, s in enumerate(syms[:-1]):
            seq.add(mx.mod.Module(s, data_names=("data",),
                                  label_names=None), auto_wiring=i > 0)
        seq.add(mx.mod.Module(syms[-1], data_names=("data",),
                              label_names=("softmax_label",)),
                take_labels=True, auto_wiring=True)
        seq.bind(data_shapes=[("data", (BATCH, DIM))],
                 label_shapes=[("softmax_label", (BATCH,))])
        assert seq._pp_engine is not None
        assert seq._pp_engine.S == 4 and seq._pp_engine.dp_size == 2
    finally:
        _reset_env_mesh()


def test_installed_mesh_wins_over_env(monkeypatch):
    monkeypatch.setenv("MXNET_MESH", "dp8")
    _reset_env_mesh()
    try:
        gm = GraftMesh.from_spec("dp2,pp4")
        with parallel.with_mesh(gm):
            assert parallel.current_graft() == gm
    finally:
        _reset_env_mesh()


def test_microbatch_not_divisible_by_dp_raises():
    gm = GraftMesh.from_spec("dp2,pp4")
    syms = _stage_syms(3)
    seq = mx.mod.SequentialModule(pipeline_microbatches=8)
    for i, s in enumerate(syms[:-1]):
        seq.add(mx.mod.Module(s, data_names=("data",), label_names=None),
                auto_wiring=i > 0)
    seq.add(mx.mod.Module(syms[-1], data_names=("data",),
                          label_names=("softmax_label",)),
            take_labels=True, auto_wiring=True)
    with pytest.raises(MXNetError, match="data-parallel degree"):
        with parallel.with_mesh(gm):
            # 16/8 = 2-row microbatches cannot split over dp=2... they can;
            # use a batch that breaks: 8 microbatches of 1 row each
            seq.bind(data_shapes=[("data", (8, DIM))],
                     label_shapes=[("softmax_label", (8,))])


# --------------------------------------------------------------------------
# composed-mesh kill-and-resume (elastic v2 checkpoints under dp×pp)
# --------------------------------------------------------------------------

def _run_elastic_worker(env, timeout=240):
    import subprocess
    import sys
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    e = dict(os.environ)
    e["PYTHONPATH"] = os.pathsep.join(
        filter(None, [root, e.get("PYTHONPATH")]))
    e["JAX_PLATFORMS"] = "cpu"
    e.pop("XLA_FLAGS", None)  # worker sets its own 8-device flag
    e.update(env)
    return subprocess.run(
        [sys.executable, os.path.join(root, "tests",
                                      "ckpt_elastic_worker.py")],
        capture_output=True, text=True, env=e, timeout=timeout, cwd=root,
    )


@pytest.mark.chaos
def test_kill_resume_composed_mesh_matches_single_host_pin(tmp_path):
    """Hard-kill mid-epoch while training a 2-stage pipeline under
    dp2,pp2 with sharded v2 checkpoints; the restarted worker must
    auto-resume from the last commit and reach the SAME convergence pin
    as the single-host kill-resume test (final_update=48, acc > 0.8)."""
    d = str(tmp_path / "ckpts")
    base = {
        "MXNET_CHECKPOINT_DIR": d,
        "MXNET_CHECKPOINT_BATCH_PERIOD": "3",
        "WORKER_MESH": "dp2,pp2",
    }
    r1 = _run_elastic_worker({**base, "MXNET_FI_CRASH_AT_BATCH": "20"})
    assert r1.returncode == 17, (r1.stdout + r1.stderr)[-3000:]

    from mxnet_tpu import checkpoint as ckpt
    pre = ckpt.load_latest(d)
    assert pre is not None
    assert (pre.next_epoch, pre.next_batch) == (2, 3)
    m = pre.manifest
    assert m["format"] == 2 and m["mesh"]["spec"] == "dp2,pp2"

    r2 = _run_elastic_worker({**base, "MXNET_FI_CRASH_AT_BATCH": "20",
                              "MXNET_NUM_RESTARTS": "1"})
    out = r2.stdout + r2.stderr
    assert r2.returncode == 0, out[-3000:]
    assert "RESUME epoch=2 batch=3 num_update=19" in out, out[-3000:]
    done = [l for l in out.splitlines() if l.startswith("TRAIN-DONE")]
    assert done, out[-3000:]
    assert int(done[0].split("final_update=")[1]) == 48
    assert float(done[0].split("acc=")[1].split()[0]) > 0.8
