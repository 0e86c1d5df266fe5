"""Executor tests (reference test_executor.py)."""

import numpy as np
import pytest

import mxnet_tpu as mx
import stacked_wgrad_cases as swc
from mxnet_tpu.base import MXNetError
from mxnet_tpu.test_utils import assert_almost_equal

rs = np.random.RandomState(11)


def test_bind_forward_backward():
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    c = a + b * 2
    x = rs.randn(3, 4).astype(np.float32)
    y = rs.randn(3, 4).astype(np.float32)
    exe = c.bind(
        mx.cpu(), args={"a": mx.nd.array(x), "b": mx.nd.array(y)},
        args_grad={"a": mx.nd.zeros(x.shape), "b": mx.nd.zeros(y.shape)},
    )
    exe.forward(is_train=True)
    assert_almost_equal(exe.outputs[0].asnumpy(), x + 2 * y)
    og = rs.randn(3, 4).astype(np.float32)
    exe.backward(mx.nd.array(og))
    assert_almost_equal(exe.grad_dict["a"].asnumpy(), og)
    assert_almost_equal(exe.grad_dict["b"].asnumpy(), 2 * og)


def test_simple_bind_allocates():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=6, name="fc")
    exe = net.simple_bind(ctx=mx.cpu(), data=(4, 8))
    assert exe.arg_dict["fc_weight"].shape == (6, 8)
    assert exe.arg_dict["fc_bias"].shape == (6,)
    assert exe.grad_dict["fc_weight"].shape == (6, 8)
    exe.forward(is_train=False)
    assert exe.outputs[0].shape == (4, 6)


def test_forward_kwargs_update():
    net = mx.sym.square(mx.sym.Variable("x"))
    exe = net.simple_bind(ctx=mx.cpu(), x=(2, 2), grad_req="null")
    exe.forward(x=mx.nd.array([[1, 2], [3, 4]]))
    assert_almost_equal(exe.outputs[0].asnumpy(), [[1, 4], [9, 16]])
    exe.forward(x=mx.nd.array([[2, 2], [2, 2]]))
    assert_almost_equal(exe.outputs[0].asnumpy(), [[4, 4], [4, 4]])


def test_outputs_persistent_handles():
    net = mx.sym.Variable("x") * 2
    exe = net.simple_bind(ctx=mx.cpu(), x=(2,), grad_req="null")
    exe.forward(x=mx.nd.array([1.0, 2.0]))
    out = exe.outputs[0]
    assert_almost_equal(out.asnumpy(), [2, 4])
    exe.forward(x=mx.nd.array([5.0, 6.0]))
    # same handle updates in place (reference persistent outputs)
    assert_almost_equal(out.asnumpy(), [10, 12])


def test_copy_params_from():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=3, name="fc")
    exe = net.simple_bind(ctx=mx.cpu(), data=(2, 4))
    w = rs.randn(3, 4).astype(np.float32)
    exe.copy_params_from({"fc_weight": mx.nd.array(w)}, allow_extra_params=True)
    assert_almost_equal(exe.arg_dict["fc_weight"].asnumpy(), w)
    with pytest.raises(MXNetError):
        exe.copy_params_from({"nonexistent": mx.nd.zeros((1,))})


def test_monitor_callback_interpret_mode():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    net = mx.sym.Activation(net, act_type="relu", name="act")
    exe = net.simple_bind(ctx=mx.cpu(), data=(2, 4))
    seen = []
    exe.set_monitor_callback(lambda name, arr: seen.append(name))
    exe.forward(is_train=False, data=mx.nd.ones((2, 4)))
    assert "fc_output" in seen
    assert "act_output" in seen


def test_executor_reshape():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=3, name="fc")
    exe = net.simple_bind(ctx=mx.cpu(), data=(4, 8))
    w = exe.arg_dict["fc_weight"]
    exe2 = exe.reshape(data=(16, 8))
    assert exe2.arg_dict["data"].shape == (16, 8)
    # parameters are shared, not copied
    assert exe2.arg_dict["fc_weight"] is w
    exe2.forward(is_train=False, data=mx.nd.ones((16, 8)))
    assert exe2.outputs[0].shape == (16, 3)


def test_rng_determinism_per_step():
    net = mx.sym.Dropout(mx.sym.Variable("x"), p=0.5)
    exe = net.simple_bind(ctx=mx.cpu(), x=(50, 50), grad_req="null")
    exe.forward(is_train=True, x=mx.nd.ones((50, 50)))
    m1 = exe.outputs[0].asnumpy()
    exe.forward(is_train=True, x=mx.nd.ones((50, 50)))
    m2 = exe.outputs[0].asnumpy()
    assert not np.array_equal(m1, m2)  # different step → different mask


def test_multi_output_executor():
    x = mx.sym.Variable("x")
    parts = mx.sym.SliceChannel(x, num_outputs=2, name="sc")
    grouped = mx.sym.Group([parts[0] * 2, parts[1] + 1])
    exe = grouped.simple_bind(ctx=mx.cpu(), x=(2, 4), grad_req="null")
    exe.forward(x=mx.nd.array([[1, 2, 3, 4], [5, 6, 7, 8]]))
    assert_almost_equal(exe.outputs[0].asnumpy(), [[2, 4], [10, 12]])
    assert_almost_equal(exe.outputs[1].asnumpy(), [[4, 5], [8, 9]])


def test_debug_str_and_partial_forward():
    """Executor introspection (reference DebugStr + PartialForward)."""
    data = mx.sym.Variable("data")
    h = mx.sym.Activation(
        mx.sym.FullyConnected(data, num_hidden=4, name="fc1"),
        act_type="relu", name="act1",
    )
    net = mx.sym.FullyConnected(h, num_hidden=2, name="fc2")
    exe = net.simple_bind(mx.cpu(), data=(3, 5))
    plan = exe.debug_str()
    assert "fc1" in plan and "fc2" in plan and "FullyConnected" in plan
    assert "Total" in plan

    rng = np.random.RandomState(0)
    x = rng.randn(3, 5).astype(np.float32)
    w1 = rng.randn(4, 5).astype(np.float32)
    exe.arg_dict["fc1_weight"][:] = mx.nd.array(w1)
    exe.arg_dict["fc1_bias"][:] = mx.nd.zeros((4,))
    # first op node only: the fc1 pre-activation
    outs = exe.partial_forward(num_nodes=1, data=mx.nd.array(x))
    np.testing.assert_allclose(outs[0].asnumpy(), x.dot(w1.T), rtol=1e-5)
    # two nodes: relu applied
    outs = exe.partial_forward(num_nodes=2, data=mx.nd.array(x))
    np.testing.assert_allclose(
        outs[0].asnumpy(), np.maximum(x.dot(w1.T), 0), rtol=1e-5
    )


def test_reshape_uses_lazy_placeholders():
    """Bucketing-style reshape must not allocate fresh input/grad buffers
    per bucket: mismatched-shape entries are lazy placeholders that the
    per-batch bind overwrites without ever materialising (the reference
    bounds bucket memory with the shared data_pool_,
    graph_executor.cc:813-817)."""
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                              name="fc"),
        name="softmax")
    exe = net.simple_bind(mx.cpu(), data=(8, 6), softmax_label=(8,))
    exe.arg_dict["fc_weight"][:] = np.ones((4, 6), np.float32)
    exe2 = exe.reshape(data=(2, 6), softmax_label=(2,))
    data2 = exe2.arg_dict["data"]
    assert data2._d is None, "placeholder materialised eagerly"
    assert data2.shape == (2, 6)          # metadata without allocation
    assert str(data2.dtype) == "float32"
    assert data2._d is None, "shape/dtype query allocated the placeholder"
    # params are SHARED, not copied
    assert exe2.arg_dict["fc_weight"]._d is exe.arg_dict["fc_weight"]._d
    # the normal flow binds fresh data; the placeholder must never fire
    out = exe2.forward(
        is_train=False, data=np.ones((2, 6), np.float32),
        softmax_label=np.zeros(2, np.float32),
    )[0].asnumpy()
    assert out.shape == (2, 4)
    # reading an UNBOUND placeholder still works (materialises zeros)
    exe3 = exe.reshape(data=(3, 6), softmax_label=(3,))
    assert np.all(exe3.grad_dict["fc_weight"].asnumpy() == 0) \
        if exe3.grad_dict.get("fc_weight") is not None else True


def test_nonuniform_workload_warns():
    import warnings

    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2),
        name="softmax")
    from mxnet_tpu.module.executor_group import DataParallelExecutorGroup
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        DataParallelExecutorGroup(
            net, [mx.cpu(0), mx.cpu(1)], workload=[1, 3],
            data_shapes=[("data", (16, 4))],
            label_shapes=[("softmax_label", (16,))],
            param_names=[n for n in net.list_arguments()
                         if n not in ("data", "softmax_label")],
            for_training=True, inputs_need_grad=False,
        )
    assert any("workload" in str(x.message) for x in w), \
        [str(x.message) for x in w]


def test_xla_flags_reach_compile_options_and_digests(monkeypatch):
    """MXNET_XLA_FLAGS threads into the per-executable compiler options
    (typed: bools/ints coerced — XLA's debug-option overrides are typed)
    AND into the AOT digest/fingerprint, so a persisted executable never
    serves a program compiled under different flags."""
    from mxnet_tpu import aot
    from mxnet_tpu.executor import _compiler_options, _parse_xla_flag

    monkeypatch.delenv("MXNET_XLA_FLAGS", raising=False)
    assert _compiler_options() is None  # empty -> jax defaults
    base_digest = aot.digest("probe")

    monkeypatch.setenv(
        "MXNET_XLA_FLAGS",
        "xla_cpu_enable_fast_math=true, xla_force_host_platform_device_count=2,"
        "xla_gpu_autotune_level=0.5,xla_dump_to=/tmp/x")
    opts = _compiler_options()
    assert opts == {"xla_cpu_enable_fast_math": True,
                    "xla_force_host_platform_device_count": 2,
                    "xla_gpu_autotune_level": 0.5,
                    "xla_dump_to": "/tmp/x"}
    assert _parse_xla_flag("false") is False
    # different flags => different AOT digest for the SAME program
    assert aot.digest("probe") != base_digest


# --- FullyConnected nodes that share a weight (Executor._shared_fc_plan) ---
# a group whose nodes do not read each other runs as one matmul, forward and
# backward: one stacked weight-gradient matmul a shared weight

def _check_grads(exe, want, rtol, atol):
    for n, g in want.items():
        assert_almost_equal(exe.grad_dict[n].asnumpy(), g, rtol=rtol,
                            atol=atol, names=(f"grad[{n}]", "reference"))


@pytest.mark.parametrize("case", sorted(swc.CASES))
def test_shared_fc_grads_match_per_node_reference(case):
    sym, shapes, loss, n_groups = swc.CASES[case]()
    vals = swc.values(sym, shapes)
    exe = swc.bound(sym, shapes, vals)
    assert swc.n_stacked(exe) == n_groups
    exe.forward(is_train=True)
    exe.backward()
    tol = (5e-2, 5e-2) if case == "bf16" else (1e-5, 1e-5)
    _check_grads(exe, swc.reference_grads(loss, vals), *tol)


def test_shared_fc_grad_req_add():
    sym, shapes, loss, n_groups = swc.recurrent("lstm")
    vals = swc.values(sym, shapes)
    exe = swc.bound(sym, shapes, vals, grad_req="add")
    assert swc.n_stacked(exe) == n_groups
    for g in exe.grad_dict.values():
        g[:] = 1.0
    exe.forward(is_train=True)
    exe.backward()
    want = {n: g + 1.0 for n, g in swc.reference_grads(loss, vals).items()}
    _check_grads(exe, want, 1e-5, 1e-5)


def test_shared_fc_under_remat(monkeypatch):
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    sym, shapes, loss, n_groups = swc.recurrent("lstm")
    vals = swc.values(sym, shapes)
    exe = swc.bound(sym, shapes, vals)
    assert exe.graph.remat and swc.n_stacked(exe) == n_groups
    exe.forward(is_train=True)
    exe.backward()
    _check_grads(exe, swc.reference_grads(loss, vals), 1e-5, 1e-5)


def test_shared_fc_on_a_dp_mesh():
    """Two CPU devices, the batch sharded: the nodes' rows are stacked on a
    new axis, the sharded batch axis stays where it is, and the gradients
    still equal the reference's."""
    sym, shapes, loss, n_groups = swc.recurrent("lstm")
    vals = swc.values(sym, shapes)
    fed = sorted(shapes)
    mod = mx.mod.Module(sym, data_names=fed, label_names=None,
                        context=[mx.cpu(0), mx.cpu(1)])
    mod.bind(data_shapes=[(n, shapes[n]) for n in fed], for_training=True,
             inputs_need_grad=True)
    mod.init_params(arg_params={n: mx.nd.array(v) for n, v in vals.items()
                                if n not in shapes}, allow_missing=False)
    exe = mod._exec_group._exec
    assert str(exe.arg_dict["data"]._data.sharding.spec) == \
        "PartitionSpec('dp',)"
    assert swc.n_stacked(exe) == n_groups
    mod.forward_backward(mx.io.DataBatch(
        data=[mx.nd.array(vals[n]) for n in fed], label=None))
    _check_grads(exe, swc.reference_grads(loss, vals), 1e-5, 1e-5)


@pytest.mark.parametrize("groups,batched", [
    (("a", "a", "a"), 1),  # one device: batched there
    (("a", "b", "a"), 0),  # split over devices: left alone
])
def test_shared_fc_groups_with_ctx_groups(groups, batched):
    sym, shapes, loss, _n = swc.towers(groups=groups)
    vals = swc.values(sym, shapes)
    exe = swc.bound(sym, shapes, vals,
                    group2ctx={"a": mx.cpu(1), "b": mx.cpu(2)})
    assert swc.n_stacked(exe) == batched
    exe.forward(is_train=True)
    exe.backward()
    _check_grads(exe, swc.reference_grads(loss, vals), 1e-5, 1e-5)


def _lowered_train_step(exe):
    exe.forward(is_train=True)
    exe.backward()
    fn = exe._get_jit("train_step")
    return fn.jit_fn.lower(
        exe._bwd_args, exe._bwd_args_flat, exe._bwd_aux, exe._bwd_aux_flat,
        exe._bwd_rng, None, exe._bwd_prev).as_text()


def test_shared_fc_groups_lower_batched_dots():
    """T = 6, two layers. Each i2h group runs as one batched matmul forward,
    one for the data gradient and one for the weight's (at these sizes a
    run holds all six time steps); the h2h chains keep a dot a time step."""
    sym, shapes, _loss, n_groups = swc.recurrent("lstm", steps=6)
    vals = swc.values(sym, shapes)
    req = {n: "null" if n in shapes else "write" for n in vals}
    dots = {}
    for name, off in (("batched", False), ("per_node", True)):
        exe = swc.bound(sym, shapes, vals, grad_req=req)
        if off:
            swc.disable(exe)
        else:
            batched, order, weights = exe._shared_fc_plan()
            assert [[n.name[-3:] for n in nodes] for nodes in batched] == [
                ["i2h"] * 6] * 2 and weights == n_groups
            assert sorted(map(id, order)) == sorted(map(id, exe.graph.topo))
        dots[name] = _lowered_train_step(exe).count("stablehlo.dot_general")
    # the data and the begin states need no gradient. One dot a node:
    # forward 4 x 6, dgrad 3 x 6 - 2, wgrad 4 x 6. Batched: forward 2 + 2 x 6,
    # dgrad 1 + (2 x 6 - 2), wgrad 2 + 2 x 6
    assert dots == {"per_node": 24 + 16 + 24, "batched": 14 + 11 + 14}


def test_long_groups_run_in_chunks():
    """Nine time steps: a run's rows stay about the weight's size, so each
    i2h group is cut in two (5 + 4) and still counts as one shared weight."""
    sym, shapes, _loss, n_groups = swc.CASES["chunked"]()
    exe = swc.bound(sym, shapes, swc.values(sym, shapes))
    batched, _order, weights = exe._shared_fc_plan()
    assert [len(nodes) for nodes in batched] == [5, 4, 5, 4]
    assert weights == n_groups == 2


def test_chunks_are_as_equal_as_they_come():
    cut = lambda n, cap: [len(c) for c in mx.executor._chunks(
        list(range(n)), cap)]
    assert cut(30, 10) == [10, 10, 10] and cut(60, 10) == [10] * 6
    assert cut(30, 9) == [8, 8, 7, 7] and cut(10, 10) == [10]
    assert cut(5, 1) == [2, 2, 1] and cut(6, 100) == [6]


def _wide_pair():
    """A siamese pair of one wide layer at a large batch: the copies of
    its rows would outweigh the one accumulation they save."""
    w, b = mx.sym.Variable("fc_weight"), mx.sym.Variable("fc_bias")
    tower = [mx.sym.FullyConnected(mx.sym.Variable(n), weight=w, bias=b,
                                   num_hidden=8, name=f"fc_{n}")
             for n in ("left", "right")]
    return (mx.sym.MakeLoss(mx.sym.sum(mx.sym.square(tower[0] - tower[1]))),
            {"left": (64, 8), "right": (64, 8)})


def _unshared():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                                name="fc1")
    net = mx.sym.FullyConnected(mx.sym.tanh(net), num_hidden=8, name="fc2")
    return mx.sym.MakeLoss(mx.sym.sum(mx.sym.square(net))), {"data": (4, 8)}


def _chain():
    return swc.chain()[:2]


@pytest.mark.parametrize("graph", [_wide_pair, _unshared, _chain])
def test_shared_fc_bypassed_lowers_as_before(graph):
    """A group the byte rule rejects, a graph with no shared weight and a
    group whose nodes read each other lower exactly as with the grouping
    pass disabled."""
    sym, shapes = graph()
    vals = swc.values(sym, shapes)
    exe = swc.bound(sym, shapes, vals)
    assert swc.n_stacked(exe) == 0
    assert bool(exe.graph.shared_fc_groups()) == (graph is not _unshared)
    off = swc.disable(swc.bound(sym, shapes, vals))
    off.graph.shared_fc_groups = lambda: []
    assert _lowered_train_step(exe) == _lowered_train_step(off)


@pytest.mark.parametrize("is_train", [False, True])
def test_forward_only_paths_batch_nothing(is_train, monkeypatch):
    sym, shapes, _loss, _n = swc.recurrent("lstm")
    exe = swc.bound(sym, shapes, swc.values(sym, shapes))
    made = []
    monkeypatch.setattr(mx.executor, "_FCBatches",
                        lambda *a, **k: made.append(a) or pytest.fail("made"))
    exe.forward(is_train=is_train)
    exe.outputs[0].asnumpy()
    assert made == [] and exe._fc_plan is None
