"""Fused train-step equivalence: the single donated fwd+bwd+update XLA
program (Executor.fused_train_update) must produce the same parameters and
optimizer state as the imperative per-param updater path it replaces
(reference semantics: Updater over src/operator/optimizer_op.cc kernels).
"""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym_mod


def _mlp():
    data = sym_mod.Variable("data")
    net = sym_mod.FullyConnected(data, name="fc1", num_hidden=16)
    net = sym_mod.Activation(net, name="relu1", act_type="relu")
    net = sym_mod.FullyConnected(net, name="fc2", num_hidden=4)
    return sym_mod.SoftmaxOutput(net, name="softmax")


def _train(optimizer, optimizer_params, n_steps=5, force_legacy=False,
           seed=7):
    mx.random.seed(42)  # identical init across the two runs
    rng = np.random.RandomState(seed)
    xs = rng.randn(n_steps, 8, 10).astype(np.float32)
    ys = rng.randint(0, 4, (n_steps, 8)).astype(np.float32)

    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 10))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(initializer=mx.init.Uniform(0.1))
    mod.init_optimizer(optimizer=optimizer, optimizer_params=optimizer_params)
    if force_legacy:
        # disabling the traceable update forces the per-param updater path
        mod._optimizer.jax_apply = None
    for i in range(n_steps):
        batch = mx.io.DataBatch(
            data=[mx.nd.array(xs[i])], label=[mx.nd.array(ys[i])]
        )
        mod.forward_backward(batch)
        mod.update()
    args, _ = mod.get_params()
    states = mod._updater.states if mod._updater is not None else {}
    return {k: v.asnumpy() for k, v in args.items()}, states


@pytest.mark.parametrize("opt,params", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("sgd", {"learning_rate": 0.1, "clip_gradient": 0.5}),
    ("adam", {"learning_rate": 0.01, "wd": 1e-4, "clip_gradient": 1.0}),
    ("rmsprop", {"learning_rate": 0.01}),
    ("adagrad", {"learning_rate": 0.05, "wd": 1e-4}),
    ("ftrl", {"learning_rate": 0.1}),
    ("adadelta", {}),
    ("nag", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
])
def test_fused_matches_imperative(opt, params):
    fused, _ = _train(opt, params)
    legacy, _ = _train(opt, params, force_legacy=True)
    assert fused.keys() == legacy.keys()
    for k in fused:
        np.testing.assert_allclose(
            fused[k], legacy[k], rtol=2e-5, atol=2e-6,
            err_msg=f"{opt}: param {k} diverged between fused and "
                    "imperative update paths",
        )


def test_fused_state_roundtrips_through_updater(tmp_path):
    """Optimizer state written by the fused path must serialize/reload via
    the Updater exactly like the imperative path (checkpoint parity)."""
    rng = np.random.RandomState(3)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 10))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(initializer=mx.init.Uniform(0.1))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rng.randn(8, 10).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 4, (8,)).astype(np.float32))],
    )
    for _ in range(3):
        mod.forward_backward(batch)
        mod.update()
    fname = str(tmp_path / "opt.states")
    mod.save_optimizer_states(fname)
    # momentum must be non-trivial (the fused path actually wrote state)
    states = mod._updater.states
    assert any(
        st is not None and float(np.abs(st.asnumpy()).sum()) > 0
        for st in states.values()
    )
    mod.load_optimizer_states(fname)
    mod.forward_backward(batch)
    mod.update()  # still trains after reload


def test_forward_after_backward_preserves_ordering():
    """forward() scheduled after a deferred backward() must not be clobbered
    when the backward materialises: engine write-ordering (reference
    threaded_engine read/write sequencing)."""
    rng = np.random.RandomState(11)
    exe_sym = _mlp()
    mod = mx.mod.Module(exe_sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 10))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params(initializer=mx.init.Uniform(0.1))
    d1 = mx.nd.array(rng.randn(4, 10).astype(np.float32))
    d2 = mx.nd.array(rng.randn(4, 10).astype(np.float32))
    lab = mx.nd.array(np.zeros(4, np.float32))
    exe = mod._exec_group._exec
    # train fwd+bwd on batch 1 (deferred), then eval fwd on batch 2
    exe.forward(is_train=True, data=d1._data, softmax_label=lab._data)
    exe.backward()
    out2 = exe.forward(is_train=False, data=d2._data, softmax_label=lab._data)
    got = out2[0].asnumpy()
    # reference: outputs must be batch-2's eval forward, not batch-1's
    exe2 = mod._exec_group._exec
    arg_vals, arg_flat = exe2._arg_vals_split()
    arg_vals = [d2._data if n == "data" else v
                for n, v in zip(exe2.arg_names, arg_vals)]
    aux_vals, aux_flat = exe2._aux_vals_split()
    ref = np.asarray(
        exe2._get_jit("forward", is_train=False)(
            arg_vals, arg_flat, aux_vals, aux_flat, exe2._rng_key(),
        )[0][0]
    )
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    # and batch-1's gradients must still have been computed
    g = exe.grad_dict["fc1_weight"].asnumpy()
    assert np.abs(g).sum() > 0


def test_fused_update_with_monitor_falls_back():
    """Installing a monitor materialises grads eagerly; update() must fall
    back to the imperative path and still converge (no pending backward)."""
    rng = np.random.RandomState(5)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 10))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(initializer=mx.init.Uniform(0.1))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rng.randn(8, 10).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 4, (8,)).astype(np.float32))],
    )
    mod.forward_backward(batch)
    # reading a gradient consumes the scheduled backward
    g = mod._exec_group._exec.grad_dict["fc1_weight"].asnumpy()
    assert np.isfinite(g).all()
    before = mod._exec_group._exec.arg_dict["fc1_weight"].asnumpy().copy()
    mod.update()  # falls back; must still apply the update
    after = mod._exec_group._exec.arg_dict["fc1_weight"].asnumpy()
    assert not np.allclose(before, after)


@pytest.mark.parametrize("n_steps", [1, 2])
def test_fused_update_batches_shared_fc(n_steps):
    """A 2-layer unrolled LSTM through the fused step (one step, and a
    2-step window over the same batch): the published gradients and the
    updated parameters equal plain per-step-dot SGD in jax.numpy, with each
    layer's i2h run as one batched matmul (one stacked wgrad matmul)."""
    import jax
    import jax.numpy as jnp
    import stacked_wgrad_cases as swc
    from mxnet_tpu import telemetry as tm

    sym, shapes, loss, n_groups = swc.recurrent("lstm")
    vals = swc.values(sym, shapes)
    fed = sorted(shapes)
    mod = mx.mod.Module(sym, data_names=fed, label_names=None,
                        context=mx.cpu())
    mod.bind(data_shapes=[(n, shapes[n]) for n in fed])
    mod.init_params(arg_params={n: mx.nd.array(v) for n, v in vals.items()
                                if n not in shapes})
    lr = 0.05
    mod.init_optimizer(optimizer="sgd", optimizer_params={
        "learning_rate": lr, "rescale_grad": 1.0})
    batch = mx.io.DataBatch(data=[mx.nd.array(vals[n]) for n in fed],
                            label=None)
    stacked = tm.counter("executor.stacked_wgrad").value
    plans = tm.counter("executor.fused_plan_compile").value
    if n_steps == 1:
        mod.forward_backward(batch)
        mod.update()
    else:
        mod.train_window(batch, n_steps=n_steps)
    exe = mod._exec_group._exec
    assert tm.counter("executor.fused_plan_compile").value == plans + 1
    # one launch, whatever the window's length
    assert tm.counter("executor.stacked_wgrad").value == stacked + n_groups

    p = {n: jnp.asarray(v) for n, v in vals.items()}
    for _ in range(n_steps):
        grads = jax.grad(loss)(p)
        last = grads
        p = {n: v if n in shapes else v - lr * grads[n] for n, v in p.items()}
    params, _ = mod.get_params()
    for n in params:
        np.testing.assert_allclose(params[n].asnumpy(), np.asarray(p[n]),
                                   rtol=1e-5, atol=1e-5, err_msg=n)
        np.testing.assert_allclose(exe.grad_dict[n].asnumpy(),
                                   np.asarray(last[n]), rtol=1e-5, atol=1e-5,
                                   err_msg=f"grad {n}")
