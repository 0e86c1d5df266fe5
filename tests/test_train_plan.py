"""The seams of the fused train step (executor._TrainPlan, aot.AOTProgram
with ``donates=True``): a donating program has no second try, the executor
says which failure it was, and a second step of any kind of plan is a plan
hit that compiles nothing."""

import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.telemetry as tm
from mxnet_tpu import aot

BATCH = 8


# --- aot.AOTProgram(donates=True) -------------------------------------------

class _SpyJit:
    """A jit function that counts the calls that go through jit itself."""

    def __init__(self, fn):
        self.fn, self.calls = fn, 0
        self.lower = fn.lower

    def __call__(self, *args):
        self.calls += 1
        return self.fn(*args)


def test_donating_program_raises_the_typed_error_and_never_retries():
    import jax
    import jax.numpy as jnp

    jit = _SpyJit(jax.jit(lambda x: x + 1, donate_argnums=0))
    prog = aot.AOTProgram(jit, donates=True)
    np.testing.assert_allclose(np.asarray(prog(jnp.ones((2,)))), 2.0)

    def broken(*args):
        raise RuntimeError("device fault")

    prog.executable = broken
    fallbacks = tm.counter("aot.exec_fallback").value
    with pytest.raises(aot.DonatedCallError) as err:
        prog(jnp.ones((2,)))
    assert isinstance(err.value.__cause__, RuntimeError)
    assert isinstance(err.value, mx.base.MXNetError)
    assert jit.calls == 0  # the jit path would have donated a second time
    assert tm.counter("aot.exec_fallback").value == fallbacks
    assert prog.executable is broken  # no permanent fallback either


def test_donating_program_whose_trace_fails_donates_nothing():
    import jax
    import jax.numpy as jnp

    def traced(x):
        raise ValueError("boom at trace time")

    jit = _SpyJit(jax.jit(traced, donate_argnums=0))
    prog = aot.AOTProgram(jit, donates=True)
    x = jnp.ones((4,))
    fallbacks = tm.counter("aot.compile_fallback").value
    with pytest.raises(ValueError, match="boom at trace time"):
        prog(x)
    assert jit.calls == 0 and not x.is_deleted()
    np.testing.assert_allclose(np.asarray(x), 1.0)
    assert tm.counter("aot.compile_fallback").value == fallbacks
    # and it is tried again, not parked on a fallback
    with pytest.raises(ValueError, match="boom at trace time"):
        prog(x)


# --- executor_group.update_fused on the executor's typed error --------------

def _bn_net(nlayer=4):
    h = mx.sym.Variable("data")
    for i in range(nlayer):
        h = mx.sym.FullyConnected(h, num_hidden=16, name=f"fc{i}")
        h = mx.sym.BatchNorm(h, fix_gamma=False, name=f"bn{i}")
        h = mx.sym.Activation(h, act_type="relu", name=f"act{i}")
    h = mx.sym.FullyConnected(h, num_hidden=4, name="out")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _shared_fc_net():
    parts = mx.sym.SliceChannel(mx.sym.Variable("data"), num_outputs=3,
                                axis=1)
    w, b = mx.sym.Variable("shared_weight"), mx.sym.Variable("shared_bias")
    towers = [mx.sym.FullyConnected(parts[i], weight=w, bias=b,
                                    num_hidden=64, name=f"tower{i}")
              for i in range(3)]
    h = mx.sym.FullyConnected(mx.sym.Concat(*towers, dim=1), num_hidden=4,
                              name="out")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _module(sym, width, contexts=None):
    mx.random.seed(5)
    mod = mx.mod.Module(sym, context=contexts or mx.cpu())
    mod.bind(data_shapes=[("data", (BATCH, width))],
             label_shapes=[("softmax_label", (BATCH,))])
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    return mod


def _batch(width, seed=0):
    rs = np.random.RandomState(seed)
    return mx.io.DataBatch(
        data=[mx.nd.array(rs.randn(BATCH, width).astype(np.float32))],
        label=[mx.nd.array(rs.randint(0, 4, (BATCH,)).astype(np.float32))])


def test_update_fused_rolls_counts_back_and_leaves_the_executor_alone(
        monkeypatch):
    mod = _module(_bn_net(), 12)
    mod.forward_backward(_batch(12))
    mod.update()
    group, opt = mod._exec_group, mod._optimizer
    exe = group._exec
    counts, num_update = dict(opt._index_update_count), opt.num_update
    mod.forward_backward(_batch(12, seed=1))

    def failing(*args, **kwargs):
        raise aot.DonatedCallError("fused train step failed after buffer "
                                   "donation")

    def untouchable(*args, **kwargs):
        raise AssertionError("update_fused read the executor's packs")

    monkeypatch.setattr(exe, "fused_train_update", failing)
    monkeypatch.setattr(exe, "_small_state", untouchable)
    with pytest.raises(aot.DonatedCallError, match="after buffer donation"):
        group.update_fused(opt, mod._updater, n_steps=3)
    assert dict(opt._index_update_count) == counts
    assert opt.num_update == num_update


# --- a second step of every kind of plan is a plan hit -----------------------

def _step(mod, width, seed):
    mod.forward_backward(_batch(width, seed))
    mod.update()


def _step_unpublished(mod, width, seed):
    mod.forward_backward(_batch(width, seed))
    mod.update(publish_grads=False)


def _window(mod, width, seed):
    mod.train_window(None, batches=[_batch(width, seed + i)
                                    for i in range(4)], publish_grads=False)


PLANS = {
    # name: (symbol, data width, contexts, env, one step, what must hold)
    "packed": (_bn_net, 12, None, {}, _step,
               lambda exe: exe._small_state()["arg"] is not None),
    "mesh": (_bn_net, 12, [mx.cpu(0), mx.cpu(1)], {}, _step,
             lambda exe: exe._small_state() is None
             and len(exe.arg_dict["fc0_weight"]._data.devices()) == 2),
    "shared-fc": (_shared_fc_net, 3 * 64, None, {}, _step,
                  lambda exe: exe._shared_fc_plan()[2] == 1),
    "no-publish": (_bn_net, 12, None, {}, _step_unpublished,
                   lambda exe: not next(iter(exe._fused_plan)).publish),
    "guard": (_bn_net, 12, None, {"MXNET_NONFINITE_GUARD": "skip"}, _step,
              lambda exe: next(iter(exe._fused_plan)).guard_on
              and exe.nonfinite_guard_stats() == (0, 0)),
    "window-k4": (_bn_net, 12, None, {}, _window,
                  lambda exe: next(iter(exe._fused_plan)).n_steps == 4),
}


@pytest.mark.parametrize("case", sorted(PLANS))
def test_second_step_of_a_plan_compiles_nothing(case, monkeypatch):
    sym, width, contexts, env, step, holds = PLANS[case]
    for var, val in env.items():
        monkeypatch.setenv(var, val)
    mod = _module(sym(), width, contexts)
    exe = mod._exec_group._exec
    tm.reset()
    step(mod, width, 0)
    assert holds(exe), case
    assert tm.counter("executor.fused_plan_compile").value == 1
    assert tm.counter("executor.fused_plan_hit").value == 0
    built = {n: tm.counter(n).value
             for n in ("executor.jit_compile", "executor.fused_plan_compile")}
    before = mod.get_params()[0]["out_weight"].asnumpy()
    step(mod, width, 7)
    assert len(exe._fused_plan) == 1
    assert tm.counter("executor.fused_plan_hit").value == 1
    assert {n: tm.counter(n).value for n in built} == built
    assert tm.counter("aot.exec_fallback").value == 0
    assert tm.counter("aot.compile_fallback").value == 0
    after = mod.get_params()[0]["out_weight"].asnumpy()
    assert np.isfinite(after).all() and not np.allclose(before, after)
