"""The memory ledger (PR 53): what a resolved program needs of its device is
kept on the ``aot.AOTProgram`` where it is resolved, the launch of a train
program sets the gauges that describe the heaviest one, and
``profiler.memory_table()`` prints every program beside the device's own
statistics, also at the end of an out-of-memory error."""

import types

import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.telemetry as tm
from mxnet_tpu import aot

BATCH = 8
PROGRAM_GAUGES = {
    "executor.program_argument_bytes": "argument",
    "executor.program_kept_output_bytes": "kept_output",
    "executor.program_temp_bytes": "temp",
    "executor.program_code_bytes": "code",
}


def _net(hidden):
    h = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(h, num_hidden=hidden, name="fc0")
    h = mx.sym.BatchNorm(h, fix_gamma=False, name="bn0")
    h = mx.sym.Activation(h, act_type="relu", name="act0")
    h = mx.sym.FullyConnected(h, num_hidden=4, name="out")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _module(hidden=16, width=12):
    mx.random.seed(5)
    mod = mx.mod.Module(_net(hidden), context=mx.cpu())
    mod.bind(data_shapes=[("data", (BATCH, width))],
             label_shapes=[("softmax_label", (BATCH,))])
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer="adam")
    return mod


def _batch(width=12, rows=BATCH, **kw):
    rs = np.random.RandomState(0)
    return mx.io.DataBatch(
        data=[mx.nd.array(rs.randn(rows, width).astype(np.float32))],
        label=[mx.nd.array(rs.randint(0, 4, (rows,)).astype(np.float32))],
        **kw)


def _step(mod, batch, **how):
    mod.forward_backward(batch)
    mod.update(**how)


def _gauges():
    return {name: tm.gauge(name).value for name in PROGRAM_GAUGES}  # graftlint: allow=telemetry-catalog(reads the four catalogued literals of PROGRAM_GAUGES)


def _of(program):
    """The four gauges as ``program``'s own executable reads now."""
    m = program.executable.memory_analysis()
    return {
        "executor.program_argument_bytes": m.argument_size_in_bytes,
        "executor.program_kept_output_bytes":
            m.output_size_in_bytes - m.alias_size_in_bytes,
        "executor.program_temp_bytes": m.temp_size_in_bytes,
        "executor.program_code_bytes": m.generated_code_size_in_bytes,
    }


def _bytes(arrays):
    return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
               for a in arrays)


@pytest.fixture
def fused():
    """(module, executor, the one fused plan) after one fused step."""
    tm.reset()
    mod = _module()
    _step(mod, _batch())
    exe = mod._exec_group._exec
    (plan,) = exe._fused_plan.values()
    return mod, exe, plan


@pytest.mark.parametrize("gauge", sorted(PROGRAM_GAUGES))
def test_a_fused_step_sets_what_its_executable_says(fused, gauge):
    _mod, _exe, plan = fused
    assert plan.program.memory is not None
    assert tm.gauge(gauge).value == _of(plan.program)[gauge]  # graftlint: allow=telemetry-catalog(a parametrised literal of PROGRAM_GAUGES)
    assert tm.gauge(gauge).value == getattr(  # graftlint: allow=telemetry-catalog(a parametrised literal of PROGRAM_GAUGES)
        plan.program.memory, PROGRAM_GAUGES[gauge])
    assert plan.program.launches == 1
    assert plan.program.label.startswith("fused update [data(8, 12)")


def test_the_training_state_is_parameters_moments_and_statistics(fused):
    _mod, exe, plan = fused
    params = [exe.arg_dict[n] for n in plan.key.update_names]
    want = 3 * _bytes(params) + _bytes(exe.aux_dict.values())  # adam: m, v
    assert tm.gauge("executor.train_state_bytes").value == want
    # its arguments hold that state, and the batch besides
    assert tm.gauge("executor.program_argument_bytes").value > want


@pytest.mark.parametrize("publish", [True, False])
def test_published_gradients_are_counted_from_shapes(publish):
    tm.reset()
    mod = _module()
    _step(mod, _batch(), publish_grads=publish)
    exe = mod._exec_group._exec
    want = _bytes(exe.arg_dict[n] for n in exe._wrt_names)
    assert want > 0
    assert tm.gauge("executor.published_grad_bytes").value == \
        (want if publish else 0)
    # and the program that leaves them out keeps that much less
    (plan,) = exe._fused_plan.values()
    assert plan.grad_bytes == (want if publish else 0)
    kept = tm.gauge("executor.program_kept_output_bytes").value
    assert (kept >= want) == publish


def test_a_plain_train_step_publishes_and_carries_only_statistics():
    tm.reset()
    mod = _module()
    mod.forward_backward(_batch())
    exe = mod._exec_group._exec
    exe.grad_dict["out_weight"].asnumpy()  # runs the scheduled backward
    assert tm.gauge("executor.published_grad_bytes").value == _bytes(
        exe.arg_dict[n] for n in exe._wrt_names)
    assert tm.gauge("executor.train_state_bytes").value == _bytes(
        exe.aux_dict.values())
    labels = [r["label"] for r in aot.memory_table()["programs"]]
    assert any(lb.startswith("train step [data(8, 12)") for lb in labels)


def _bucketing_module():
    def sym_gen(rows):
        return _net(16), ("data",), ("softmax_label",)

    mx.random.seed(5)
    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=64)
    mod.bind(data_shapes=[("data", (64, 48))],
             label_shapes=[("softmax_label", (64,))])
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer="adam")
    return mod


def _bucket_batch(rows):
    return _batch(
        48, rows, bucket_key=rows,
        provide_data=[mx.io.DataDesc("data", (rows, 48))],
        provide_label=[mx.io.DataDesc("softmax_label", (rows,))])


@pytest.mark.parametrize("order", [(64, 8, 64), (8, 64, 8)])
def test_two_buckets_leave_the_heavier_programs_gauges(order):
    """Rows of 64 and of 8 through one model: whichever order they are
    launched in, all four gauges are the 64-row program's."""
    tm.reset()
    mod = _bucketing_module()
    for rows in order:
        _step(mod, _bucket_batch(rows))
    programs = {}
    for rows, bucket in mod._buckets.items():
        (plan,) = bucket._exec_group._exec._fused_plan.values()
        programs[rows] = plan.program
    heavy, light = programs[64], programs[8]
    assert heavy.memory.footprint > light.memory.footprint
    assert _gauges() == _of(heavy) != _of(light)
    assert (heavy.launches, light.launches) == (order.count(64),
                                                order.count(8))
    rows = aot.memory_table()["programs"]
    mine = [r for r in rows if r["label"].startswith("fused update [data(")
            and r["footprint_bytes"] in (heavy.memory.footprint,
                                         light.memory.footprint)]
    assert {r["label"] for r in mine} >= {
        "fused update [data(64, 48), softmax_label(64,)]",
        "fused update [data(8, 48), softmax_label(8,)]"}


def test_a_reset_zeroes_the_gauges_and_the_next_launch_sets_them(fused):
    mod, _exe, plan = fused
    before = _gauges()
    state = tm.gauge("executor.train_state_bytes").value
    tm.reset()
    assert set(_gauges().values()) == {0}
    _step(mod, _batch())
    assert _gauges() == before == _of(plan.program)
    assert tm.gauge("executor.train_state_bytes").value == state > 0


class _NoAnalysis:
    """An executable as a backend without the analysis hands it out."""

    def __init__(self, compiled):
        self._compiled = compiled

    def __call__(self, *args):
        return self._compiled(*args)


class _SilentJit:
    def __init__(self, fn):
        self._fn = fn

    def lower(self, *args):
        lowered = self._fn.lower(*args)
        return types.SimpleNamespace(
            compile=lambda: _NoAnalysis(lowered.compile()))


def test_a_program_with_no_analysis_sets_nothing_and_shows_none():
    import jax
    import jax.numpy as jnp

    tm.reset()
    prog = aot.AOTProgram(_SilentJit(jax.jit(lambda x: x + 1)),
                          label="no analysis here")
    np.testing.assert_allclose(np.asarray(prog(jnp.ones((2,)))), 2.0)
    assert prog.executable is not None and prog.memory is None
    mx.executor.Executor._note_train_memory(prog, 5, 7)
    assert set(_gauges().values()) == {0}
    assert tm.gauge("executor.published_grad_bytes").value == 0
    assert tm.gauge("executor.train_state_bytes").value == 0
    table = mx.profiler.memory_table()
    (row,) = [r for r in table["programs"]
              if r["label"] == "no analysis here"]
    assert row["launches"] == 1
    assert all(row[k] is None for k in row if k.endswith("_bytes"))
    line = next(ln for ln in table["text"].splitlines()
                if ln.endswith("no analysis here"))
    assert line.split()[:6] == ["None"] * 6


def test_the_table_has_the_five_numbers_and_the_devices_statistics(fused):
    _mod, _exe, plan = fused
    table = mx.profiler.memory_table()
    m = plan.program.executable.memory_analysis()
    row = next(r for r in table["programs"]
               if r["label"] == plan.program.label
               and r["footprint_bytes"] == plan.program.memory.footprint)
    assert (row["argument_bytes"], row["output_bytes"], row["alias_bytes"],
            row["temp_bytes"], row["code_bytes"]) == (
        m.argument_size_in_bytes, m.output_size_in_bytes,
        m.alias_size_in_bytes, m.temp_size_in_bytes,
        m.generated_code_size_in_bytes)
    assert row["footprint_bytes"] == (
        row["argument_bytes"] + row["output_bytes"] - row["alias_bytes"]
        + row["temp_bytes"] + row["code_bytes"])
    # the CPU keeps no statistics: None, never a zero
    import jax

    assert len(table["devices"]) == len(jax.local_devices())
    assert all(d["bytes_in_use"] is None and d["bytes_limit"] is None
               for d in table["devices"])
    assert "bytes_reserved None" in table["text"]


@pytest.mark.parametrize("fault, carried", [
    ("RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to "
     "allocate 2.84G. That was not possible. There are 1.12G free.", True),
    ("INTERNAL: device fault", False),
])
def test_an_out_of_memory_error_ends_in_the_table(fault, carried):
    import jax
    import jax.numpy as jnp

    prog = aot.AOTProgram(jax.jit(lambda x: x + 1, donate_argnums=0),
                          donates=True, label="the step that did not fit")
    prog(jnp.ones((2,)))

    def exhausted(*args):
        raise RuntimeError(fault)

    prog.executable = exhausted
    with pytest.raises(aot.DonatedCallError) as err:
        prog(jnp.ones((2,)))
    message = str(err.value)
    assert message.startswith("a donating executable failed")
    assert ("the step that did not fit" in message) == carried
    assert ("; memory_stats()):" in message) == carried
    assert bool(err.value.memory) == carried


def test_the_fused_steps_error_carries_the_table_on(monkeypatch):
    tm.reset()
    mod = _module()
    _step(mod, _batch())
    exe = mod._exec_group._exec
    (plan,) = exe._fused_plan.values()

    def exhausted(*args):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    monkeypatch.setattr(plan.program, "executable", exhausted)
    mod.forward_backward(_batch())
    with pytest.raises(aot.DonatedCallError) as err:
        mod.update()
    message = str(err.value)
    assert message.startswith("fused train step failed after buffer donation")
    assert plan.program.label in message
    assert "; memory_stats()):" in message
