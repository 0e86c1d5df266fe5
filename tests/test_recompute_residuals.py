"""Per-operator recomputation (``MXNET_BACKWARD_DO_MIRROR=1``) keeps the
residuals an operator names (``ops/registry.keep``) and recomputes the rest:
the executor's per-operator ``jax.checkpoint`` carries the policy
``registry.KeptResiduals``.

For ``RingAttention`` (full, window, grouped heads; the ``jax.numpy`` blocks
and the Pallas kernels), ``GatedDeltaRule`` (kernels and ``jax.numpy`` form)
and ``MoE`` (every expert held, a held range; ``ragged_dot`` and the
kernels), each bound through ``simple_bind`` at a small size on the CPU, the
kernels in Pallas's interpreter: what the operator's checkpoint saves, how
often the gradient's program runs the forward, that outputs and gradients
are the same bits with the switch off, on, and on with no policy (the
behaviour before the policy), and that an operator that marks nothing lowers
as it did.
"""

import functools
import importlib
import re

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import executor as ex
from mxnet_tpu import telemetry as tm
from mxnet_tpu.ops import defs_transformer as dt
from mxnet_tpu.ops import flash_attention as fa
from mxnet_tpu.ops import gated_delta as gd
from mxnet_tpu.ops import gated_delta_kernels as gk
from mxnet_tpu.ops import registry
from mxnet_tpu.ops import row_sum_kernels as rsk

ra = importlib.import_module("mxnet_tpu.parallel.ring_attention")

SWITCH = "MXNET_BACKWARD_DO_MIRROR"
NAMED = "named 'mxnet_tpu.kept_residual'"
V5E_VMEM = 128 << 20
T = 256  # attention's positions


def _attention(steer, kernels=False, kv_heads=2, **params):
    q, k, v = (mx.sym.Variable(n) for n in "qkv")
    sym = mx.sym.RingAttention(q, k, v, causal=True, name="attention",
                               **params)
    shapes = dict(q=(1, 4, T, 128), k=(1, kv_heads, T, 128),
                  v=(1, kv_heads, T, 128))
    if kernels:
        blockwise = ra.blockwise_attention
        steer.setattr(ra, "kernel_plan",
                      lambda *a, **kw: fa.Plan(128, 128, 64 << 20))
        steer.setattr(ra, "blockwise_attention",
                      lambda *a: blockwise(*a, True))   # interpreted
    return sym, shapes, {n: "bfloat16" for n in "qkv"}


def _selected_attention(steer):
    """``RingAttention`` under a selection in the four kernels of
    ``flash_attention`` (interpreted)."""
    names = ("q", "k", "v", "iq", "ik", "iw")
    sym = mx.sym.RingAttention(*[mx.sym.Variable(n) for n in names],
                               causal=True, select_top_k=64,
                               index_loss_coef=1.0, name="attention")
    shapes = dict(q=(1, 4, T, 128), k=(1, 2, T, 128), v=(1, 2, T, 128),
                  iq=(1, 4, T, 64), ik=(1, 1, T, 64), iw=(1, 4, T))
    selected = ra.selected_kernels
    steer.setattr(ra, "kernel_plan",
                  lambda *a, **kw: fa.Plan(128, 128, 64 << 20))
    steer.setattr(ra, "selected_kernels", lambda *a: selected(*a, True))
    return sym, shapes, {n: "bfloat16" for n in names}


def _gated_delta(steer, kernels=False, channel=False):
    names = ("query", "key", "value", "g", "beta")
    sym = mx.sym.GatedDeltaRule(*[mx.sym.Variable(n) for n in names],
                                name="delta")
    t = 1024
    shapes = dict(query=(1, 1, t, 128), key=(1, 1, t, 128),
                  value=(1, 2, t, 128), beta=(1, 2, t),
                  g=(1, 2, t) + ((128,) if channel else ()))
    if kernels:
        rule = gd.chunk_gated_delta_rule
        steer.setattr(gd, "kernel_plan",
                      lambda *a: gk.Plan(gk._BLOCK, 64 << 20))
        steer.setattr(gd, "chunk_gated_delta_rule",
                      functools.partial(rule, interpret=True))
    return sym, shapes, {n: "bfloat16" for n in names[:3]}


def _moe(steer, held=0, kernels=False, row_sums=False):
    sym = mx.sym.MoE(mx.sym.Variable("data"), name="moe", num_experts=8,
                     num_hidden=128, top_k=2, num_local_experts=held,
                     lb_coef=0.01, z_coef=0.001)
    if kernels:
        matmul = dt._expert_matmul
        steer.setattr(
            dt, "_expert_matmul",
            lambda counts, dtype, m, weights, platform=None: matmul(
                counts, dtype, m, weights, "tpu", V5E_VMEM, interpret=True))
    if row_sums:   # a round's two row sums in their kernel too
        steer.setattr(
            dt, "_row_sum_plan",
            lambda platform, dtype, rows, n, h, weights, top_k, vmem=None:
            rsk.kernel_plan("tpu", V5E_VMEM, dtype, rows, n, h,
                            weights[0].shape[0], top_k))
        steer.setattr(rsk, "sum_rows",
                      functools.partial(rsk.sum_rows, interpret=True))
    return sym, dict(data=(1, 256, 128)), {"data": "bfloat16"}


# name: (the bound operator, whether it marks anything, a primitive only
# its forward holds, how often that primitive sits in the recomputed part of
# the gradient's program with the policy and without it)
CASES = {
    "attention-full": (_attention, True, "reduce_max", 0, 1),
    "attention-window": (
        functools.partial(_attention, window=64), True, "reduce_max", 0, 1),
    "attention-grouped": (
        functools.partial(_attention, kv_heads=1), True, "reduce_max", 0, 1),
    "attention-kernels": (
        functools.partial(_attention, kernels=True), True,
        "pallas_call", 1, 2),
    "attention-window-kernels": (
        functools.partial(_attention, kernels=True, window=128), True,
        "pallas_call", 1, 2),
    # the indexer's backward kernel and the attention's, with the select
    # and the forward kernel again or not
    "attention-select-kernels": (_selected_attention, True, "pallas_call",
                                 2, 4),
    # the chunk-local algebra's kernel and the scan's: two backward kernels,
    # with both forward kernels again or not
    "gated-delta-kernels": (
        functools.partial(_gated_delta, kernels=True), True,
        "pallas_call", 2, 4),
    # the jax.numpy form marks nothing: its inner checkpoint is as it was
    "gated-delta-form": (_gated_delta, False, "cumsum", 2, 2),
    "moe-all-held": (_moe, True, "top_k", 0, 1),
    "moe-held-range": (functools.partial(_moe, held=2), True, "top_k", 0, 1),
    # 3 forward + 6 backward kernels of the first round and as many of the
    # loop over further rounds, with the first round's forward again or not
    "moe-all-held-kernels": (
        functools.partial(_moe, kernels=True), True, "pallas_call", 6, 9),
    "moe-held-range-kernels": (
        functools.partial(_moe, held=2, kernels=True), True,
        "pallas_call", 15, 18),
    # 4 of 8 experts: one round holds every assignment and no loop is
    # traced, so the round alone, differentiated as it stands
    "moe-one-round-kernels": (
        functools.partial(_moe, held=4, kernels=True), True,
        "pallas_call", 6, 9),
    # the same two with a round's row sums in their kernel: one more
    # backward kernel a round (the dispatch's) and, where a round runs
    # forward again, the combine's; no backward reads the combine's output,
    # so without the policy the first round's three matmuls alone run twice
    "moe-held-range-row-sum-kernels": (
        functools.partial(_moe, held=2, kernels=True, row_sums=True), True,
        "pallas_call", 18, 21),
    "moe-one-round-row-sum-kernels": (
        functools.partial(_moe, held=4, kernels=True, row_sums=True), True,
        "pallas_call", 7, 10),
}


@pytest.fixture(params=sorted(CASES))
def case(request):
    return (request.param,) + CASES[request.param]


class _NoPolicy(registry.KeptResiduals):
    """The executor before it passed a policy: nothing is saveable, which
    is what ``jax.checkpoint`` makes of ``policy=None``."""

    def __call__(self, prim, *avals, **params):
        return False


def _bind(monkeypatch, build, switch, policy=True):
    monkeypatch.setenv(SWITCH, switch)
    if not policy:   # the executor before it passed one
        monkeypatch.setattr(ex, "KeptResiduals", _NoPolicy)
    sym, shapes, types = build(monkeypatch)
    exe = sym.simple_bind(mx.cpu(), type_dict=types, **shapes)
    rs = np.random.RandomState(5)
    for name, a in exe.arg_dict.items():
        scale = 0.1 if name.endswith("weight") else 1.0
        value = rs.randn(*a.shape) * scale
        if name == "g":
            value = -np.abs(value)
        a[:] = mx.nd.array(value).astype(a.dtype)
    return exe


def _gradient_program(exe):
    """(the traced function, its arguments) of the executor's forward +
    backward, every output's head gradient ones."""
    import jax
    import jax.numpy as jnp

    core = exe._make_grad_core()
    args = [jnp.asarray(a.asnumpy()).astype(a.dtype) for a in exe.arg_arrays]
    _, out_shapes, _ = exe._symbol.infer_shape(
        **{n: a.shape for n, a in exe.arg_dict.items()})
    heads = [jnp.ones(s, args[0].dtype) for s in out_shapes]
    rng = (jax.random.PRNGKey(0), jnp.uint32(0))
    return (lambda a, h: core(a, [], rng, h, None)), (args, heads)


def _counted():
    """The counter ``executor.kept_residual_nodes`` so far."""
    return tm.snapshot().get("executor", {}).get("kept_residual_nodes", 0)


def _equations(jaxpr, inside=False):
    """(equation, whether it sits in a rematerialised part: under a
    differentiated ``jax.checkpoint`` equation) of ``jaxpr`` and its
    sub-programs."""
    from jax._src import core as jcore

    for eqn in jaxpr.eqns:
        yield eqn, inside
        below = inside or (eqn.primitive.name == "remat2"
                           and eqn.params["differentiated"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    yield from _equations(sub, below)


def _recomputed(jaxpr, primitive):
    """Equations of ``primitive`` in the rematerialised parts of
    ``jaxpr``."""
    return sum(inside and eqn.primitive.name == primitive
               for eqn, inside in _equations(jaxpr))


# --- (a) what the operator's checkpoint saves ---------------------------------

def _kept_by_the_operators_checkpoint(monkeypatch, build):
    """(the bound executor, the operands of the executor's checkpoint around
    the operator, [(aval, why)] of what that checkpoint saves beside its
    arguments) under the switch."""
    import jax
    from jax._src.ad_checkpoint import saved_residuals

    made = []
    checkpoint = jax.checkpoint

    def recording(fun, **kw):
        wrapped = checkpoint(fun, **kw)

        def call(*args):
            made.append((wrapped, jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)))
            return wrapped(*args)

        return call

    exe = _bind(monkeypatch, build, "1")
    monkeypatch.setattr(jax, "checkpoint", recording)
    fun, args = _gradient_program(exe)
    jax.make_jaxpr(fun)(*args)
    monkeypatch.setattr(jax, "checkpoint", checkpoint)
    operator, (ins,) = made[0]          # the executor's: the outermost
    return exe, ins, [(aval, why) for aval, why
                      in saved_residuals(operator, ins)
                      if "from the argument" not in why]


def test_checkpoint_saves_the_marked_values_and_nothing_the_rule_forbids(
        monkeypatch, case):
    name, build, marks = case[:3]
    exe, ins, kept = _kept_by_the_operators_checkpoint(monkeypatch, build)
    assert bool(kept) == marks
    # of the order of the operands and the output: no score tile, nothing
    # that grows with T x T or with a vocabulary (a float32 copy of the
    # widest operand, top_k rows of it or both halves of a gated pair at
    # most). jax reports a named float as the output of the
    # ``reduce_precision`` it puts before it, one named inside a jitted
    # function as that function's output
    widest = max(int(np.prod(a.shape)) * a.dtype.itemsize for a in ins)
    for aval, why in kept:
        assert NAMED in why or "reduce_precision" in why \
            or "jitted function" in why, why
        assert aval.size * aval.dtype.itemsize <= 4 * widest, (aval, why)
    shapes = sorted({aval.shape for aval, _ in kept})
    if name == "attention-select-kernels":
        # and the rows' thresholds and the kept index scores' log-sum-exp
        assert shapes == [(1, 4, T), (1, 4, T, 128), (1, T)]
    elif name.startswith("attention"):
        # the output and the rows' log-sum-exp: no (T, T) tile
        assert shapes == [(1, 4, T), (1, 4, T, 128)]
    elif name == "gated-delta-kernels":
        # the chunks' inverses in pairs, U and W, and the state every
        # chunk started from: half the widest operand's size in float32
        assert shapes == [(1, 1, 2, 8, 64, 128), (1, 1, 2, 16, 64, 128),
                          (1, 1, 2, 16, 128, 128)]
    elif marks:
        # the routed experts, the rows an expert, the sorted order
        assert {(512,), (8,)} <= set(shapes)
    assert exe._kept_residual_nodes == int(marks)


# name: (rows of a round, how many round-sized arrays its checkpoint keeps)
ROUNDS_KEPT = {
    # ``ragged_dot`` keeps its masks: the rows as each of the gate's and
    # the up's matmul read them and the product as the down's did (masked
    # copies, ``_where``'s outputs) beside the marked values
    "moe-held-range": (256, 7),
    # the kernels own the dead rows: their backward reads the marked rows
    # and product themselves, and the three masked copies are two arrays
    "moe-held-range-kernels": (256, 6),
    "moe-held-range-row-sum-kernels": (256, 6),
    "moe-one-round-kernels": (512, 5),
    "moe-one-round-row-sum-kernels": (512, 5),
}


@pytest.mark.parametrize("name", sorted(ROUNDS_KEPT))
def test_a_rounds_kept_residuals_are_the_marked_values(monkeypatch, name):
    """What a held round's checkpoint keeps at the round's size: on the
    kernel path the values ``_held_round`` marks and nothing a select
    made of them (no ``_where`` output: no select is traced there), one
    array fewer than with the masks; the ``ragged_dot`` path as it was."""
    rows, count = ROUNDS_KEPT[name]
    kept = [why for aval, why in _kept_by_the_operators_checkpoint(
        monkeypatch, CASES[name][0])[2] if aval.shape == (rows, 128)]
    assert len(kept) == count
    assert any("'_where'" in why for why in kept) == ("kernels" not in name)


# --- (b) the forward runs once --------------------------------------------------

def test_gradient_program_holds_the_forward_once(monkeypatch, case):
    import jax

    _, build, marks, primitive, kept, again = case
    counts = []
    for policy in (True, False):
        with monkeypatch.context() as steer:
            exe = _bind(steer, build, "1", policy)
            fun, args = _gradient_program(exe)
            counts.append(_recomputed(jax.make_jaxpr(fun)(*args).jaxpr,
                                      primitive))
            assert exe._kept_residual_nodes == int(marks and policy)
    assert counts == [kept, again]


@pytest.mark.parametrize("channel", [False, True],
                         ids=["a-gate-a-head", "a-gate-a-channel"])
@pytest.mark.parametrize("policy", [True, False], ids=["kept", "no-policy"])
def test_gated_delta_forward_kernels_run_once_by_name(monkeypatch, policy,
                                                      channel):
    """The kernels of ``GatedDeltaRule`` by name: the gradient's program
    holds each forward kernel once (the scan's start states, ``U``, ``W``
    and the inverses are kept; with a gate a channel its two Gram matrices
    and the cumulative decays too) and its rematerialised part only the
    backward kernels; without the policy the forward kernels are there
    again. A gate a channel's running sum and its transpose are the Gram
    kernels' own: no ``cumsum`` anywhere in the program."""
    import jax

    exe = _bind(monkeypatch, functools.partial(
        _gated_delta, kernels=True, channel=channel), "1", policy)
    fun, args = _gradient_program(exe)
    jaxpr = jax.make_jaxpr(fun)(*args).jaxpr
    kernels = [(eqn.params["name"], inside)
               for eqn, inside in _equations(jaxpr)
               if eqn.primitive.name == "pallas_call"]
    halves = ["chunks", "scan"] + ["grams"] * channel
    forward = [f"gated_delta_{half}_fwd" for half in halves]
    backward = [f"gated_delta_{half}_bwd" for half in halves]
    again = [] if policy else forward
    assert sorted(n for n, inside in kernels if inside) == sorted(
        backward + again)
    assert sorted(n for n, _ in kernels) == sorted(
        backward + again + forward)
    if channel:
        assert not [eqn for eqn, _ in _equations(jaxpr)
                    if eqn.primitive.name == "cumsum"]


# --- (c) the same bits ------------------------------------------------------------

def test_outputs_and_gradients_are_the_same_bits(monkeypatch, case):
    build, marks = case[1:3]
    got = []
    for switch, policy in (("0", True), ("1", True), ("1", False)):
        with monkeypatch.context() as steer:
            exe = _bind(steer, build, switch, policy)
            before = _counted()
            exe.forward(is_train=True)
            exe.backward([mx.nd.ones(o.shape, dtype=o.dtype)
                          for o in exe.outputs])
            out = [o.asnumpy() for o in exe.outputs]
            grads = {n: g.asnumpy() for n, g in exe.grad_dict.items()}
            # one launched train program, one node that kept something
            assert _counted() - before == int(
                marks and switch == "1" and policy)
            got.append((out, grads))
    (out, grads), others = got[0], got[1:]
    assert all(np.abs(g.astype(np.float32)).sum() > 0
               for g in grads.values())
    for other_out, other_grads in others:
        for a, b in zip(out, other_out):
            np.testing.assert_array_equal(a, b)
        for name, g in grads.items():
            np.testing.assert_array_equal(g, other_grads[name], name)


# --- (d) an operator that marks nothing lowers as it did ---------------------------

def _convolution(_steer):
    sym = mx.sym.Activation(mx.sym.Convolution(
        mx.sym.Variable("data"), kernel=(3, 3), num_filter=8, name="conv"),
        act_type="relu", name="act")
    return sym, dict(data=(2, 3, 16, 16)), {}


def _fully_connected(_steer):
    sym = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=16, name="fc"), name="softmax")
    return sym, dict(data=(4, 32)), {}


@pytest.mark.parametrize("build", [_convolution, _fully_connected],
                         ids=["Convolution", "FullyConnected"])
def test_operator_that_marks_nothing_lowers_as_before(monkeypatch, build):
    import jax

    texts = []
    for policy in (True, False):
        with monkeypatch.context() as steer:
            exe = _bind(steer, build, "1", policy)
            fun, args = _gradient_program(exe)
            texts.append(jax.jit(fun).lower(*args).as_text())
            assert exe._kept_residual_nodes == 0
    assert texts[0] == texts[1]


def test_a_mark_outside_a_checkpoint_lowers_to_nothing(monkeypatch):
    """Without the switch no ``jax.checkpoint`` is built and ``keep`` leaves
    no operation in the program: the OLMoE cell's, which runs the three
    operators and does not set the switch."""
    import jax

    def lowered():
        exe = _bind(monkeypatch, CASES["moe-all-held"][0], "0")
        fun, args = _gradient_program(exe)
        # private functions are numbered as they are met, process-wide
        return re.sub(r"(@\w+?)_\d+\b", r"\1",
                      jax.jit(fun).lower(*args).as_text())

    marked = lowered()
    monkeypatch.setattr(dt, "keep", lambda values: values)
    assert lowered() == marked


# --- the models' fused step counts its nodes ------------------------------------

@pytest.mark.parametrize("model,switch,kernels,kept", [
    # four attention layers + three sparse layers
    ("test_trinity", "1", False, 7),
    ("test_trinity", "0", False, 0),
    # one attention layer + four sparse layers; on the CPU the three linear
    # layers take the jax.numpy form, which marks nothing
    ("test_qwen3_next", "1", False, 5),
    # steered through the kernels as the cell runs them: jax differentiates
    # the jitted rule of three alike layers once, and all three count
    ("test_qwen3_next", "1", True, 8),
])
def test_launched_train_program_counts_the_nodes_that_kept(
        monkeypatch, model, switch, kernels, kept):
    """``executor.kept_residual_nodes`` through ``Module``'s fused step, as
    the benchmark reads it: once a launched train program, the nodes whose
    checkpoint kept something; nothing with the switch off."""
    tiny = importlib.import_module(model)
    monkeypatch.setenv(SWITCH, switch)
    if kernels:
        _gated_delta(monkeypatch, kernels=True)
    ids, label = tiny.seeded_tokens()
    mod = mx.mod.Module(tiny.tiny_sym_gen()(tiny.T)[0], context=mx.cpu())
    mod.bind(data_shapes=[("data", ids.shape)],
             label_shapes=[("softmax_label", label.shape)])
    mod.init_params(mx.init.Normal(0.1))
    mod.init_optimizer(optimizer="adam")
    counts = []
    for _ in range(2):
        before = _counted()
        mod.forward_backward(mx.io.DataBatch(data=[mx.nd.array(ids)],
                                             label=[mx.nd.array(label)]))
        mod.update()
        counts.append(_counted() - before)
    assert counts == [kept, kept]
