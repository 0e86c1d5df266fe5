"""The Pallas kernels of the gated delta rule (``ops/gated_delta_kernels.py``:
the chunk-local algebra and the scan over chunks) in Pallas's interpreter on
the CPU, against the ``jax.numpy`` form they stand in for
(``gated_delta._within_chunks``, ``_chunk_step`` under ``lax.scan``) and the
token-by-token recurrence of the plain reference: head width 128, two value
heads a key head, chunks of 64, T a whole number of grid steps (1024) and one
that is padded (1100 -> 2048); the scan's own cases at two key heads and grid
steps of two chunks, so that a few hundred tokens cross several steps. Their
compile for a described v5e sits with the other compile tests in
``test_grouped_matmul.py`` (one file, one libtpu).

Tolerances, and why: with float32 operands the kernels and the ``jax.numpy``
form compute the same float32 algebra in another order (pairs of chunks, the
inverse's own rule in both), so a tensor agrees to 2e-5 of its largest
element, as the chunked form agrees with the recurrence in
``test_qwen3_next.py``. With bfloat16 operands both round ``W`` and the
gradients to bfloat16 (one part in 256), after sums in another order: 8e-3.
"""

import functools

import numpy as np
import pytest
import test_qwen3_next as tq
from test_qwen3_next import rel

import mxnet_tpu as mx
from mxnet_tpu.ops import gated_delta as gd
from mxnet_tpu.ops import gated_delta_kernels as gk

V5E_VMEM = 128 << 20
PLAN = gk.Plan(gk._BLOCK, 64 << 20)
TENSORS = ["output", "dq", "dk", "dv", "dg", "dbeta"]
D, CHUNK = 128, 64


def _inputs(t):
    """``test_qwen3_next._rule_inputs`` at the kernels' widths: one key
    head of 128 under two value heads, one batch row."""
    return tq._rule_inputs(t, key_heads=1, value_heads=2, batch=1, dim=D)


@functools.lru_cache(maxsize=None)
def _kernels_and_form(t, dtype, make=_inputs, plan=PLAN):
    """{tensor: (through the kernels at ``plan``, the ``jax.numpy`` form)}
    of ``make(t)``: outputs and all five gradients."""
    import jax
    import jax.numpy as jnp

    q, k, v, g, beta = make(t)
    args = [jnp.asarray(x, dtype) for x in (q, k, v)] \
        + [jnp.asarray(g), jnp.asarray(beta)]
    head = jnp.asarray(np.random.RandomState(4).randn(*v.shape), dtype)

    def both(**kw):
        out, vjp = jax.vjp(functools.partial(
            gd.chunk_gated_delta_rule, chunk=CHUNK, **kw), *args)
        return (out,) + vjp(head)

    got, want = both(kernels=plan, interpret=True), both()
    return {n: (np.asarray(a, np.float32), np.asarray(b, np.float32))
            for n, a, b in zip(TENSORS, got, want)}


@pytest.mark.parametrize("tensor", TENSORS)
@pytest.mark.parametrize("dtype,tolerance", [("float32", 2e-5),
                                             ("bfloat16", 8e-3)])
@pytest.mark.parametrize("t", [1024, 1100], ids=["whole_steps", "padded"])
def test_kernels_match_the_jax_numpy_form(t, dtype, tolerance, tensor):
    got, want = _kernels_and_form(t, dtype)[tensor]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert rel(got, want) < tolerance


def test_kernels_pad_t_to_whole_grid_steps():
    assert gk.padded(1024, CHUNK, PLAN) == 1024
    assert gk.padded(1100, CHUNK, PLAN) == 2048
    assert gk.padded(1, CHUNK, PLAN) == CHUNK * gk._BLOCK


def test_alike_keys_do_not_break_the_kernels_inverse():
    """``test_alike_keys_do_not_break_the_chunks_inverse`` through the
    kernels: the triangular system of a chunk has entries near 1; block
    substitution in float32 holds the recurrence's answer."""
    import jax
    import jax.numpy as jnp

    q, k, v, g, beta = _inputs(1024)
    k = k[:, :, :1] + 0.05 * k
    k = (k / np.linalg.norm(k, axis=-1, keepdims=True)).astype(np.float32)
    g, beta = 0.01 * g, 0.9 + 0.1 * beta
    args = [jnp.asarray(x) for x in (q, k, v, g, beta)]
    with jax.default_matmul_precision("highest"):
        want = tq._token_by_token(tq.mc.load("reference", tq.NAME), *args)
    got = gd.chunk_gated_delta_rule(*args, chunk=CHUNK, kernels=PLAN,
                                    interpret=True)
    assert rel(got, want) < 2e-5


def test_every_product_with_the_inverse_is_float32_at_highest():
    """The kernels' twin of
    ``test_decay_inverse_and_state_are_float32_under_a_bfloat16_trunk``:
    inside both kernels ``exp`` is float32, and every ``dot_general`` is a
    float32 product at ``precision=HIGHEST`` except ``K K^T`` and its
    gradient, whose operands are the trunk's with float32 accumulation and
    none of which is (chunk x chunk) wide on both sides of a float32
    operand."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    q, k, v, g, beta = _inputs(1024)
    args = [jnp.asarray(x, jnp.bfloat16) for x in (q, k, v)] \
        + [jnp.asarray(g), jnp.asarray(beta)]
    f = functools.partial(gd.chunk_gated_delta_rule, chunk=CHUNK,
                          kernels=PLAN, interpret=True)
    calls = [e for e in tq._eqns(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(f(*a).astype(jnp.float32)),
        (0, 1, 2, 3, 4)))(*args).jaxpr) if e.primitive.name == "pallas_call"]
    assert sorted(e.params["name"] for e in calls) == [
        "gated_delta_chunks_bwd", "gated_delta_chunks_fwd",
        "gated_delta_scan_bwd", "gated_delta_scan_fwd"]
    calls = [e for e in calls if "chunks" in e.params["name"]]
    trunk, highest = 0, 0
    for call in calls:
        for e in tq._eqns(call.params["jaxpr"]):
            out = e.outvars[0].aval
            if e.primitive.name == "exp":
                assert out.dtype == jnp.float32, e
            if e.primitive.name != "dot_general":
                continue
            a, b = (x.aval for x in e.invars)
            assert out.dtype == jnp.float32, e
            if a.dtype == b.dtype == jnp.bfloat16:
                # K K^T (C, Dk) x (C, Dk), or its gradient's (C, C) x (C, Dk)
                assert D in a.shape or D in b.shape, e
                trunk += 1
            else:
                assert a.dtype == b.dtype == jnp.float32, e
                assert e.params["precision"] in (
                    lax.Precision.HIGHEST,
                    (lax.Precision.HIGHEST, lax.Precision.HIGHEST)), e
                highest += 1
    # a pair of chunks: forward 2 K K^T; backward 2 and 2 for their gradient
    assert trunk == 6
    # forward, a value head: 10 of the inverse, 2 solves; backward: 2 x 2 for
    # d rhs and dX, 2 for dL
    assert highest == 2 * (10 + 2) + 2 * (4 + 2)


def test_backward_keeps_a_state_a_chunk_not_a_token_with_the_kernels_on():
    """``test_backward_keeps_a_state_a_chunk_not_a_token``'s bound with the
    kernels on: the operands, ``U``, ``W``, the chunks' inverses (T x chunk
    a head, pairs of chunks side by side) and one state a chunk."""
    import jax
    import jax.numpy as jnp

    batch, key_heads, heads, t = 1, 1, 2, 2048
    inputs = [jnp.asarray(x) for x in _inputs(t)]
    _, vjp = jax.vjp(functools.partial(
        gd.chunk_gated_delta_rule, chunk=CHUNK, kernels=PLAN,
        interpret=True), *inputs)
    kept = [x for x in jax.tree.leaves(vjp) if hasattr(x, "shape")]
    state = batch * heads * D * D
    states = [x for x in kept
              if x.shape[-2:] == (D, D) and x.size >= state]
    assert [x.size for x in states] == [t // CHUNK * state]
    assert states[0].dtype == jnp.float32
    pairs = (batch, key_heads, heads // key_heads, t // CHUNK // 2, CHUNK,
             2 * CHUNK)
    assert [x.dtype for x in kept if x.shape == pairs] == [jnp.float32]
    # everything else is of the operands' size: (B, H, T, D) at most
    assert max(x.size for x in kept if x is not states[0]) \
        <= batch * heads * t * D
    assert sum(x.size for x in kept) < t * state // 4


RULE_CASES = {
    # the cell: 16 key / 32 value heads of 128, chunks of 64, T 8192
    "the_cell": (("tpu", V5E_VMEM, "bfloat16", 128, 128, 2, 64, 8192), True),
    "one_to_one_heads_of_256": (
        ("tpu", V5E_VMEM, "bfloat16", 256, 256, 1, 64, 4096), True),
    "t_is_padded": (("tpu", V5E_VMEM, "bfloat16", 128, 128, 2, 64, 100),
                    True),
    "cpu": (("cpu", V5E_VMEM, "bfloat16", 128, 128, 2, 64, 8192), False),
    "float32_trunk": (("tpu", V5E_VMEM, "float32", 128, 128, 2, 64, 8192),
                      False),
    # ``attached_vmem_bytes`` of a process that holds several chips, or a
    # chip of a kind its table does not list
    "several_chips_or_an_unknown_vmem": (
        ("tpu", None, "bfloat16", 128, 128, 2, 64, 8192), False),
    "key_width_128_does_not_divide": (
        ("tpu", V5E_VMEM, "bfloat16", 64, 128, 2, 64, 8192), False),
    "value_width_128_does_not_divide": (
        ("tpu", V5E_VMEM, "bfloat16", 128, 192, 2, 64, 8192), False),
    "another_chunk": (("tpu", V5E_VMEM, "bfloat16", 128, 128, 2, 32, 8192),
                      False),
    "a_vmem_too_small": (("tpu", 8 << 20, "bfloat16", 128, 128, 2, 64, 8192),
                         False),
}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_rule_says_where_the_kernels_engage(case):
    args, engages = RULE_CASES[case]
    plan = gk.plan(*args)
    assert (plan is not None) == engages
    if engages:
        assert plan.chunks == gk._BLOCK and plan.chunks % 16 == 0
        assert plan.vmem_limit <= args[1] * 3 // 4


@pytest.mark.parametrize("chips,engages", [(1, True), (4, False)])
def test_rule_with_chips_attached(monkeypatch, chips, engages):
    """``kernel_plan``, what the op and the executor's counter ask: one
    attached v5e gives the cell's shapes a plan, four give none, and a
    program lowered for the CPU in such a process gets none."""
    import jax

    class Chip:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()] * chips)
    shapes = ((1, 16, 8192, 128), (1, 32, 8192, 128), 64)
    assert (gd.kernel_plan("bfloat16", *shapes) is not None) == engages
    assert (gd.kernel_plan("bfloat16", *shapes, "tpu") is not None) == engages
    assert gd.kernel_plan("bfloat16", *shapes, "cpu") is None
    assert gd.kernel_plan("float32", *shapes, "tpu") is None


def test_on_the_cpu_the_op_takes_the_jax_numpy_form():
    import jax
    import jax.numpy as jnp

    assert gd.kernel_plan("bfloat16", (1, 16, 8192, 128), (1, 32, 8192, 128),
                          64) is None
    sym = mx.sym.GatedDeltaRule(*[mx.sym.Variable(n) for n in (
        "query", "key", "value", "g", "beta")], name="delta")
    inputs = _inputs(128)
    exe = sym.bind(mx.cpu(), {n: mx.nd.array(a).astype("bfloat16")
                              for n, a in zip(sym.list_arguments(), inputs)})
    text = str(jax.make_jaxpr(lambda *a: gd.chunk_gated_delta_rule(
        *a, chunk=CHUNK))(*[jnp.asarray(x, jnp.bfloat16) for x in inputs]))
    assert "pallas_call" not in text
    assert exe.forward()[0].shape == inputs[2].shape


# --- the scan over chunks in its kernels --------------------------------------
# a grid step of two chunks: 384 tokens are three steps, 300 are padded to them
STEP = gk.Plan(2, 64 << 20)


def _scan_inputs(t, slow=False):
    """Two key heads under four value heads; ``slow``: decays near 1 (a
    state fades by under a tenth over all of T), so that what a chunk
    reads is mostly what the chunks before it wrote."""
    q, k, v, g, beta = tq._rule_inputs(t, key_heads=2, value_heads=4,
                                       batch=1, dim=D)
    return [q, k, v, g * (0.1 / t if slow else 1.0), beta]


def _slow_scan_inputs(t):
    return _scan_inputs(t, slow=True)


@pytest.mark.parametrize("tensor", TENSORS)
@pytest.mark.parametrize("dtype,tolerance", [("float32", 2e-5),
                                             ("bfloat16", 8e-3)])
@pytest.mark.parametrize("t", [384, 300], ids=["three_steps", "padded"])
def test_scan_kernels_match_the_jax_numpy_form(t, dtype, tolerance, tensor):
    got, want = _kernels_and_form(t, dtype, _scan_inputs, STEP)[tensor]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert rel(got, want) < tolerance


@pytest.mark.parametrize("tensor", TENSORS)
def test_scan_kernels_carry_the_state_over_chunks_and_grid_steps(tensor):
    """Decays near 1: the form's output over the last grid step's tokens is
    far from what the same tokens give alone (a state dropped at a grid
    step's edge), and the kernels hold the form's answer."""
    import jax.numpy as jnp

    t, step = 384, CHUNK * STEP.chunks
    got, want = _kernels_and_form(t, "float32", _slow_scan_inputs,
                                  STEP)[tensor]
    assert rel(got, want) < 2e-5
    if tensor == "output":
        alone = gd.chunk_gated_delta_rule(
            *[jnp.asarray(x[:, :, t - step:])
              for x in _slow_scan_inputs(t)], chunk=CHUNK)
        assert rel(alone, want[:, :, t - step:]) > 0.3
        chunk = gd.chunk_gated_delta_rule(
            *[jnp.asarray(x[:, :, t - CHUNK:])
              for x in _slow_scan_inputs(t)], chunk=CHUNK)
        assert rel(chunk, want[:, :, t - CHUNK:]) > 0.3


SCAN_TENSORS = ["output", "dq", "dk", "dU", "dW", "dc"]


@functools.lru_cache(maxsize=None)
def _scan_alone(dtype):
    """{tensor: (``across_chunks``, ``_chunk_step`` under ``lax.scan``)} on
    the same ``U`` and ``W``: the new kernels with nothing else between."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, hk, g, n = 1, 2, 2, 6
    rs = np.random.RandomState(7)
    q, k = (rs.randn(b, hk, n, CHUNK, D) for _ in range(2))
    q, k = (x / np.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    args = [jnp.asarray(q / np.sqrt(D), dtype), jnp.asarray(k, dtype),
            jnp.asarray(rs.randn(b, hk, g, n, CHUNK, D), jnp.float32),
            jnp.asarray(0.1 * rs.randn(b, hk, g, n, CHUNK, D), dtype),
            jnp.cumsum(jnp.asarray(-rs.uniform(
                0.001, 0.05, (b, hk, g, n, CHUNK)), jnp.float32), -1)]
    head = jnp.asarray(rs.randn(b, hk, g, n, CHUNK, D), dtype)

    def form(q, k, u, w, c):
        chunks = (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0),
                  jnp.moveaxis(u, 3, 0), jnp.moveaxis(w, 3, 0),
                  jnp.moveaxis(c, 3, 0))
        state = jnp.zeros((b, hk, g, D, D), jnp.float32)
        return jnp.moveaxis(lax.scan(gd._chunk_step, state, chunks)[1], 0, 3)

    def both(f):
        out, vjp = jax.vjp(f, *args)
        return (out,) + vjp(head)

    got = both(lambda *a: gk.across_chunks(*a, STEP, True))
    return {name: (np.asarray(a, np.float32), np.asarray(b, np.float32))
            for name, a, b in zip(SCAN_TENSORS, got, both(form))}


@pytest.mark.parametrize("tensor", SCAN_TENSORS)
@pytest.mark.parametrize("dtype,tolerance", [("float32", 2e-5),
                                             ("bfloat16", 8e-3)])
def test_scan_kernels_alone_match_the_scan(dtype, tolerance, tensor):
    got, want = _scan_alone(dtype)[tensor]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert rel(got, want) < tolerance


def test_scan_kernels_products_are_the_trunks_with_float32_accumulation():
    """``_chunk_step``'s precision and no other: inside both kernels of the
    scan every ``dot_general`` has operands of the trunk's dtype (the state
    and every cotangent cast to it, as ``mm`` casts them) and a float32
    result, every ``exp`` is float32, and the state the forward kernel
    carries, writes and the backward kernel reads is float32."""
    import jax
    import jax.numpy as jnp

    q, k, v, g, beta = _inputs(1024)
    args = [jnp.asarray(x, jnp.bfloat16) for x in (q, k, v)] \
        + [jnp.asarray(g), jnp.asarray(beta)]
    f = functools.partial(gd.chunk_gated_delta_rule, chunk=CHUNK,
                          kernels=PLAN, interpret=True)
    calls = {e.params["name"]: e for e in tq._eqns(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(f(*a).astype(jnp.float32)),
        (0, 1, 2, 3, 4)))(*args).jaxpr) if e.primitive.name == "pallas_call"}
    products = {}
    for name in ("gated_delta_scan_fwd", "gated_delta_scan_bwd"):
        body = list(tq._eqns(calls[name].params["jaxpr"]))
        assert any(e.primitive.name == "exp" for e in body)
        for e in body:
            if e.primitive.name == "exp":
                assert e.outvars[0].aval.dtype == jnp.float32, e
            if e.primitive.name == "dot_general":
                a, b = (x.aval for x in e.invars)
                assert a.dtype == b.dtype == jnp.bfloat16, e
                assert e.outvars[0].aval.dtype == jnp.float32, e
                products[name] = products.get(name, 0) + 1
    # a chunk: Q K^T once, then a value head: W S, (e^c Q) S, A V', K^T V'
    # forward; backward W S again, eight of the cotangents, and dq, dk
    assert products == {"gated_delta_scan_fwd": 1 + 2 * 4,
                        "gated_delta_scan_bwd": 1 + 2 * 9 + 2}
    # the start states: the forward kernel's second result, float32
    states = calls["gated_delta_scan_fwd"].outvars[1].aval
    assert states.dtype == jnp.float32 and states.shape[-2:] == (D, D)
    assert [x.aval.dtype for x in calls["gated_delta_scan_fwd"].params[
        "jaxpr"].invars][-1] == jnp.float32     # the carried state (scratch)


@pytest.mark.parametrize("case", sorted(
    c for c, (_, engages) in RULE_CASES.items() if not engages))
def test_without_a_plan_the_scan_is_the_while_it_was(case):
    """Where the rule returns None the operator is the ``jax.numpy`` form:
    its lowered text holds the ``lax.scan``'s ``while`` and no kernel."""
    import jax
    import jax.numpy as jnp

    (platform, vmem, dtype, dk, dv, group, chunk, t), _ = RULE_CASES[case]
    assert gk.plan(platform, vmem, dtype, dk, dv, group, chunk, t) is None
    t = 4 * chunk
    shapes = [(1, 1, t, dk), (1, 1, t, dk), (1, group, t, dv),
              (1, group, t), (1, group, t)]
    text = jax.jit(functools.partial(
        gd.chunk_gated_delta_rule, chunk=chunk, kernels=None)).lower(
            *[jax.ShapeDtypeStruct(s, dtype if i < 3 else jnp.float32)
              for i, s in enumerate(shapes)]).as_text()
    assert "stablehlo.while" in text
    assert "custom_call" not in text


# --- the model through the kernels, steered here ------------------------------

@pytest.mark.parametrize("mirror", ["", "1"], ids=["kept", "recomputed"])
def test_train_program_through_the_kernels_takes_its_inputs(monkeypatch,
                                                            mirror):
    """The tiny model's fused train step with the chunk-local algebra in
    the kernels (the rule steered here to a plan, the kernels interpreted),
    also under ``MXNET_BACKWARD_DO_MIRROR=1`` as the benchmark's cell runs:
    the ``custom_vjp`` over the pair of kernels sits in ``jax.checkpoint``
    then, and a traced value it closed over would be another trace's tracer
    (PR 34: "compiled for 158 inputs but called with 146"). The program
    launches, counts three kernel layers and three scan-kernel layers, and its
    outputs and every parameter's step are those of the ``jax.numpy`` form."""
    from mxnet_tpu import telemetry as tm

    if mirror:
        monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", mirror)
    gen = tq.tiny_sym_gen()
    ids, label = tq.seeded_tokens(seq_len=200)
    params = tq.seeded_params(gen(200)[0], data=(tq.B, 200),
                              softmax_label=(tq.B, 200))

    def step(steered):
        if steered:
            rule = gd.chunk_gated_delta_rule
            monkeypatch.setattr(gd, "kernel_plan", lambda *a: PLAN)
            monkeypatch.setattr(
                gd, "chunk_gated_delta_rule",
                functools.partial(rule, interpret=True))
        mod = mx.mod.Module(gen(200)[0], context=mx.cpu())
        mod.bind(data_shapes=[("data", (tq.B, 200))],
                 label_shapes=[("softmax_label", (tq.B, 200))])
        mod.init_params(arg_params={n: mx.nd.array(a)
                                    for n, a in params.items()},
                        aux_params={})
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        before = tm.snapshot()
        mod.forward_backward(mx.io.DataBatch(data=[mx.nd.array(ids)],
                                             label=[mx.nd.array(label)]))
        mod.update()
        after = tm.snapshot()["executor"]
        counted = tuple(
            after.get(name, 0) - before.get("executor", {}).get(name, 0)
            for name in ("linear_attention_kernel_layers",
                         "linear_attention_scan_kernel_layers"))
        return (counted, mod.get_outputs()[0].asnumpy(),
                {n: a.asnumpy() for n, a in mod.get_params()[0].items()})

    form_count, form_out, form_params = step(False)
    count, out, now = step(True)
    # ``Executor._count_train_launch``: the chunk-local algebra and the
    # scan over chunks, three layers each, from the one rule
    assert (form_count, count) == ((0, 0), (3, 3))
    assert rel(out, form_out) < 1e-4
    for n, a in now.items():
        assert not np.array_equal(a, params[n]), n
        assert rel(a - params[n], form_params[n] - params[n]) < 1e-3, n
