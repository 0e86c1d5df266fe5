"""``SelectiveScan`` (``mxnet_tpu/ops/selective_scan.py``): the chunked
``jax.numpy`` form against a token-at-a-time loop (values and all seven
gradients, a T that is no multiple of the chunk); the Pallas kernels, run in
Pallas's interpreter on the CPU, against the same (a padded T, several grid
steps, one and two lane groups a slab, states of 8 and 16); what a
lower-precision state would read; the rule; the operator, its shapes and its
launch counts; and a small Phi-4-mini-flash model steered through the kernels
with and without per-operator recomputation. The compile for a described
v5e sits with the others in ``test_grouped_matmul.py``."""

import model_cases as mc
import numpy as np
import pytest
import test_phi4flash as tp

import mxnet_tpu as mx
from mxnet_tpu.ops import pallas_support as ps
from mxnet_tpu.ops import registry
from mxnet_tpu.ops import selective_scan as ss
from mxnet_tpu.ops.registry import OpMode

V5E_VMEM = 128 << 20
NAMES = ("x", "dt", "A_log", "B", "C", "D", "dt_bias")
# grid steps of 32 rows: 70 rows are three of them, the last padded
PLAN = ss.Plan(32, 128, 32 << 20)


def _inputs(batch, t, channels, states, dtype, seed=0):
    import jax
    import jax.numpy as jnp

    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    a_log = jnp.log(jnp.broadcast_to(
        jnp.arange(1, states + 1, dtype=jnp.float32), (channels, states))) \
        + 0.1 * jax.random.normal(k[2], (channels, states))
    return (jax.random.normal(k[0], (batch, t, channels)).astype(dtype),
            jax.random.normal(k[1], (batch, t, channels)).astype(dtype),
            a_log,
            jax.random.normal(k[3], (batch, t, states)).astype(dtype),
            jax.random.normal(k[4], (batch, t, states)).astype(dtype),
            1.0 + 0.1 * jax.random.normal(k[5], (channels,)),
            jax.random.normal(k[6], (channels,)) - 3.0), \
        jax.random.normal(k[7], (batch, t, channels)).astype(dtype)


def token_loop(x, dt, a_log, b, c, d, dt_bias, state="float32"):
    """The equations a token at a time, float32 but for the state's dtype."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    xf = x.astype(f32)
    delta = jax.nn.softplus(dt.astype(f32) + dt_bias)
    A = -jnp.exp(a_log)

    def step(h, row):
        dl, xt, bt, ct = row
        h = jnp.exp(dl[:, :, None] * A) * h.astype(f32) \
            + (dl * xt)[:, :, None] * bt[:, None, :]
        h = h.astype(state)
        return h, jnp.einsum("bcn,bn->bc", h.astype(f32), ct,
                             precision="highest") + d * xt

    rows = [z.astype(f32).swapaxes(0, 1) for z in (delta, xf, b, c)]
    _, ys = jax.lax.scan(
        step, jnp.zeros((x.shape[0], x.shape[2], a_log.shape[1]), state),
        tuple(rows))
    return ys.swapaxes(0, 1)


def _both(f, args, g):
    import jax

    out, vjp = jax.vjp(f, *args)
    return out, vjp(g.astype(out.dtype))


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_form_is_the_token_loop(chunk):
    import jax.numpy as jnp

    args, g = _inputs(2, 70, 128, 16, jnp.float32)
    want, want_g = _both(token_loop, args, g)
    got, got_g = _both(
        lambda *a: ss.selective_scan_chunked(*a, chunk=chunk), args, g)
    assert _rel(got, want) < 1e-6
    for name, a, b in zip(NAMES, got_g, want_g):
        assert _rel(a, b) < 2e-6, name


@pytest.mark.parametrize("case", ["padded_three_steps", "two_lane_groups",
                                  "eight_states", "one_short_step"])
def test_kernels_match_the_chunked_form(case):
    """Output and every gradient, bfloat16 operands: the two forms do the
    same float32 arithmetic and differ by the order of a few sums (dB and
    dC over the channels, dA over the rows) and a bfloat16 rounding of dx
    and d dt at a tie."""
    import jax.numpy as jnp

    (batch, t, channels, states), plan = {
        "padded_three_steps": ((2, 70, 128, 16), PLAN),
        "two_lane_groups": ((1, 64, 512, 16), ss.Plan(32, 256, 32 << 20)),
        "eight_states": ((1, 40, 256, 8), PLAN),
        "one_short_step": ((1, 24, 128, 16), ss.Plan(32, 128, 32 << 20)),
    }[case]
    args, g = _inputs(batch, t, channels, states, jnp.bfloat16)
    want, want_g = _both(lambda *a: ss.selective_scan_chunked(*a, chunk=16),
                         args, g)
    got, got_g = _both(lambda *a: ss.selective_scan(*a, plan, True), args, g)
    assert got.dtype == jnp.bfloat16 and got.shape == want.shape
    assert _rel(got, want) < 1e-4
    for name, a, b in zip(NAMES, got_g, want_g):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert _rel(a, b) < (8e-3 if name in ("x", "dt") else 1e-5), name


def test_the_state_is_float32_a_bfloat16_state_reads_otherwise():
    """On operands that bfloat16 holds exactly, read in float32 (so that no
    output rounding hides it): the chunked form is the float32 token loop to
    1e-5, and a token loop whose STATE is bfloat16 is a hundred times
    further off. The kernels are the chunked form to 1e-4 on the same kind of
    operands (the test above), so a kernel with a bfloat16 state would fail
    there."""
    import jax.numpy as jnp

    args, _ = _inputs(1, 256, 128, 16, jnp.bfloat16, seed=3)
    args = tuple(a.astype(jnp.float32) for a in args)
    want = token_loop(*args)
    assert _rel(ss.selective_scan_chunked(*args), want) < 1e-5
    assert _rel(token_loop(*args, state="bfloat16"), want) > 1e-3


def test_d_and_the_bias_enter():
    import jax.numpy as jnp

    args, _ = _inputs(1, 32, 128, 16, jnp.float32)
    base = ss.selective_scan_chunked(*args)
    for i in (5, 6):
        other = list(args)
        other[i] = jnp.zeros_like(args[i])
        assert _rel(ss.selective_scan_chunked(*other), base) > 1e-2


# --- the rule --------------------------------------------------------------------
RULE = {
    "the_cell": (("bfloat16", (1, 4096, 5120), 16, "tpu"), (256, 512)),
    "odd_lane_groups": (("bfloat16", (1, 4096, 384), 16, "tpu"), (256, 128)),
    "a_short_row": (("bfloat16", (2, 40, 256), 8, "tpu"), (48, 256)),
    "float32_trunk": (("float32", (1, 4096, 5120), 16, "tpu"), None),
    "lowered_for_the_cpu": (("bfloat16", (1, 4096, 5120), 16, "cpu"), None),
    "channels_128_does_not_divide": (
        ("bfloat16", (1, 4096, 192), 16, "tpu"), None),
    "four_states": (("bfloat16", (1, 4096, 5120), 4, "tpu"), None),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_rule_says_where_the_kernels_engage(monkeypatch, case):
    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: V5E_VMEM)
    asked, want = RULE[case]
    plan = ss.kernel_plan(*asked)
    assert (plan and (plan.time, plan.lanes)) == want
    if plan:
        assert plan.vmem_limit <= V5E_VMEM * 3 // 4


def test_rule_without_a_chip_or_with_a_small_vmem(monkeypatch):
    assert ss.kernel_plan("bfloat16", (1, 4096, 5120), 16, "tpu") is None
    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: 16 << 20)
    assert ss.kernel_plan("bfloat16", (1, 4096, 5120), 16, "tpu") is None


# --- the operator ------------------------------------------------------------------
def test_the_operator_fills_its_shapes_and_takes_the_chunked_form():
    import jax.numpy as jnp

    op = registry.get("SelectiveScan")
    sym = mx.sym.SelectiveScan(
        mx.sym.Variable("x"), mx.sym.Variable("dt"),
        mx.sym.Variable("scan_A_log", shape=(128, 16)), name="scan")
    assert sym.list_arguments() == ["x", "dt", "scan_A_log", "scan_B",
                                    "scan_C", "scan_D", "scan_dt_bias"]
    shapes, out, _ = sym.infer_shape(x=(2, 24, 128))
    assert shapes == [(2, 24, 128), (2, 24, 128), (128, 16), (2, 24, 16),
                      (2, 24, 16), (128,), (128,)] and out == [(2, 24, 128)]
    args, _ = _inputs(2, 24, 128, 16, jnp.float32)
    got = op.fn(list(args), op.parse_params({}),
                OpMode(is_train=True, platform="cpu"))
    assert _rel(got, token_loop(*args)) < 1e-6


def test_launch_counts(monkeypatch):
    import jax
    import jax.numpy as jnp

    op = registry.get("SelectiveScan")
    x = jax.ShapeDtypeStruct((1, 4096, 5120), jnp.bfloat16)
    a = jax.ShapeDtypeStruct((5120, 16), jnp.float32)
    params = op.parse_params({})
    want = {"executor.selective_scan_layers": 1,
            "executor.selective_scan_kernel_layers": 0,
            "executor.selective_scan_state_updates": 4096 * 5120 * 16}
    assert op.launch_counts([x, x, a], [x], params, "tpu") == want
    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: V5E_VMEM)
    assert op.launch_counts([x, x, a], [x], params, "tpu") == dict(
        want, **{"executor.selective_scan_kernel_layers": 1})
    assert op.launch_counts([x, x, a], [x], params, "cpu") == want
    assert set(want) == set(op.launch_instruments)


# --- the model through the kernels, and the executor's counters -------------------
def _steer(monkeypatch):
    """The rule as a process with one TPU would hear it for a program
    lowered for that chip, the kernels at the test's small blocks in the
    interpreter."""
    rule, scan = ss.kernel_plan, ss.selective_scan
    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: V5E_VMEM)
    monkeypatch.setattr(
        ss, "kernel_plan", lambda dtype, shape, states, platform=None:
        rule(dtype, shape, states, "tpu") and PLAN)
    monkeypatch.setattr(
        ss, "selective_scan", lambda *a: scan(*a, True))


@pytest.mark.parametrize("mirror", ["", "1"], ids=["kept", "recomputed"])
def test_train_program_through_the_kernels(monkeypatch, mirror):
    """A bfloat16 model through ``Module``: on the CPU the kernel counter
    stays (the ``jax.numpy`` form); with the rule asked as for one TPU the
    program launches (under ``MXNET_BACKWARD_DO_MIRROR`` the ``custom_vjp``
    sits in ``jax.checkpoint``: nothing traced may be closed over), counts
    one kernel layer a scan, and its outputs and every parameter's step are
    the ``jax.numpy`` form's to bfloat16 rounding."""
    from mxnet_tpu import telemetry as tm

    if mirror:
        monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", mirror)
    # two scans and the unit that reads one: no attention layer to compile
    sym = tp.tiny_sym_gen("bfloat16", layer_ids=[0, 16, 18], layer_kinds=[
        "mamba", "mamba_memory", "gmu"])(tp.T)[0]
    ids, label = tp.seeded_tokens()
    shapes = dict(data=ids.shape, softmax_label=label.shape)
    params = tp.seeded_params(sym, **shapes)
    names = ("selective_scan_layers", "selective_scan_kernel_layers",
             "kept_residual_nodes")

    def step(steered):
        if steered:
            _steer(monkeypatch)
        mod = mx.mod.Module(sym, context=mx.cpu())
        mod.bind(data_shapes=[("data", shapes["data"])],
                 label_shapes=[("softmax_label", shapes["softmax_label"])])
        mod.init_params(arg_params={n: mx.nd.array(a)
                                    for n, a in params.items()},
                        aux_params={})
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        before = tm.snapshot().get("executor", {})
        mod.forward_backward(mx.io.DataBatch(data=[mx.nd.array(ids)],
                                             label=[mx.nd.array(label)]))
        mod.update()
        after = tm.snapshot()["executor"]
        return ([after.get(n, 0) - before.get(n, 0) for n in names],
                mod.get_outputs()[0].asnumpy(),
                {n: a.asnumpy() for n, a in mod.get_params()[0].items()})

    form_counts, form_out, form_params = step(False)
    counts, out, now = step(True)
    assert form_counts[:2] == [2, 0] and counts[:2] == [2, 2]
    # under the switch the kernels' start states are kept: two nodes more
    assert counts[2] - form_counts[2] == (2 if mirror else 0)
    assert mc.rel(out, form_out) < 2e-2
    for n, a in now.items():
        moved = np.abs(form_params[n] - params[n]).max()
        assert np.abs(a - form_params[n]).max() <= 0.05 * moved + 1e-6, n
