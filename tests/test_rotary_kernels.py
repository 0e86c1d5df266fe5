"""``RotaryEmbedding`` (``ops/defs_transformer._rotary``) under its own
derivative and in its Pallas kernel (``ops/rotary_kernels.py``), against the
operator as it stood before either: ``oracle`` below is that ``jax.numpy``
form, differentiated by jax. Forward of the three forms to the bit; the
derivative (the rotation by the negated angle) against ``jax.vjp`` of the
oracle; the kernel in Pallas's interpreter against the form in both
directions; the rule; the three counts of a bound train program. A schedule
of frequencies other than the geometric one and an amplitude (``scaling=
"yarn"``) are cases of the same tests: the oracle makes its tables from the
published formula itself. The compile for a described v5e sits with the
others in ``test_grouped_matmul.py``."""

import hashlib
import math

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry as tm
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import defs_transformer as dt
from mxnet_tpu.ops import pallas_support as ps
from mxnet_tpu.ops import registry
from mxnet_tpu.ops import rotary_kernels as rk

V5E_VMEM = 128 << 20
CPU = registry.OpMode(is_train=True, platform="cpu")
# row blocks of 32 positions in tiles of 16, two heads a grid step: T = 64 is
# two row blocks of two tiles, so the loop inside a block and the tables'
# second block are both walked
PLAN = rk.Plan(2, 32, 16, 32 << 20)
# Mellum2's full layers (its ``rope_parameters.full_attention``), and a
# schedule small enough that the ramp rises inside a head of 64 over 40
# positions, its amplitude the default ``0.1 ln(factor) + 1``
PUBLISHED_YARN = dict(base=500000.0, scaling="yarn", factor=16.0,
                      original_max_position=8192, beta_fast=32.0,
                      beta_slow=1.0, attention_factor=1.2772588722239782)
SMALL_YARN = dict(base=100.0, scaling="yarn", factor=4.0,
                  original_max_position=16, beta_fast=2.0, beta_slow=0.25)
FORMS = {form: registry.get("RotaryEmbedding").parse_params(raw)
         for form, raw in {
    "rotate_half": dict(base=10000.0, rotary_dim=0, interleaved=False),
    "interleaved": dict(base=1e6, rotary_dim=0, interleaved=True),
    "partial": dict(base=1e7, rotary_dim=16, interleaved=False),
    "partial_interleaved": dict(base=1e7, rotary_dim=16, interleaved=True),
    "rotary_dim_names_the_whole_head": dict(base=1e6, rotary_dim=64,
                                            interleaved=False),
    "yarn": PUBLISHED_YARN,
    "yarn_interleaved": dict(SMALL_YARN, interleaved=True),
    "yarn_partial": dict(SMALL_YARN, rotary_dim=32),
}.items()}


def yarn_inv_freq(half, base, factor, original_max_position, beta_fast,
                  beta_slow):
    """``transformers``' ``_compute_yarn_parameters`` (``truncate`` true)
    in float64, a pair at a time."""
    d = 2 * half

    def pair_of(rotations):
        return d * math.log(original_max_position / (
            rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(half):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        plain = base ** (-i / half)
        out.append((1.0 - ramp) * plain + ramp * plain / factor)
    return np.array(out, np.float64), (low, high)


def oracle_tables(t, half, params):
    """cos and sin (t, half) float32 in the operator's stated precision,
    from the formula and not from the operator."""
    if params["scaling"]:
        inv_freq, _ = yarn_inv_freq(
            half, params["base"], params["factor"],
            params["original_max_position"], params["beta_fast"],
            params["beta_slow"])
        a = params["attention_factor"] \
            or 0.1 * math.log(params["factor"]) + 1.0
    else:
        inv_freq = params["base"] ** (-np.arange(half, dtype=np.float64)
                                      / half)
        a = 1.0
    angle = (np.arange(t, dtype=np.float32)[:, None]
             * inv_freq.astype(np.float32)[None, :]).astype(np.float64)
    return ((a * np.cos(angle)).astype(np.float32),
            (a * np.sin(angle)).astype(np.float32))


def oracle(x, params):
    """The operator as it was before it had a derivative or a kernel."""
    import jax.numpy as jnp

    if params["rotary_dim"] and params["rotary_dim"] != x.shape[-1]:
        r = params["rotary_dim"]
        turned = oracle(x[..., :r], dict(params, rotary_dim=0))
        return jnp.concatenate([turned, x[..., r:]], axis=-1)
    t, d = x.shape[-2:]
    half = d // 2
    cos, sin = oracle_tables(t, half, params)
    xf = x.astype(jnp.float32)
    if params["interleaved"]:
        pairs = xf.reshape(x.shape[:-1] + (half, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        axis=-1).reshape(x.shape)
    else:
        x1, x2 = xf[..., :half], xf[..., half:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
    return out.astype(x.dtype)


def _inputs(shape, dtype, seed=0):
    import jax

    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.random.normal(k[0], shape, dtype),
            jax.random.normal(k[1], shape, dtype))


def _with_pull_back(f, x, g):
    import jax

    y, vjp = jax.vjp(f, x)
    return y, vjp(g)[0]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_forward_is_the_oracles_bits_and_backward_its_pull_back(form, dtype):
    """Op by op (no ``jax.jit``: XLA:CPU contracts a product into the add
    that follows where it fuses them, on either side as it likes) forward
    and the derivative are the oracle's and autodiff's to the bit: the
    float32 products are the same and a float32 add commutes. Under
    ``jax.jit`` forward stays the same program, bit for bit, and the
    pull-back is within the one float32 rounding a contraction moves.
    The derivative is the operator's own where the whole head turns; a
    partial ``rotary_dim`` traces what it traced, equation for equation."""
    import jax

    params = FORMS[form]
    x, g = _inputs((2, 3, 40, 64), dtype)

    def op(x):
        return dt._rotary([x], params, CPU)

    y, dx = _with_pull_back(op, x, g)
    y0, dx0 = _with_pull_back(lambda x: oracle(x, params), x, g)
    assert y.dtype == x.dtype and dx.dtype == x.dtype
    assert np.array_equal(np.asarray(y), np.asarray(y0))
    assert np.array_equal(np.asarray(dx), np.asarray(dx0))
    whole = params["rotary_dim"] in (0, x.shape[-1])
    assert ("custom_vjp" in str(jax.make_jaxpr(op)(x))) == whole
    if not whole:   # a partial rotary_dim is the program it was
        assert str(jax.make_jaxpr(op)(x)) == str(jax.make_jaxpr(
            lambda x: oracle(x, params))(x))
    jy, jdx = jax.jit(lambda x, g: _with_pull_back(op, x, g))(x, g)
    jy0, jdx0 = jax.jit(lambda x, g: _with_pull_back(
        lambda x: oracle(x, params), x, g))(x, g)
    assert np.array_equal(np.asarray(jy), np.asarray(jy0))
    ulp = 2.0 ** (-8 if dtype == "bfloat16" else -23)
    scale = 2.0 * float(np.abs(np.asarray(g, np.float32)).max())
    assert np.abs(np.asarray(jdx, np.float32)
                  - np.asarray(jdx0, np.float32)).max() <= ulp * scale


@pytest.mark.parametrize("form", ["rotate_half", "partial_interleaved",
                                  "yarn"])
def test_the_derivative_is_linear_and_can_be_taken_again(form):
    """The pull-back of the pull-back is the operator: the rotation by the
    angle negated twice."""
    import jax

    params = FORMS[form]
    x, g = _inputs((1, 2, 24, 64), "float32", seed=2)

    def pulled(g):
        return jax.vjp(lambda x: dt._rotary([x], params, CPU), x)[1](g)[0]

    again = jax.vjp(pulled, g)[1](x)[0]
    assert np.array_equal(np.asarray(again),
                          np.asarray(dt._rotary([x], params, CPU)))


# sha256 of the printed jaxpr of output and pull-back at the commit before
# the operator had a schedule (PR 61's tree, jax 0.9.0)
_BEFORE_THE_SCHEDULE = {
    ("rotate_half", "bfloat16"):
        "a708344497409c60dfc34ffe8959acf117032527b1436eb8cd6c9160cc081e94",
    ("rotate_half", "float32"):
        "3a888e8cebe52ff2c8053e5651c98057b34bedb635fb5552fc364f3afc6b34c2",
    ("interleaved", "bfloat16"):
        "4acb4939678ed35699205d9e1a5121e9cbe2d2ad0c3b72fbf424880175ad3a30",
    ("interleaved", "float32"):
        "c3191d2e22a774bf760a40d2f6167138ae1320075757a1d84272d9a819361fbd",
    ("partial", "bfloat16"):
        "25baad7388e283ee0220e45ec1f6ab11a571238ae11a759231ba90e32e75d6f5",
    ("partial", "float32"):
        "95f32baa9f159be15b6b8583b80dc23c160cc19960331d42954a55e0f3b7835e",
}


@pytest.mark.parametrize("form,dtype", sorted(_BEFORE_THE_SCHEDULE))
def test_the_defaults_trace_the_jaxpr_they_traced(form, dtype):
    """With ``scaling`` "" a node traces, forward and backward, the jaxpr
    the parent's operator traced, to the digest; a scaled node traces the
    same equations over other constants."""
    import jax
    import jax.numpy as jnp

    if jax.__version__ != "0.9.0":
        pytest.skip("the digests are of jax 0.9.0's printed jaxprs")
    x = jax.ShapeDtypeStruct((2, 3, 40, 64), jnp.dtype(dtype))

    def text(params):
        return str(jax.make_jaxpr(lambda x, g: _with_pull_back(
            lambda x: dt._rotary([x], params, CPU), x, g))(x, x))

    assert hashlib.sha256(text(FORMS[form]).encode()).hexdigest() \
        == _BEFORE_THE_SCHEDULE[form, dtype]
    assert text(dict(FORMS[form], **SMALL_YARN)) == text(FORMS[form])


@pytest.mark.parametrize("case,t,half", [("published", 16384, 64),
                                         ("small", 40, 32)])
def test_yarn_tables_are_the_formula_in_float64(case, t, half):
    """The frequencies against the formula a pair at a time (the published
    numbers give the ramp over pairs 18 to 35 of 64: the fast pairs as
    trained, the slow ones stretched 16 times, a blend between), and the
    tables bit for bit in the stated precision, the amplitude in them."""
    raw = PUBLISHED_YARN if case == "published" else SMALL_YARN
    params = registry.get("RotaryEmbedding").parse_params(raw)
    schedule = dt._schedule(params)
    want, (low, high) = yarn_inv_freq(
        half, *(raw[k] for k in ("base", "factor", "original_max_position",
                                 "beta_fast", "beta_slow")))
    got = schedule.inv_freq(half)
    assert got.dtype == np.float64
    assert np.allclose(got, want, rtol=1e-15, atol=0.0)
    plain = raw["base"] ** (-np.arange(half) / half)
    assert 0 < low < high < half - 1
    assert np.array_equal(got[:low + 1], plain[:low + 1])
    assert np.allclose(got[high:], plain[high:] / raw["factor"], rtol=1e-15)
    ratio = got[low + 1:high] / plain[low + 1:high]
    assert np.all(np.diff(ratio) < 0) and ratio[0] < 1 \
        and ratio[-1] > 1 / raw["factor"]
    if case == "published":
        assert (low, high) == (18, 35)
        assert schedule.amplitude() == 1.2772588722239782 \
            == 0.1 * math.log(16) + 1
    else:
        assert schedule.amplitude() == 0.1 * math.log(4.0) + 1.0
    cos, sin = dt._rotary_tables(t, half, schedule)
    want_cos, want_sin = oracle_tables(t, half, params)
    assert np.array_equal(cos, want_cos) and np.array_equal(sin, want_sin)
    # the amplitude, and that it is no rotation: a pair's length grows by it
    assert np.allclose(np.hypot(cos, sin), schedule.amplitude(), rtol=1e-6)
    assert np.array_equal(cos[0], np.full(half, np.float32(
        schedule.amplitude())))


@pytest.mark.parametrize("bad", [
    dict(scaling="linear"), dict(scaling="yarn"),
    dict(SMALL_YARN, factor=0.5), dict(SMALL_YARN, beta_slow=4.0),
    dict(SMALL_YARN, attention_factor=-1.0)])
def test_a_schedule_the_operator_does_not_define_is_refused(bad):
    x, _ = _inputs((1, 1, 8, 64), "float32")
    params = registry.get("RotaryEmbedding").parse_params(bad)
    with pytest.raises(MXNetError, match="RotaryEmbedding"):
        dt._rotary([x], params, CPU)


@pytest.mark.parametrize("rotary_dim", [3, 65, -2])
def test_a_rotary_dim_that_is_no_part_of_the_head_is_refused(rotary_dim):
    x, _ = _inputs((1, 1, 8, 64), "float32")
    with pytest.raises(MXNetError, match="rotary_dim"):
        dt._rotary([x], dict(FORMS["partial"], rotary_dim=rotary_dim), CPU)


# --- the kernel ----------------------------------------------------------------
# (leading dims, T, plan): several heads and several row blocks; an odd
# number of flattened heads taken one a grid step; no batch axis
SHAPES = {"batch_2_heads_4": ((2, 4), 64, PLAN),
          "three_heads_a_grid_step_each": ((1, 3), 64,
                                           PLAN._replace(heads=1)),
          "no_batch_axis_one_block": ((4,), 32, PLAN)}


_TABLES = dt._rotary_tables


def _short_tables(t, half, schedule, lanes=False):
    """The operator's tables kept to bfloat16's 8 bits: a float32 product of
    such a factor with a bfloat16 value is exact, so whether XLA:CPU
    contracts it into the add that follows cannot show in a bit."""
    import jax.numpy as jnp

    cos, sin = (np.asarray(jnp.asarray(a).astype(jnp.bfloat16), np.float32)
                for a in _TABLES(t, half, schedule))
    return rk.lane_tables(cos, sin) if lanes else (cos, sin)


def _through_the_kernel(monkeypatch, plan=PLAN):
    """The operator as a process with one TPU would trace it, the kernel at
    the test's small blocks in Pallas's interpreter."""
    turn = rk.turn
    monkeypatch.setattr(rk, "kernel_plan", lambda *a: plan)
    monkeypatch.setattr(
        rk, "turn", lambda x, c, s, back, plan: turn(x, c, s, back, plan,
                                                     True))


@pytest.mark.parametrize("form", ["rotate_half", "yarn"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_kernel_is_the_form_in_both_directions(monkeypatch, shape, form):
    """Output and pull-back of the operator through the kernel against the
    ``jax.numpy`` form: every bit where the products are exact
    (``_short_tables``), and at the operator's own tables to the one
    bfloat16 rounding a contraction moves, at a handful of places. (On the
    chip the two agree in every bit at the cells' shapes: PERF.md section 6,
    PR 59.)"""
    lead, t, plan = SHAPES[shape]
    params = FORMS[form]
    x, g = _inputs(lead + (t, 128), "bfloat16", seed=1)

    def both():
        return _with_pull_back(lambda x: dt._rotary([x], params, CPU), x, g)

    with monkeypatch.context() as m:
        want = both()
        _through_the_kernel(m, plan)
        got = both()
    for a, b in zip(got, want):
        off = np.asarray(a) != np.asarray(b)
        assert off.mean() < 1e-3
        assert np.abs(np.asarray(a, np.float32)
                      - np.asarray(b, np.float32)).max() <= 2.0 ** -6
    if form == "yarn":      # the scaled tables reached both: a pair grew
        xf, yf = (np.asarray(a, np.float32) for a in (x, want[0]))
        grew = np.hypot(yf[..., :64], yf[..., 64:]) / np.maximum(
            np.hypot(xf[..., :64], xf[..., 64:]), 1e-3)
        assert abs(np.median(grew) - 1.2772588722239782) < 2.0 ** -6
    monkeypatch.setattr(dt, "_rotary_tables", _short_tables)
    want = both()
    _through_the_kernel(monkeypatch, plan)
    got = both()
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_position_zero_is_the_identity_and_a_pairs_length_is_kept():
    """The kernel alone: the angle at t = 0 is 0 in every pair, and a
    rotation keeps every pair's length, to the one rounding."""
    x, _ = _inputs((2, 4, 64, 128), "bfloat16", seed=5)
    y = np.asarray(rk.turn(x, *dt._rotary_tables(64, 64, dt._Schedule(1e6), True), False,
                           PLAN, True), np.float32)
    xf = np.asarray(x, np.float32)
    assert np.array_equal(y[..., 0, :], xf[..., 0, :])
    assert not np.array_equal(y[..., 1:, :], xf[..., 1:, :])
    pairs = np.hypot(y[..., :64], y[..., 64:]) \
        / np.maximum(np.hypot(xf[..., :64], xf[..., 64:]), 1e-3)
    assert np.abs(pairs - 1.0).max() < 2.0 ** -6


# --- the rule ------------------------------------------------------------------
RULE_CASES = {
    # dtype, x_shape, rotary_dim, interleaved, platform
    "the_sdar_cells_queries": (
        ("bfloat16", (2, 32, 8192, 128), 0, False, "tpu"), True),
    "the_keye_cells_queries": (
        ("bfloat16", (1, 32, 16384, 128), 0, False, "tpu"), True),
    "rotary_dim_names_the_whole_head": (
        ("bfloat16", (2, 32, 8192, 128), 128, False, "tpu"), True),
    "exactly_half_the_vmem": (
        ("bfloat16", (1, 16, 16384, 128), 0, False, "tpu"), True),
    "no_batch_axis": (("bfloat16", (64, 8192, 128), 0, False, "tpu"), True),
    # 16 and 32 MiB: arrays XLA holds in the v5e's 128 MiB of VMEM
    "the_sdar_cells_keys_are_under_the_size": (
        ("bfloat16", (2, 4, 8192, 128), 0, False, "tpu"), False),
    "the_ouro_cells_queries_are_under_the_size": (
        ("bfloat16", (1, 16, 4096, 128), 0, False, "tpu"), False),
    "the_trinity_cells_queries_are_under_the_size": (
        ("bfloat16", (1, 32, 4096, 128), 0, False, "tpu"), False),
    "just_under_half_the_vmem": (
        ("bfloat16", (1, 16, 16128, 128), 0, False, "tpu"), False),
    "cpu": (("bfloat16", (2, 32, 8192, 128), 0, False, "cpu"), False),
    "float32_trunk": (("float32", (2, 32, 8192, 128), 0, False, "tpu"),
                      False),
    "part_of_the_head_turns": (
        ("bfloat16", (2, 32, 8192, 128), 64, False, "tpu"), False),
    "interleaved_pairs": (("bfloat16", (2, 32, 8192, 128), 0, True, "tpu"),
                          False),
    "heads_of_64": (("bfloat16", (2, 64, 8192, 64), 0, False, "tpu"), False),
    "heads_of_192": (("bfloat16", (2, 32, 8192, 192), 0, False, "tpu"),
                     False),
    "t_is_not_whole_tiles": (
        ("bfloat16", (2, 32, 8200, 128), 0, False, "tpu"), False),
}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_rule_says_where_the_kernel_engages(monkeypatch, case):
    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: V5E_VMEM)
    args, engages = RULE_CASES[case]
    plan = rk.kernel_plan(*args)
    assert (plan is not None) == engages
    if engages:
        shape = args[1]
        assert int(np.prod(shape)) * 2 >= V5E_VMEM // 2
        assert shape[-2] % plan.rows == 0 and plan.rows % plan.tile == 0
        assert plan.tile % 16 == 0
        assert int(np.prod(shape[:-2])) % plan.heads == 0
        assert plan.vmem_limit <= V5E_VMEM * 3 // 4


@pytest.mark.parametrize("chips,engages", [(1, True), (4, False)])
def test_rule_with_chips_attached(monkeypatch, chips, engages):
    """One attached v5e gives the cells' queries a plan, four give none
    (XLA cannot partition a Mosaic call), and a program lowered for the CPU
    in such a process gets none."""
    import jax

    class Chip:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()] * chips)
    cell = ("bfloat16", (2, 32, 8192, 128), 0, False)
    assert (rk.kernel_plan(*cell) is not None) == engages
    assert (rk.kernel_plan(*cell, "tpu") is not None) == engages
    assert rk.kernel_plan(*cell, "cpu") is None


def test_the_op_asks_the_rule_and_on_the_cpu_hears_none(monkeypatch):
    """On the CPU no ask gives a plan; with a v5e's VMEM and a program
    lowered for the chip the SDAR cell's queries trace one ``custom_vjp``
    around one ``pallas_call`` (``tests/conftest.py`` switches jax's cache
    off, so ``pallas_support._kernel`` traces the kernel in place), and the
    same cell's keys the form."""
    import jax
    import jax.numpy as jnp

    cell = ("bfloat16", (2, 32, 8192, 128), 0, False)
    assert rk.kernel_plan(*cell) is None
    assert rk.kernel_plan(*cell, "tpu") is None
    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: V5E_VMEM)
    params = FORMS["rotate_half"]
    for heads, kernels in ((32, 1), (4, 0)):
        x = jax.ShapeDtypeStruct((2, heads, 8192, 128), jnp.bfloat16)
        for platform in ("tpu", "cpu"):
            text = str(jax.make_jaxpr(lambda x: dt._rotary(
                [x], params, registry.OpMode(is_train=True,
                                             platform=platform)))(x))
            assert text.count("custom_vjp_call") == 1
            assert text.count("pallas_call") == kernels * (platform == "tpu")
            assert ("rotary_turn" in text) == (kernels
                                               and platform == "tpu")


# --- a bound train program and its counts --------------------------------------
def _two_node_graph(**schedule):
    """Queries of 4 heads and keys of 1 from one (B, T, 5 x 128) input,
    rotated and scored against each other under a loss head."""
    data = mx.sym.Variable("data")
    heads = mx.sym.transpose(mx.sym.Reshape(data, shape=(0, 0, 5, 128)),
                             axes=(0, 2, 1, 3))
    schedule = schedule or dict(base=1e6)
    q = mx.sym.RotaryEmbedding(
        mx.sym.slice_axis(heads, axis=1, begin=0, end=4), name="q",
        **schedule)
    k = mx.sym.RotaryEmbedding(
        mx.sym.slice_axis(heads, axis=1, begin=4, end=5), name="k",
        **schedule)
    return mx.sym.MakeLoss(mx.sym.sum(mx.sym.broadcast_mul(q, k)))


@pytest.mark.parametrize("schedule", [{}, PUBLISHED_YARN],
                         ids=["geometric", "yarn"])
@pytest.mark.parametrize("mirror", ["", "1"], ids=["kept", "recomputed"])
def test_a_train_programs_counts_ask_the_rule_the_op_asks(monkeypatch,
                                                          mirror, schedule):
    """On the CPU a launch counts both nodes and no kernel node. With the
    rule asked as for one TPU whose half VMEM the queries (4 heads, 32 KiB)
    reach and the keys do not, the program launches through the interpreted
    kernel (under ``MXNET_BACKWARD_DO_MIRROR`` the ``custom_vjp`` sits in
    ``jax.checkpoint``), counts one kernel node of two, and its output and
    gradient are the form's."""
    if mirror:
        monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", mirror)
    rs = np.random.RandomState(0)
    x = rs.randn(1, 32, 5 * 128).astype(np.float32)

    def launch():
        exe = _two_node_graph(**schedule).simple_bind(
            mx.cpu(), grad_req="write", type_dict={"data": "bfloat16"},
            data=x.shape)
        exe.arg_dict["data"][:] = mx.nd.array(x).astype("bfloat16")
        before = tm.snapshot().get("executor", {})
        exe.forward(is_train=True)
        exe.backward()
        grad = exe.grad_dict["data"].astype("float32").asnumpy()
        after = tm.snapshot()["executor"]
        return (exe.graph.launch_counts,
                [after.get(n, 0) - before.get(n, 0)
                 for n in ("rotary_nodes", "rotary_kernel_nodes",
                           "rotary_scaled_nodes")],
                exe.outputs[0].astype("float32").asnumpy(), grad)

    scaled = 2 * bool(schedule)
    counts, moved, out, grad = launch()
    assert counts == {"executor.rotary_nodes": 2,
                      "executor.rotary_kernel_nodes": 0,
                      "executor.rotary_scaled_nodes": scaled}
    assert moved == [2, 0, scaled]
    rule, turn = rk.kernel_plan, rk.turn
    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: 64 << 10)
    monkeypatch.setattr(rk, "_TILE", 16)
    monkeypatch.setattr(rk, "_ROWS", (16,))
    monkeypatch.setattr(
        rk, "kernel_plan", lambda dtype, shape, rotary_dim, interleaved,
        platform=None: rule(dtype, shape, rotary_dim, interleaved, "tpu"))
    monkeypatch.setattr(
        rk, "turn", lambda x, c, s, back, plan: turn(x, c, s, back, plan,
                                                     True))
    counts, moved, kernel_out, kernel_grad = launch()
    assert counts == {"executor.rotary_nodes": 2,
                      "executor.rotary_kernel_nodes": 1,
                      "executor.rotary_scaled_nodes": scaled}
    assert moved == [2, 1, scaled]
    for a, b in ((kernel_out, out), (kernel_grad, grad)):
        assert np.abs(a - b).max() <= 2.0 ** -7 * np.abs(b).max()


def test_the_counts_without_a_bind():
    """``launch_counts`` over shapes alone, as a program read from the AOT
    store is counted: every node is one, and on the CPU none is a kernel's."""
    import jax
    import jax.numpy as jnp

    op = registry.get("RotaryEmbedding")
    assert op.launch_instruments == ("executor.rotary_nodes",
                                     "executor.rotary_kernel_nodes",
                                     "executor.rotary_scaled_nodes")
    x = jax.ShapeDtypeStruct((2, 32, 8192, 128), jnp.bfloat16)
    for form, scaled in (("rotate_half", 0), ("yarn", 1)):
        assert op.launch_counts([x], [x], FORMS[form], "cpu") == {
            "executor.rotary_nodes": 1, "executor.rotary_kernel_nodes": 0,
            "executor.rotary_scaled_nodes": scaled}


def test_the_tables_are_made_once_and_cannot_be_written():
    """Every node of a program asks for its layer's tables, once a
    direction: the same read-only arrays come back, the kernel's as
    ``lane_tables`` lays them over a head's 128 lanes."""
    plain = dt._schedule(FORMS["interleaved"])
    assert plain == dt._Schedule(1e6) == dt._schedule(dict(
        FORMS["interleaved"], factor=8.0, attention_factor=2.0))
    cos, sin = dt._rotary_tables(48, 64, plain)
    again = dt._rotary_tables(48, 64, dt._Schedule(1e6))
    assert again[0] is cos and again[1] is sin
    # a program's second schedule is another entry and evicts nothing
    scaled = dt._rotary_tables(48, 64, dt._schedule(FORMS["yarn"]))
    assert scaled[0] is not cos and not np.array_equal(scaled[0], cos)
    assert dt._rotary_tables(48, 64, plain)[0] is cos
    assert dt._rotary_tables(48, 64, dt._schedule(FORMS["yarn"]))[0] \
        is scaled[0]
    c, s = dt._rotary_tables(48, 64, plain, True)
    assert np.array_equal(c, np.concatenate([cos, cos], -1))
    assert np.array_equal(s, np.concatenate([-sin, sin], -1))
    for table in (cos, sin, c, s):
        assert table.dtype == np.float32
        with pytest.raises(ValueError):
            table[0, 0] = 0.0
