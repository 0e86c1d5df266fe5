"""``GatedDeltaRule`` with a gate a KEY CHANNEL (``g`` of (B, Hv, T, Dk):
Kimi Delta Attention's decay) against the recurrence a token at a time, and
beside the gate a head it shares ``gated_delta.py`` with.

Tolerances, and why: in float32 both sides compute the same function and
differ by the order of their sums (chunks, sub-chunks of 8 and columns
against tokens) and by ``exp`` of a difference of cumulative sums against a
product of ``exp``s: 1e-6 measured, held to 2e-5, which a bfloat16
computation misses by three orders. In bfloat16 the operands of every
product are rounded to 8 bits, the decayed ones too: held to 4e-2 of the
largest entry against the float32 recurrence on the same rounded inputs.
"""

import functools

import numpy as np
import pytest
from model_cases import rel

import mxnet_tpu as mx
from mxnet_tpu.ops import gated_delta as gd
from mxnet_tpu.ops import gated_delta_kernels as gk
from mxnet_tpu.ops import pallas_support as ps
from mxnet_tpu.ops import registry

V5E_VMEM = 128 << 20
NAMES = ("query", "key", "value", "g", "beta")


def recurrence(q, k, v, g, beta):
    """o (B, Hv, T, Dv) a token at a time, float32: q, k (B, Hk, T, Dk)
    already normalised and scaled, g (B, Hv, T) or (B, Hv, T, Dk)."""
    import jax
    import jax.numpy as jnp

    group = v.shape[1] // q.shape[1]
    q, k = (jnp.repeat(x.astype(jnp.float32), group, 1) for x in (q, k))
    if g.ndim == 3:
        g = jnp.broadcast_to(g[..., None], q.shape)

    def token(s, x):
        q, k, v, g, beta = x
        s = s * jnp.exp(g)[..., None]
        read = jnp.einsum("bhkv,bhk->bhv", s, k)
        s = s + jnp.einsum("bhk,bhv->bhkv", k, (v - read) * beta[..., None])
        return s, jnp.einsum("bhkv,bhk->bhv", s, q)

    xs = tuple(jnp.moveaxis(x.astype(jnp.float32), 2, 0)
               for x in (q, k, v, g, beta))
    state = jnp.zeros(v.shape[:2] + (q.shape[3], v.shape[3]), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(token, state, xs)[1], 0, 2)


def inputs(T, dtype="float32", B=2, Hk=3, group=1, D=32, seed=0, low=0.001,
           high=1.6, channel=True):
    """Unit keys, scaled unit queries, decays log-uniform over [low, high] a
    token (the configuration's initialisation gives 0.001 to 1.6)."""
    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    q, k = (rs.randn(B, Hk, T, D) for _ in range(2))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(D)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rs.randn(B, Hk * group, T, D)
    shape = (B, Hk * group, T) + ((D,) if channel else ())
    g = -np.exp(rs.uniform(np.log(low), np.log(high), shape))
    beta = rs.uniform(0, 1, (B, Hk * group, T))
    return tuple(jnp.asarray(x, dtype) for x in (q, k, v)) + (
        jnp.asarray(g, jnp.float32), jnp.asarray(beta, jnp.float32))


def both_ways(args, chunk=64):
    """(outputs, gradients) of the chunked form and of the recurrence under
    one random cotangent."""
    import jax
    import jax.numpy as jnp

    co = jnp.asarray(np.random.RandomState(9).randn(*args[2].shape),
                     jnp.float32)

    def chunked(*a):
        o = gd.chunk_gated_delta_rule(*a, chunk=chunk).astype(jnp.float32)
        return jnp.sum(o * co), o

    def by_token(*a):
        o = recurrence(*a)
        return jnp.sum(o * co), o

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(chunked, argnums=range(5), has_aux=True)(
            *args)
        want = jax.value_and_grad(by_token, argnums=range(5), has_aux=True)(
            *args)
    return (got[0][1], got[1]), (want[0][1], want[1])


@pytest.mark.parametrize("T", [128, 200, 40])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 4e-2)])
def test_chunks_against_the_recurrence_outputs_and_five_gradients(T, dtype,
                                                                  tol):
    """T a multiple of the chunk, no multiple of it (padded: alpha 1, beta
    0) and shorter than one chunk."""
    (out, grads), (want_out, want) = both_ways(inputs(T, dtype))
    assert out.shape == want_out.shape
    assert rel(out, want_out) < tol
    for name, a, b in zip(NAMES, grads, want):
        assert a.shape == b.shape and rel(a, b) < tol, name


def test_float32_tolerance_fails_a_bfloat16_computation():
    (out, _), (want, _) = both_ways(inputs(128, "bfloat16"))
    assert rel(out, want) > 2e-4


@pytest.mark.parametrize("chunk", [16, 32, 128])
def test_other_chunks(chunk):
    """Two sub-chunks a chunk, four, sixteen (and one: T 40 above)."""
    (out, grads), (want_out, want) = both_ways(inputs(256), chunk=chunk)
    assert rel(out, want_out) < 2e-5
    for name, a, b in zip(NAMES, grads, want):
        assert rel(a, b) < 2e-5, name


def test_value_heads_in_groups_over_a_key_head():
    """Hv = 2 Hk: each value head has its own decays over its key head's
    queries and keys, whose gradients come back summed over the group."""
    (out, grads), (want_out, want) = both_ways(inputs(96, group=2, Hk=2))
    assert rel(out, want_out) < 2e-5
    for name, a, b in zip(NAMES, grads, want):
        assert a.shape == b.shape and rel(a, b) < 2e-5, name


@pytest.mark.parametrize("per_token", [1.6, 50.0])
def test_decays_as_strong_as_a_whole_chunk_can_hold(per_token):
    """``g`` = -1.6 a token in every channel is the strongest the
    configuration's initialisation gives (A = 16, dt = 0.1): e^-102 over a
    chunk of 64, where ``exp(-c)`` is e^102 and overflows float32; -50 a
    token is e^-3200. Mixed with channels that hardly fade, forward and
    backward stay finite and follow the recurrence: no ``e`` is ever raised
    to a positive power."""
    import jax.numpy as jnp

    q, k, v, g, beta = inputs(192, low=0.001, high=0.002)
    strong = np.arange(g.shape[-1]) % 2 == 0
    g = jnp.where(strong, -per_token, g)
    (out, grads), (want_out, want) = both_ways((q, k, v, g, beta))
    for x in (out,) + tuple(grads):
        assert np.isfinite(np.asarray(x)).all()
    assert rel(out, want_out) < 2e-5
    for name, a, b in zip(NAMES, grads, want):
        assert rel(a, b) < 2e-5, name


def test_a_gate_a_channel_with_equal_channels_is_the_gate_a_head():
    """Rank 4 with all channels equal gives the rank-3 form's outputs and
    gradients (``g``'s summed over its channels)."""
    import jax
    import jax.numpy as jnp

    q, k, v, g, beta = inputs(200, channel=False)
    wide = jnp.broadcast_to(g[..., None], g.shape + (q.shape[-1],))
    (out, grads), _ = both_ways((q, k, v, g, beta))
    (out4, grads4), _ = both_ways((q, k, v, wide, beta))
    assert rel(out4, out) < 2e-5
    for name, a, b in zip(NAMES, grads4, grads):
        if name == "g":
            a = a.sum(-1)
        assert rel(a, b) < 2e-5, name
    # and the rank-3 form is what it was: against the recurrence
    with jax.default_matmul_precision("highest"):
        assert rel(out, recurrence(q, k, v, g, beta)) < 2e-5


def _intermediates(jaxpr):
    """Every value a jaxpr (and the jaxprs inside it) computes."""
    import jax

    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _intermediates(sub)


def test_nothing_chunk_by_chunk_by_keys_is_ever_held():
    """Forward and backward of a whole row: no value has more than four
    times an operand's elements (a (C, C, Dk) array a chunk would have
    64), and none has two token axes beside a channel axis."""
    import jax
    import jax.numpy as jnp

    args = inputs(256, B=1, Hk=2, D=128)

    def loss(*a):
        return jnp.sum(gd.chunk_gated_delta_rule(*a, chunk=64))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=range(5)))(*args)
    sizes = [int(np.prod(a.shape)) for a in _intermediates(jaxpr.jaxpr)
             if hasattr(a, "shape")]
    assert len(sizes) > 100
    assert max(sizes) <= 4 * args[0].size


@functools.lru_cache(maxsize=None)
def _kernels_and_form():
    """(outputs and the five gradients through the kernels, the same of
    the ``jax.numpy`` form) under one cotangent: bfloat16, 200 tokens, the
    rule's own block (T padded to one grid step of 16 chunks)."""
    import jax
    import jax.numpy as jnp

    args = inputs(200, "bfloat16", B=1, Hk=1, D=128)
    head = jnp.asarray(np.random.RandomState(4).randn(*args[2].shape),
                       jnp.bfloat16)

    def both(**kw):
        out, vjp = jax.vjp(functools.partial(
            gd.chunk_gated_delta_rule, chunk=64, **kw), *args)
        return [np.asarray(x, np.float32) for x in (out,) + vjp(head)]

    return both(kernels=gk.Plan(gk._BLOCK, 64 << 20), interpret=True), both()


@pytest.mark.parametrize("tensor", range(6), ids=("output",) + NAMES)
def test_the_kernels_take_a_gate_a_channel(tensor):
    """A plan with ``g`` of rank 4 runs (the kernels in Pallas's
    interpreter) and matches the ``jax.numpy`` form, outputs and the five
    gradients. Both round the decayed operands of a Gram product, ``W`` and
    the gradients to bfloat16 (one part in 256) after sums in another
    order: 8e-3, what ``test_gated_delta_kernels.py`` holds the scalar gate
    to; the keys' gradient is the sum of three kernels' shares, each
    rounded, where the form rounds one sum: 1.2e-2. In float32, at 2e-5:
    ``test_gated_delta_channel_kernels.py``."""
    got, want = (x[tensor] for x in _kernels_and_form())
    assert got.shape == want.shape and np.isfinite(got).all()
    assert rel(got, want) < (1.2e-2 if tensor == 2 else 8e-3)


# --- the rule and the counts -------------------------------------------------

QWEN = ((1, 16, 8192, 128), (1, 32, 8192, 128), 64)     # Qwen3-Next's cell
KIMI = ((1, 32, 4096, 128), (1, 32, 4096, 128), 64)     # Kimi-Linear's


def test_the_rule_gives_a_gate_a_channel_a_plan(monkeypatch):
    """On a described v5e: a plan for Kimi-Linear's and Qwen3-Next's shapes
    with either gate, at the same block. None on the CPU, for a float32
    trunk, for a head width 128 does not divide, and where the channel
    form's blocks (``c`` and ``dc`` (chunks, C, Dk) float32, a Gram matrix
    and its cotangent) pass half the VMEM while the scalar form's do not."""
    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: V5E_VMEM)
    for shapes in (QWEN, KIMI):
        for channel in (False, True):
            plan = gd.kernel_plan("bfloat16", *shapes, "tpu", channel)
            assert plan is not None and plan.chunks == gk._BLOCK
            assert plan.vmem_limit <= V5E_VMEM * 3 // 4
    assert gd.kernel_plan("bfloat16", *QWEN, "tpu") is not None
    assert gd.kernel_plan("bfloat16", *KIMI, "cpu", True) is None
    assert gd.kernel_plan("float32", *KIMI, "tpu", True) is None
    narrow = ((1, 32, 4096, 64), (1, 32, 4096, 128), 64)
    assert gd.kernel_plan("bfloat16", *narrow, "tpu", True) is None
    small = ("tpu", 16 << 20, "bfloat16", 128, 128, 1, 64, 4096)
    assert gk.plan(*small) is not None
    assert gk.plan(*small, True) is None
    assert gk.plan("tpu", None, "bfloat16", 128, 128, 1, 64, 4096,
                   True) is None


@pytest.mark.parametrize("channel,platform,want", [
    (True, "tpu", (1, 1)), (True, "cpu", (1, 0)),
    (False, "tpu", (0, 1)), (False, "cpu", (0, 0))])
def test_launch_counts(monkeypatch, channel, platform, want):
    """What one launch of a train program counts for a node: the
    channel-gated counter follows ``g``'s rank, and the kernels' counters
    the rule, which gives either gate a plan on a v5e."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: V5E_VMEM)
    op = registry.get("GatedDeltaRule")
    assert "executor.linear_attention_channel_gated_layers" \
        in op.launch_instruments
    k_shape, v_shape, chunk = KIMI
    g_shape = v_shape[:3] + ((k_shape[3],) if channel else ())
    ins = [jax.ShapeDtypeStruct(s, d) for s, d in (
        (k_shape, jnp.bfloat16), (k_shape, jnp.bfloat16),
        (v_shape, jnp.bfloat16), (g_shape, jnp.float32),
        (v_shape[:3], jnp.float32))]
    counts = op.launch_counts(ins, [ins[2]], {"chunk": chunk}, platform)
    assert counts == {
        "executor.linear_attention_layers": 1,
        "executor.linear_attention_chunks": 4096 // 64,
        "executor.linear_attention_kernel_layers": want[1],
        "executor.linear_attention_scan_kernel_layers": want[1],
        "executor.linear_attention_channel_gated_layers": want[0]}


# --- the operator --------------------------------------------------------------

@pytest.mark.parametrize("mirror", ["0", "1"])
def test_the_symbol_forward_and_backward(monkeypatch, mirror):
    """``mx.sym.GatedDeltaRule`` with ``g`` of rank 4 through a bound
    executor, with and without per-operator recomputation: the op's own
    length normalisation and query scale, then the recurrence; and the
    launch counts the new counter."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import telemetry as tm

    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", mirror)
    rs = np.random.RandomState(3)
    B, H, T, D = 2, 2, 80, 16
    raw = {"query": rs.randn(B, H, T, D), "key": rs.randn(B, H, T, D),
           "value": rs.randn(B, H, T, D),
           "g": -np.exp(rs.uniform(np.log(0.001), np.log(1.6),
                                   (B, H, T, D))),
           "beta": rs.uniform(0, 1, (B, H, T))}
    raw = {n: a.astype(np.float32) for n, a in raw.items()}
    sym = mx.sym.GatedDeltaRule(*[mx.sym.Variable(n) for n in NAMES],
                                name="delta")
    exe = sym.bind(mx.cpu(), {n: mx.nd.array(a) for n, a in raw.items()},
                   args_grad={n: mx.nd.zeros(a.shape)
                              for n, a in raw.items()})
    before = tm.snapshot().get("executor", {})
    out = exe.forward(is_train=True)[0].asnumpy()
    co = rs.randn(*out.shape).astype(np.float32)
    exe.backward([mx.nd.array(co)])
    grads = {n: exe.grad_dict[n].asnumpy() for n in NAMES}
    after = tm.snapshot()["executor"]
    assert after["linear_attention_channel_gated_layers"] - before.get(
        "linear_attention_channel_gated_layers", 0) == 1

    def plain(q, k, v, g, beta):
        q, k = (x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
                for x in (q, k))
        o = recurrence(q * D ** -0.5, k, v, g, beta)
        return jnp.sum(o * co), o

    with jax.default_matmul_precision("highest"):
        (_, want_out), want = jax.value_and_grad(
            plain, argnums=range(5), has_aux=True)(
                *(jnp.asarray(raw[n]) for n in NAMES))
    assert rel(out, want_out) < 2e-5
    for n, b in zip(NAMES, want):
        assert rel(grads[n], b) < 2e-5, n
