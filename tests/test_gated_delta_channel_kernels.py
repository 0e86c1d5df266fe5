"""The Pallas kernels of the gated delta rule with a gate a KEY CHANNEL
(``ops/gated_delta_kernels.py``: the two Gram matrices' kernels, and the
chunk-local algebra's and the scan's taking ``c`` a channel and their Gram
matrix as given) in Pallas's interpreter on the CPU, against the ``jax.numpy``
form they stand in for (``gated_delta._channel_grams``, ``_within_chunks``,
``_chunk_step`` under ``lax.scan``). Head width 128, in float32 two value
heads over a key head (each forms its own Gram matrices from the one key,
where the form repeats the key), chunks of 64, grid steps of two chunks, so that 256 tokens
are two steps (bfloat16 operands, and a T padded to whole steps of the rule's
own block, are ``test_gated_delta_channel.py``'s
``test_the_kernels_take_a_gate_a_channel``).
Their compile for a described v5e sits with the other compile tests in
``test_grouped_matmul.py`` (one file, one libtpu).

Tolerances, and why: ``test_gated_delta_kernels.py``'s. With float32 operands
both sides compute the same float32 algebra in another order (the Gram
matrices by levels of 8, 16, 32 rows against sub-chunks of 8; pairs of
chunks): 2e-5 of a tensor's largest element.
"""

import functools

import numpy as np
import pytest
from test_gated_delta_channel import NAMES, inputs, rel

import mxnet_tpu as mx
from mxnet_tpu.ops import gated_delta as gd
from mxnet_tpu.ops import gated_delta_kernels as gk

D, CHUNK = 128, 64
STEP = gk.Plan(2, 64 << 20)      # two chunks a grid step
TENSORS = ["output", "dq", "dk", "dv", "dg", "dbeta"]


def _strong(t):
    """Every other channel at -1.6 a token, the strongest the
    configuration's initialisation gives: e^-102 over a chunk, where
    ``exp(-c)`` overflows float32; the others hardly fade."""
    import jax.numpy as jnp

    q, k, v, g, beta = inputs(t, B=1, Hk=1, group=2, D=D, low=0.001,
                              high=0.002)
    return q, k, v, jnp.where(np.arange(D) % 2 == 0, -1.6, g), beta


T_PADDED = 200      # of STEP's 128 tokens a grid step: a tail of 56 padded

CASES = {
    "float32_two_steps": lambda: inputs(256, B=1, Hk=1, group=2, D=D),
    "float32_strongest_decays": lambda: _strong(256),
    "float32_padded_tail": lambda: inputs(T_PADDED, B=1, Hk=1, group=2, D=D),
}


def _padded(args):
    """The operands as ``chunk_gated_delta_rule`` pads them to STEP's whole
    grid steps: tokens that write nothing (beta 0) and fade nothing (g 0)."""
    import jax.numpy as jnp

    pad = gk.padded(args[0].shape[2], CHUNK, STEP) - args[0].shape[2]
    return tuple(jnp.pad(x, ((0, 0), (0, 0), (0, pad))
                         + ((0, 0),) * (x.ndim - 3)) for x in args)


@functools.lru_cache(maxsize=None)
def _kernels_and_form(case):
    """{tensor: (through the kernels, the ``jax.numpy`` form)}: outputs and
    all five gradients under one random cotangent; the running sum ``c``
    that the Gram matrices' forward kernel takes of ``g`` on its tile
    against ``jnp.cumsum``; and of a case with a padded tail the gate's
    gradient there, from the same operands padded by hand, against 0."""
    import jax
    import jax.numpy as jnp

    args = CASES[case]()
    head = jnp.asarray(np.random.RandomState(4).randn(*args[2].shape),
                       args[2].dtype)

    def both(args, head, **kw):
        out, vjp = jax.vjp(functools.partial(
            gd.chunk_gated_delta_rule, chunk=CHUNK, **kw), *args)
        return (out,) + vjp(head)

    got = both(args, head, kernels=STEP, interpret=True)
    tensors = dict(zip(TENSORS, zip(got, both(args, head))))
    q, k, v, g, beta = _padded(args)
    B, Hk, T, _ = q.shape
    chunks = (B, Hk, T // CHUNK, CHUNK, D)
    g = g.reshape(B, Hk, -1, T // CHUNK, CHUNK, D)
    tensors["c"] = (gk._grams_fwd(
        q.reshape(chunks), k.reshape(chunks), g, chunks=STEP.chunks,
        vmem_limit=STEP.vmem_limit, interpret=True)[0], jnp.cumsum(g, axis=4))
    if T != args[0].shape[2]:
        tail = both(_padded(args), _padded((head,))[0], kernels=STEP,
                    interpret=True)[4][:, :, args[0].shape[2]:]
        tensors["dg_of_the_padded_tail"] = (tail, jnp.zeros_like(tail))
    return {n: (np.asarray(a, np.float32), np.asarray(b, np.float32))
            for n, (a, b) in tensors.items()}


# ``c``: float32 sums in another order; a padded tail's ``dg``: exact zeros
TOLERANCES = {"c": 1e-6, "dg_of_the_padded_tail": 0.0}


@pytest.mark.parametrize("case,tensor", [
    (case, tensor) for case in sorted(CASES)
    for tensor in TENSORS + ["c"] + ["dg_of_the_padded_tail"]
    * case.endswith("padded_tail")])
def test_kernels_match_the_jax_numpy_form(case, tensor):
    """``dg`` a channel is held to what ``test_gated_delta_kernels.py``
    holds the scalar gate's to; no inf and no nan at decays that a positive
    exponent anywhere would overflow. All six kernels stand under one rule
    (``gated_delta_kernels.channel_gated``): the gradients are what its
    backward hands from kernel to kernel and sums on the tile. (A bfloat16
    trunk with a padded tail: ``test_gated_delta_channel.py``.)"""
    got, want = _kernels_and_form(case)[tensor]
    assert got.shape == want.shape
    assert np.isfinite(got).all()
    assert rel(got, want) <= TOLERANCES.get(tensor, 2e-5)


def test_the_six_kernels_and_their_precision():
    """One launch each of the two Gram kernels and the four others. In the
    Gram kernels every ``exp`` is float32 and of a masked or folded
    difference, and every product has operands of the trunk's dtype with a
    float32 result; the inverse's products stay float32 at HIGHEST."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from test_qwen3_next import _eqns

    q, k, v, g, beta = inputs(256, "bfloat16", B=1, Hk=1, D=D)
    f = functools.partial(gd.chunk_gated_delta_rule, chunk=CHUNK,
                          kernels=STEP, interpret=True)
    calls = {e.params["name"]: e for e in _eqns(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(f(*a).astype(jnp.float32)),
        (0, 1, 2, 3, 4)))(q, k, v, g, beta).jaxpr)
        if e.primitive.name == "pallas_call"}
    assert sorted(calls) == [
        "gated_delta_chunks_bwd", "gated_delta_chunks_fwd",
        "gated_delta_grams_bwd", "gated_delta_grams_fwd",
        "gated_delta_scan_bwd", "gated_delta_scan_fwd"]
    for name, call in calls.items():
        products = 0
        for e in _eqns(call.params["jaxpr"]):
            if e.primitive.name == "exp":
                assert e.outvars[0].aval.dtype == jnp.float32, e
            if e.primitive.name != "dot_general":
                continue
            a, b = (x.aval for x in e.invars)
            assert e.outvars[0].aval.dtype == jnp.float32, e
            if "chunks" in name:
                assert a.dtype == b.dtype == jnp.float32, e
                assert e.params["precision"] in (
                    lax.Precision.HIGHEST,
                    (lax.Precision.HIGHEST, lax.Precision.HIGHEST)), e
            else:
                assert a.dtype == b.dtype == jnp.bfloat16, e
            products += 1
        # a chunk's three levels: one product forward, four backward
        if "grams" in name:
            assert products == 2 * 3 * (1 if name.endswith("fwd") else 4)


@pytest.mark.parametrize("gate", ["a_channel", "a_channel_recomputed",
                                  "a_head_recomputed"])
def test_backward_keeps_the_gram_matrices_and_a_state_a_chunk(gate):
    """What backward keeps with the kernels on: the operands, ``U``, ``W``,
    the chunks' inverses and the two Gram matrices (T x chunk a head each,
    pairs of chunks side by side) and one state a chunk; nothing (chunk,
    chunk, Dk). ``recomputed``: under the executor's per-operator
    ``jax.checkpoint`` with its policy (``MXNET_BACKWARD_DO_MIRROR=1``) what
    the one rule of a gate a channel names is what the three rules named,
    ``c`` (the Gram kernel's own result now), both Gram matrices, ``U``,
    ``W``, the inverses and the states, and nothing else is saved; a gate
    a head names what it named, with its four kernels and its ``cumsum``
    outside them."""
    import jax
    import jax.numpy as jnp
    from jax._src.ad_checkpoint import saved_residuals
    from test_qwen3_next import _eqns

    from mxnet_tpu.ops import registry

    t = 256
    q, k, v, g, beta = CASES["float32_two_steps"]()
    if gate.startswith("a_head"):
        g = g[..., 0]
    rule = functools.partial(gd.chunk_gated_delta_rule, chunk=CHUNK,
                             kernels=STEP, interpret=True)
    pairs = (1, 1, 2, t // CHUNK // 2, CHUNK, 2 * CHUNK)
    states = (1, 1, 2, t // CHUNK, D, D)
    wide = (1, 1, 2, t // CHUNK, CHUNK, D)
    if gate == "a_channel":
        _, vjp = jax.vjp(rule, q, k, v, g, beta)
        kept = [x for x in jax.tree.leaves(vjp) if hasattr(x, "shape")]
        assert [x.dtype for x in kept if x.shape == pairs] == [jnp.float32] * 3
        assert [x.shape for x in kept if x.shape[-2:] == (D, D)] == [states]
        assert max(x.size for x in kept if x.shape != states) <= 2 * t * D
        return
    named = sorted(
        aval.shape for aval, why in saved_residuals(jax.checkpoint(
            rule, policy=registry.KeptResiduals()), q, k, v, g, beta)
        if "from the argument" not in why)
    channel = gate.startswith("a_channel")
    # the inverses (and the two matrices), U and W (and c), the states
    assert named == sorted([pairs] * (3 if channel else 1)
                           + [wide] * (3 if channel else 2) + [states])
    eqns = list(_eqns(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(rule(*a)), (0, 1, 2, 3, 4)))(
            q, k, v, g, beta).jaxpr))
    halves = ["chunks", "scan"] + ["grams"] * channel
    assert sorted(e.params["name"] for e in eqns
                  if e.primitive.name == "pallas_call") == sorted(
        f"gated_delta_{half}_{way}" for half in halves
        for way in ("fwd", "bwd"))
    assert sum(e.primitive.name == "cumsum" for e in eqns) == 2 * (not channel)


@pytest.mark.parametrize("mirror", ["0", "1"])
def test_the_symbol_through_the_kernels(monkeypatch, mirror):
    """``mx.sym.GatedDeltaRule`` with ``g`` of rank 4 through a bound
    executor with the rule steered to a plan and the kernels interpreted,
    with and without per-operator recomputation: outputs and gradients are
    the ``jax.numpy`` form's, the launch counts a kernel layer and a
    scan-kernel layer beside the channel-gated one, and under the switch
    the node's checkpoint keeps what the kernels name."""
    from mxnet_tpu import telemetry as tm

    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", mirror)
    rs = np.random.RandomState(3)
    B, H, T = 1, 1, 128
    raw = {"query": rs.randn(B, H, T, D), "key": rs.randn(B, H, T, D),
           "value": rs.randn(B, H, T, D),
           "g": -np.exp(rs.uniform(np.log(0.001), np.log(1.6),
                                   (B, H, T, D))),
           "beta": rs.uniform(0, 1, (B, H, T))}
    raw = {n: a.astype(np.float32) for n, a in raw.items()}
    co = rs.randn(B, H, T, D).astype(np.float32)
    sym = mx.sym.GatedDeltaRule(*[mx.sym.Variable(n) for n in NAMES],
                                name="delta")

    def run(steered):
        with monkeypatch.context() as steer:
            if steered:
                steer.setattr(gd, "kernel_plan", lambda *a: STEP)
                steer.setattr(gd, "chunk_gated_delta_rule", functools.partial(
                    gd.chunk_gated_delta_rule, interpret=True))
            exe = sym.bind(
                mx.cpu(), {n: mx.nd.array(a) for n, a in raw.items()},
                args_grad={n: mx.nd.zeros(a.shape) for n, a in raw.items()})
            before = tm.snapshot().get("executor", {})
            out = exe.forward(is_train=True)[0].asnumpy()
            exe.backward([mx.nd.array(co)])
            grads = [exe.grad_dict[n].asnumpy() for n in NAMES]
            after = tm.snapshot()["executor"]
            counted = tuple(after.get(n, 0) - before.get(n, 0) for n in (
                "linear_attention_kernel_layers",
                "linear_attention_scan_kernel_layers",
                "linear_attention_channel_gated_layers"))
            return counted, exe._kept_residual_nodes, out, grads

    form_counted, form_kept, form_out, form_grads = run(False)
    counted, kept, out, grads = run(True)
    assert (form_counted, counted) == ((0, 0, 1), (1, 1, 1))
    assert (form_kept, kept) == (0, int(mirror == "1"))
    assert rel(out, form_out) < 2e-5
    for n, a, b in zip(NAMES, grads, form_grads):
        assert rel(a, b) < 2e-5, n
