"""The gated delta rule of linear attention (Gated DeltaNet: Yang, Kautz,
Hatamizadeh 2024, arXiv:2412.06464) in its chunked form, plain ``jax.numpy``
and ``lax``.

Per value head, ``S`` a (keys x values) state that starts at 0::

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                    alpha_t = exp(g_t), g_t <= 0

A recurrence over T. ``chunk_gated_delta_rule`` computes it ``chunk`` tokens
at a time. Inside a chunk (the WY / UT form) with ``c`` the cumulative sum
of ``g`` from the chunk's start, ``L = tril(diag(beta) (K K^T) * exp(c_i -
c_j), -1)`` and ``X = (I + L)^-1``::

    U = X (beta * V)            W = X (beta * exp(c) * K)
    V' = U - W S                                what the chunk really writes
    O  = (exp(c) * Q) S + tril((Q K^T) * exp(c_i - c_j)) V'
    S <- exp(c_last) S + (exp(c_last - c) * K)^T V'

``X`` of every chunk and head is found at once, outside the scan, by block
forward substitution (``_unit_lower_inverse``: log2(chunk) - 1 steps of two
batched matmuls, no loop over rows); only the four small matmuls against
``S`` run in the ``lax.scan`` over chunks.

What is float32 whatever the operands' dtype: ``g``, its cumulative sums
and every decay, ``beta``, ``L`` and its inverse, ``V'`` and the state.
Matmul operands are in the dtype of ``q`` with float32 accumulation (the
inverse's own products are float32 at ``precision=HIGHEST``). A decay
between two tokens of a chunk is ``exp`` of a difference of the float32
cumulative sum, so its relative error is 6e-8 x the chunk's summed ``|g|``:
nothing at a trained model's decays (under 100 a chunk), 1e-4 of a test
that draws ``g`` in the thousands.

What backward keeps: the scan's body and the chunk-local algebra are
``jax.checkpoint``ed, so the residuals are the operands, ``U``, ``W``, the
chunks' inverses ``X`` (T x chunk a head: half an operand's size) and one
state a chunk ((T / chunk) x heads x keys x values float32), never a state
a token. ``X`` is kept because its backward is two matmuls on it and its
forward twelve. Under the executor's per-operator recomputation
(``MXNET_BACKWARD_DO_MIRROR``) the kernel path names ``U``, ``W``, ``X``
(``gated_delta_kernels._within_fwd``) and the state every chunk started
from (``_across_fwd``), the operator's checkpoint keeps them, and neither
forward kernel runs again; this form marks nothing, and its scan's forward
runs again (the one state a chunk is jax's own scan residual, which no name
reaches).

Where the rule gives a plan (``kernel_plan``) both halves run in Pallas
kernels, the chunk-local algebra and the scan over chunks
(``gated_delta_kernels``: the same mathematics at the same precision, a
head's state in VMEM over all of its chunks); everywhere else this file's
``jax.numpy`` form is the operator, and it is the kernels' oracle in the
tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from . import gated_delta_kernels, pallas_support
from .defs_tensor import matmul_precision

_HIGHEST = lax.Precision.HIGHEST


def l2_normalize(x, eps=1e-6):
    """``x / sqrt(sum(x^2, last axis) + eps)`` with float32 statistics."""
    xf = x.astype(jnp.float32)
    return (xf * lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True) + eps)
            ).astype(x.dtype)


_INVERSE = "gated_delta_inverse"   # the one residual `_within_chunks` keeps


def _forward_substitution(low):
    """``(I + low)^-1`` as ``_unit_lower_inverse`` describes it, named so
    that ``_within_chunks``' checkpoint keeps it."""
    c = low.shape[-1]
    row, col = jnp.arange(c)[:, None], jnp.arange(c)[None, :]

    def below(s):
        """The off-diagonal block X of each diagonal block of 2s rows."""
        return (row // (2 * s) == col // (2 * s)) & (row % (2 * s) >= s) \
            & (col % (2 * s) < s)

    # blocks of two rows: [[1, 0], [x, 1]]^-1 = [[1, 0], [-x, 1]], no matmul
    inverse = jnp.eye(c, dtype=low.dtype) - jnp.where(below(1), low, 0.0)
    s = 2
    while s < c:
        inverse = inverse - jnp.matmul(
            jnp.matmul(inverse, jnp.where(below(s), low, 0.0),
                       precision=_HIGHEST), inverse, precision=_HIGHEST)
        s *= 2
    return checkpoint_name(inverse, _INVERSE)


@jax.custom_vjp
def _unit_lower_inverse(low):
    """``(I + low)^-1`` for strictly lower triangular ``low`` (..., C, C),
    C a power of two, float32, by block forward substitution: the inverse
    of the diagonal blocks of s rows is known (s = 1: the identity), and
    ``[[A, 0], [X, B]]^-1 = [[A^-1, 0], [-B^-1 X A^-1, B^-1]]`` gives that
    of the blocks of 2s. log2(C) - 1 steps of two batched matmuls, each as
    stable as substitution a row at a time (the product ``(I - L)(I + L^2)
    (I + L^4)...`` is not: with alike keys the powers of L grow like
    binomial coefficients and cancel). Backward is the inverse's own rule,
    ``d low = -tril(X^T g X^T, -1)``, two matmuls on the kept ``X`` and not
    the chain's twenty-four (a third of the Qwen3-Next step's device time
    when it was differentiated through; PERF.md, PR 34)."""
    return _forward_substitution(low)


def _inverse_fwd(low):
    inverse = _forward_substitution(low)
    return inverse, inverse


def _inverse_bwd(inverse, g):
    xt = jnp.swapaxes(inverse, -1, -2)
    return (-jnp.tril(jnp.matmul(jnp.matmul(xt, g, precision=_HIGHEST), xt,
                                 precision=_HIGHEST), -1),)


_unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _decay(c, strict):
    """``exp(c_i - c_j)`` (..., C, C) where ``j < i`` (``strict``) or ``j
    <= i``, 0 elsewhere; the difference is masked before ``exp``, so that
    what lies above the diagonal neither overflows nor has a gradient."""
    n = c.shape[-1]
    seen = jnp.tril(jnp.ones((n, n), bool), -1 if strict else 0)
    apart = jnp.where(seen, c[..., :, None] - c[..., None, :], 0.0)
    return jnp.where(seen, jnp.exp(apart), 0.0)


def _within_chunks(k, v, c, beta):
    """(U, W) of every chunk: k (B, Hk, N, C, Dk) shared by the G value
    heads of its group, v (B, Hk, G, N, C, Dv), c and beta (B, Hk, G, N, C)
    float32."""
    dt = k.dtype
    kk = jnp.einsum("bhnid,bhnjd->bhnij", k, k,
                    preferred_element_type=jnp.float32,
                    precision=matmul_precision(dt))
    low = beta[..., None] * kk[:, :, None] * _decay(c, strict=True)
    inverse = _unit_lower_inverse(low)
    kf = k.astype(jnp.float32)[:, :, None]
    rhs = jnp.concatenate(
        [v.astype(jnp.float32) * beta[..., None],
         kf * (beta * jnp.exp(c))[..., None]], axis=-1)
    solved = jnp.matmul(inverse, rhs, precision=_HIGHEST)
    dv = v.shape[-1]
    return solved[..., :dv], solved[..., dv:].astype(dt)


def _chunk_step(state, chunk):
    """One chunk against the carried ``state`` (B, Hk, G, Dk, Dv) float32:
    (the next state, the chunk's outputs (B, Hk, G, C, Dv))."""
    q, k, u, w, c = chunk
    dt = q.dtype
    prec = matmul_precision(dt)

    def mm(spec, a, b):
        return jnp.einsum(spec, a.astype(dt), b.astype(dt), precision=prec,
                          preferred_element_type=jnp.float32)

    s = state
    written = u - mm("bhgik,bhgkv->bhgiv", w, s)
    qk = mm("bhid,bhjd->bhij", q, k)[:, :, None] * _decay(c, strict=False)
    rise = jnp.exp(c)[..., None]
    out = mm("bhgik,bhgkv->bhgiv", q[:, :, None] * rise, s) \
        + mm("bhgij,bhgjv->bhgiv", qk, written)
    last = c[..., -1:]
    fade = jnp.exp(last - c)[..., None]
    s = s * jnp.exp(last)[..., None] \
        + mm("bhgik,bhgiv->bhgkv", k[:, :, None] * fade, written)
    return s, out.astype(dt)


def chunks_of(T, chunk):
    """Chunks of ``chunk`` tokens that cover T positions."""
    return -(-T // chunk)


def kernel_plan(dtype, k_shape, v_shape, chunk, platform=None):
    """The rule of the operator's kernels, the chunk-local algebra's and
    the scan's alike: their block (``gated_delta_kernels.plan``) for keys
    ``k_shape`` (B, Hk, T, Dk) and values ``v_shape`` (B, Hv, T, Dv) of
    ``dtype`` in a program lowered for ``platform`` (the executor's, through
    ``OpMode.platform``; None: jax's default backend) in a process that
    holds one TPU, or None: the ``jax.numpy`` form (the CPU, several chips,
    a float32 trunk, a head width 128 does not divide, another chunk). The
    op and its launch counts ask it with the same arguments."""
    _, Hk, T, Dk = k_shape
    return gated_delta_kernels.plan(
        platform or jax.default_backend(),
        pallas_support.attached_vmem_bytes(), dtype, Dk, v_shape[3],
        v_shape[1] // Hk, chunk, T)


@functools.partial(jax.jit, static_argnames=("chunk", "kernels", "interpret"))
def chunk_gated_delta_rule(q, k, v, g, beta, chunk=64, kernels=None,
                           interpret=False):
    """o (B, Hv, T, Dv) in v's dtype: the gated delta rule of q, k (B, Hk,
    T, Dk), v (B, Hv, T, Dv) and g, beta (B, Hv, T), value head n reading
    key head ``n // (Hv / Hk)``, in chunks of ``chunk`` tokens (a power of
    two). q arrives scaled and, like k, normalised if the model does so. A
    T that is no multiple of ``chunk`` is padded with alpha = 1, beta = 0:
    tokens that write nothing and fade nothing.

    ``kernels`` (a ``gated_delta_kernels.Plan``, from the rule
    :func:`kernel_plan`): the chunk-local algebra and the scan over chunks
    in the Pallas kernels, T padded to their whole blocks of chunks; None:
    ``_within_chunks`` and ``_chunk_step`` under ``lax.scan``.
    ``interpret`` runs the kernels in Pallas's interpreter (tests on the
    CPU)."""
    if chunk & (chunk - 1):
        raise ValueError(f"gated delta rule: chunk {chunk} is no power of "
                         "two")
    B, Hk, T, Dk = q.shape
    Hv, Dv = v.shape[1], v.shape[3]
    if Hv % Hk:
        raise ValueError(f"gated delta rule: {Hv} value heads over {Hk} "
                         "key heads")
    G, N = Hv // Hk, chunks_of(
        T if kernels is None
        else gated_delta_kernels.padded(T, chunk, kernels), chunk)
    pad = N * chunk - T
    k = k.astype(q.dtype)
    v = v.astype(q.dtype)
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for x in (q, k, v))
        g, beta = (jnp.pad(x, ((0, 0), (0, 0), (0, pad))) for x in (g, beta))
    with jax.named_scope("gated_delta_rule"):
        q = q.reshape(B, Hk, N, chunk, Dk)
        k = k.reshape(B, Hk, N, chunk, Dk)
        v = v.reshape(B, Hk, G, N, chunk, Dv)
        c = jnp.cumsum(g.reshape(B, Hk, G, N, chunk), axis=-1)
        beta = beta.reshape(B, Hk, G, N, chunk)
        if kernels is not None:
            with jax.named_scope("within_chunks"):
                u, w = gated_delta_kernels.within_chunks(
                    k, v, c, beta, kernels, interpret)
            with jax.named_scope("across_chunks"):
                out = gated_delta_kernels.across_chunks(
                    q, k, u, w, c, kernels, interpret)
        else:
            with jax.named_scope("within_chunks"):
                u, w = jax.checkpoint(
                    _within_chunks,
                    policy=jax.checkpoint_policies.save_only_these_names(
                        _INVERSE))(k, v, c, beta)
            # the chunk axis first: what the scan walks
            chunks = (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0),
                      jnp.moveaxis(u, 3, 0), jnp.moveaxis(w, 3, 0),
                      jnp.moveaxis(c, 3, 0))
            state = jnp.zeros((B, Hk, G, Dk, Dv), jnp.float32)
            with jax.named_scope("across_chunks"):
                _, out = lax.scan(jax.checkpoint(_chunk_step), state, chunks)
    if kernels is None:
        out = jnp.moveaxis(out, 0, 3)
    out = out.reshape(B, Hv, N * chunk, Dv)
    return out[:, :, :T] if pad else out
