"""The gated delta rule of linear attention in its chunked form, plain
``jax.numpy`` and ``lax``, with the state's decay in either of two forms: one
scalar a value head and token (Gated DeltaNet: Yang, Kautz, Hatamizadeh 2024,
arXiv:2412.06464) or one a KEY CHANNEL of a head and token (Kimi Delta
Attention: Moonshot AI 2025, arXiv:2510.26692).

Per value head, ``S`` a (keys x values) state that starts at 0::

    a gate a head     S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
                      alpha_t = exp(g_t), g_t <= 0 a scalar
    a gate a channel  S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
                      g_t <= 0 a vector over the keys' Dk channels
    both              o_t = S_t^T q_t

A scalar gate is the vector gate with all channels equal (``alpha`` then
commutes with the reflection), and the two forms share every line below that
does not touch a decay: the padding, the inverse and its backward rule,
``U`` and ``W``, the scan over chunks and what is checkpointed.

A recurrence over T. ``chunk_gated_delta_rule`` computes it ``chunk`` tokens
at a time. Inside a chunk (the WY / UT form) with ``c`` the cumulative sum
of ``g`` from the chunk's start (a scalar or a vector a token), ``G =
exp(c)``, ``X = (I + L)^-1``::

    a head      L = tril(diag(beta) (K K^T) * exp(c_i - c_j), -1)
                P = tril((Q K^T) * exp(c_i - c_j))
    a channel   L_ij = beta_i sum_d k_id k_jd exp(c_id - c_jd)     (j < i)
                P_ij =        sum_d q_id k_jd exp(c_id - c_jd)     (j <= i)
    both        U = X (beta * V)            W = X (beta * (K . G))
                V' = U - W S                    what the chunk really writes
                O  = (Q . G) S + P V'
                S <- Diag(G_last) S + (K . G_last / G)^T V'

With a gate a head the decay of a pair leaves the sum over the keys' width:
the chunk's two Gram matrices are a product and a mask (``_decay``). With a
gate a channel it sits INSIDE the sum, and ``(K . G) (K / G)^T`` overflows
(over 64 tokens at ``g`` = -1.6 a token ``1 / G`` is e^102).
``_channel_grams`` forms both matrices without ever raising ``e`` to a
positive power and without a (chunk, chunk, Dk) array: the chunk is cut into
sub-chunks of ``_SUB`` = 8 tokens; for a sub-chunk ``I`` against the
sub-chunks ``J`` before it the reference is ``r``, the first token of
``I``, and ``(K_I . exp(c_I - c_r)) (K_J . exp(c_r - c_J))^T`` is one product
of two operands whose exponents are both <= 0; the diagonal pairs (``I``
against itself) are contracted a column at a time, ``sum_d a_id b_jd
exp(c_id - c_jd)`` for one ``j`` and the sub-chunk's 8 ``i``, with the
difference masked before its ``exp``. Every other exponent above is <= 0 as
written (``G``; ``G_last / G`` is ``exp(c_last - c)``).

``X`` of every chunk and head is found at once, outside the scan, by block
forward substitution (``_unit_lower_inverse``: log2(chunk) - 1 steps of two
batched matmuls, no loop over rows); so are the Gram matrices of a gate a
channel; only the four small matmuls against ``S`` run in the ``lax.scan``
over chunks.

What is float32 whatever the operands' dtype: ``g``, its cumulative sums
and every decay (a head or a channel), ``beta``, ``L`` and its inverse,
``V'`` and the state.
Matmul operands are in the dtype of ``q`` with float32 accumulation (the
inverse's own products are float32 at ``precision=HIGHEST``); an operand
that carries a decay (``Q . G``, ``K . G_last / G``, the two operands of a
sub-chunk pair) is multiplied in float32 and rounded once, as an operand. A
decay between two tokens of a chunk is ``exp`` of a difference of the float32
cumulative sum, so its relative error is 6e-8 x the chunk's summed ``|g|``:
nothing at a trained model's decays (under 100 a chunk), 1e-4 of a test
that draws ``g`` in the thousands.

What backward keeps: the scan's body and the chunk-local algebra are
``jax.checkpoint``ed, so the residuals are the operands, ``U``, ``W``, the
chunks' inverses ``X`` (T x chunk a head: half an operand's size; with a
gate a channel the two Gram matrices too, the same size each) and one
state a chunk ((T / chunk) x heads x keys x values float32), never a state
a token. ``X`` is kept because its backward is two matmuls on it and its
forward twelve. Under the executor's per-operator recomputation
(``MXNET_BACKWARD_DO_MIRROR``) the kernel path names ``U``, ``W``, ``X``
(``gated_delta_kernels._within_fwd``), the state every chunk started
from (``_across_fwd``) and, with a gate a channel, ``c`` and the two Gram
matrices too (``_channel_fwd``), the operator's checkpoint keeps them, and
no forward kernel runs again; this form marks nothing, and its scan's forward
runs again (the one state a chunk is jax's own scan residual, which no name
reaches).

Where the rule gives a plan (``kernel_plan``) both halves run in Pallas
kernels, the chunk-local algebra and the scan over chunks
(``gated_delta_kernels``: the same mathematics at the same precision, a
head's state in VMEM over all of its chunks), with either gate: a gate a
channel under the same rule (one TPU, a bfloat16 trunk, widths 128 divides,
chunks of 64, its wider blocks under half the VMEM), its two Gram matrices
formed in VMEM by two kernels of their own and all six under one
differentiation rule (``gated_delta_kernels.channel_gated``: ``g``'s running
sum and every sum of cotangents are taken on the kernels' tiles), all of the
operator or none of it. Everywhere else this file's ``jax.numpy`` form is the operator, and it
is the kernels' oracle in the tests.
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


_SUB = 8    # tokens a sub-chunk of a gate a channel's Gram matrices


def _fade_from(c, j):
    """``exp(c_i - c_j)`` (..., sub, Dk) for the rows ``i >= j`` of a
    sub-chunk, 0 above; the difference is masked before its ``exp``."""
    seen = jnp.arange(c.shape[-2])[:, None] >= j
    apart = jnp.where(seen, c - lax.dynamic_slice_in_dim(c, j, 1, -2), 0.0)
    return jnp.where(seen, jnp.exp(apart), 0.0)


def _diagonal_columns(q, k, c):
    sub = c.shape[-2]
    below = jnp.arange(sub)[:, None]

    def column(j, grams):
        col = lax.dynamic_slice_in_dim(k, j, 1, -2) * _fade_from(c, j)
        kk_j = jnp.sum(jnp.where(below > j, k * col, 0.0), -1, keepdims=True)
        qk_j = jnp.sum(q * col, -1, keepdims=True)
        return tuple(lax.dynamic_update_slice_in_dim(g, g_j, j, -1)
                     for g, g_j in zip(grams, (kk_j, qk_j)))

    empty = jnp.zeros(c.shape[:-1] + (sub,), jnp.float32)
    return lax.fori_loop(0, sub, column, (empty, empty))


@jax.custom_vjp
def _diagonal_grams(q, k, c):
    """A sub-chunk against itself: ``sum_d k_id k_jd exp(c_id - c_jd)``
    where ``j < i`` and ``sum_d q_id k_jd exp(c_id - c_jd)`` where ``j <=
    i``, (..., sub, sub) each, of q, k, c (..., sub, Dk) float32. A column
    ``j`` at a time, forward and backward: the sub-chunk's rows against ONE
    key and its decays, a (sub, Dk) product and a sum over Dk, so that
    nothing (sub, sub, Dk) is ever held; backward makes each column's
    decays again and keeps nothing but the operands. The columns are a
    ``fori_loop`` and not ``sub`` copies of the body: unrolled, XLA held the
    copies' decays side by side (2.9 GiB of temporaries a layer at the
    Kimi-Linear cell's shapes against 1.3, and 87 ms against 48 forward
    and backward on a v5e); and not one (sub, sub, Dk) expression a pass
    either, which XLA fuses into its sums (28 ms) but takes three times as
    long to compile (PERF.md section 6, PR 48, has both)."""
    return _diagonal_columns(q, k, c)


def _diagonal_fwd(q, k, c):
    return _diagonal_columns(q, k, c), (q, k, c)


def _diagonal_bwd(operands, cotangents):
    q, k, c = operands
    dkk, dqk = cotangents
    below = jnp.arange(c.shape[-2])[:, None]

    def column(j, grads):
        dq, dk, dc = grads
        fade = _fade_from(c, j)
        k_j = lax.dynamic_slice_in_dim(k, j, 1, -2)
        col = k_j * fade
        to_kk = jnp.where(below > j, lax.dynamic_slice_in_dim(dkk, j, 1, -1),
                          0.0)
        to_qk = lax.dynamic_slice_in_dim(dqk, j, 1, -1)
        d_col = (to_kk * k + to_qk * q) * fade
        d_c = d_col * k_j
        # column j's own key and decays: row j of dk and of dc
        return (dq + to_qk * col,
                dk + to_kk * col + jnp.where(
                    below == j, jnp.sum(d_col, -2, keepdims=True), 0.0),
                dc + d_c - jnp.where(
                    below == j, jnp.sum(d_c, -2, keepdims=True), 0.0))

    return lax.fori_loop(0, c.shape[-2], column,
                         tuple(jnp.zeros_like(x) for x in operands))


_diagonal_grams.defvjp(_diagonal_fwd, _diagonal_bwd)


def _channel_grams(q, k, c):
    """The two Gram matrices of a gate a channel, (..., C, C) float32 each:
    ``sum_d k_id k_jd exp(c_id - c_jd)`` where ``j < i`` and ``sum_d q_id
    k_jd exp(c_id - c_jd)`` where ``j <= i``, 0 elsewhere, of q, k (..., C,
    Dk) and the cumulative log decay ``c`` (..., C, Dk) float32. By
    sub-chunks of ``_SUB`` tokens (the module's docstring): no exponent is
    positive and nothing (C, C, Dk) exists. Both matrices read one set of
    decays."""
    dt = k.dtype
    prec = matmul_precision(dt)
    C, D = c.shape[-2:]
    sub = min(_SUB, C)
    n = C // sub
    lead = c.shape[:-2]
    qf, kf, cs = (x.astype(jnp.float32).reshape(lead + (n, sub, D))
                  for x in (q, k, c))
    kk, qk = _diagonal_grams(qf, kf, cs)              # (..., n, sub, sub)
    # sub-chunk i against those before it, from i's first token
    rows_kk, rows_qk = [], []
    for i in range(n):
        parts_kk, parts_qk = [kk[..., i, :, :]], [qk[..., i, :, :]]
        if i:
            first = cs[..., i, :1, :]
            rise = jnp.exp(cs[..., i, :, :] - first)
            here = jnp.concatenate([kf[..., i, :, :] * rise,
                                    qf[..., i, :, :] * rise], -2).astype(dt)
            before = (kf[..., :i, :, :]
                      * jnp.exp(first[..., None, :, :] - cs[..., :i, :, :])
                      ).reshape(lead + (i * sub, D)).astype(dt)
            both = jnp.einsum("...id,...jd->...ij", here, before,
                              precision=prec,
                              preferred_element_type=jnp.float32)
            parts_kk.insert(0, both[..., :sub, :])
            parts_qk.insert(0, both[..., sub:, :])
        if i < n - 1:
            after = jnp.zeros(lead + (sub, (n - 1 - i) * sub), jnp.float32)
            parts_kk.append(after)
            parts_qk.append(after)
        rows_kk.append(jnp.concatenate(parts_kk, -1))
        rows_qk.append(jnp.concatenate(parts_qk, -1))
    return jnp.concatenate(rows_kk, -2), jnp.concatenate(rows_qk, -2)


def _per_key(c, rows):
    """The cumulative log decay with a keys' axis beside ``rows`` (..., C,
    D): ``c`` (..., C, Dk) of a gate a channel as it is, (..., C) of a gate
    a head as (..., C, 1)."""
    return c if c.ndim == rows.ndim else c[..., None]


def _within_chunks(k, v, c, beta, gram=None):
    """(U, W) of every chunk: k (B, Hk, N, C, Dk) shared by the G value
    heads of its group, v (B, Hk, G, N, C, Dv), beta (B, Hk, G, N, C)
    float32, and c float32 (B, Hk, G, N, C) of a gate a head or (B, Hk, G,
    N, C, Dk) of a gate a channel, whose decayed ``K K^T`` is ``gram``
    (``_channel_grams``)."""
    dt = k.dtype
    if gram is None:
        kk = jnp.einsum("bhnid,bhnjd->bhnij", k, k,
                        preferred_element_type=jnp.float32,
                        precision=matmul_precision(dt))
        gram = kk[:, :, None] * _decay(c, strict=True)
    low = beta[..., None] * gram
    inverse = _unit_lower_inverse(low)
    kf = k.astype(jnp.float32)[:, :, None]
    rhs = jnp.concatenate(
        [v.astype(jnp.float32) * beta[..., None],
         kf * (beta[..., None] * jnp.exp(_per_key(c, v)))], axis=-1)
    solved = jnp.matmul(inverse, rhs, precision=_HIGHEST)
    dv = v.shape[-1]
    return solved[..., :dv], solved[..., dv:].astype(dt)


def _chunk_step(state, chunk):
    """One chunk against the carried ``state`` (B, Hk, G, Dk, Dv) float32:
    (the next state, the chunk's outputs (B, Hk, G, C, Dv)). ``chunk`` is
    (q, k, U, W, c) and, for a gate a channel, its decayed ``Q K^T`` too
    (``_channel_grams``)."""
    q, k, u, w, c, *given = chunk
    dt = q.dtype
    prec = matmul_precision(dt)

    def mm(spec, a, b):
        return jnp.einsum(spec, a.astype(dt), b.astype(dt), precision=prec,
                          preferred_element_type=jnp.float32)

    s = state
    written = u - mm("bhgik,bhgkv->bhgiv", w, s)
    qk = given[0] if given else \
        mm("bhid,bhjd->bhij", q, k)[:, :, None] * _decay(c, strict=False)
    c = _per_key(c, u)
    out = mm("bhgik,bhgkv->bhgiv", q[:, :, None] * jnp.exp(c), s) \
        + mm("bhgij,bhgjv->bhgiv", qk, written)
    last = c[..., -1:, :]
    s = s * jnp.swapaxes(jnp.exp(last), -1, -2) \
        + mm("bhgik,bhgiv->bhgkv", k[:, :, None] * jnp.exp(last - c), written)
    return s, out.astype(dt)


def chunks_of(T, chunk):
    """Chunks of ``chunk`` tokens that cover T positions."""
    return -(-T // chunk)


def kernel_plan(dtype, k_shape, v_shape, chunk, platform=None,
                channel_gate=False):
    """The rule of the operator's kernels, the chunk-local algebra's and
    the scan's alike: their block (``gated_delta_kernels.plan``) for keys
    ``k_shape`` (B, Hk, T, Dk) and values ``v_shape`` (B, Hv, T, Dv) of
    ``dtype`` in a program lowered for ``platform`` (the executor's, through
    ``OpMode.platform``; None: jax's default backend) in a process that
    holds one TPU, or None: the ``jax.numpy`` form (the CPU, several chips,
    a float32 trunk, a head width 128 does not divide, another chunk).
    ``channel_gate``: the gate is one a key channel, whose blocks are wider
    and whose plan also runs the Gram matrices' kernels. The op and its
    launch counts ask it with the same arguments."""
    _, Hk, T, Dk = k_shape
    return gated_delta_kernels.plan(
        platform or jax.default_backend(),
        pallas_support.attached_vmem_bytes(), dtype, Dk, v_shape[3],
        v_shape[1] // Hk, chunk, T, channel_gate)


@functools.partial(jax.jit, static_argnames=("chunk", "kernels", "interpret"))
def chunk_gated_delta_rule(q, k, v, g, beta, chunk=64, kernels=None,
                           interpret=False):
    """o (B, Hv, T, Dv) in v's dtype: the gated delta rule of q, k (B, Hk,
    T, Dk), v (B, Hv, T, Dv), beta (B, Hv, T) and g, (B, Hv, T) for a gate
    a head or (B, Hv, T, Dk) for a gate a key channel, value head n reading
    key head ``n // (Hv / Hk)``, in chunks of ``chunk`` tokens (a power of
    two). q arrives scaled and, like k, normalised if the model does so. A
    T that is no multiple of ``chunk`` is padded with alpha = 1, beta = 0:
    tokens that write nothing and fade nothing.

    ``kernels`` (a ``gated_delta_kernels.Plan``, from the rule
    :func:`kernel_plan`): the chunk-local algebra and the scan over chunks
    (and a gate a channel's Gram matrices and its running sum before them)
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
    channel = g.ndim == 4
    if channel and Hv != Hk and kernels is None:
        # its decays make a key head's Gram matrices each value head's own
        # (the kernels form them a value head at a time from the one key)
        q, k = (jnp.repeat(x, Hv // Hk, axis=1) for x in (q, k))
        Hk = Hv
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
        g, beta = (jnp.pad(x, ((0, 0), (0, 0), (0, pad))
                           + ((0, 0),) * (x.ndim - 3)) for x in (g, beta))
    with jax.named_scope("gated_delta_rule"):
        q = q.reshape(B, Hk, N, chunk, Dk)
        k = k.reshape(B, Hk, N, chunk, Dk)
        v = v.reshape(B, Hk, G, N, chunk, Dv)
        g = g.reshape((B, Hk, G, N, chunk) + g.shape[3:])
        # a gate a channel's kernels take the running sum themselves
        c = None if channel and kernels is not None else jnp.cumsum(g, axis=4)
        beta = beta.reshape(B, Hk, G, N, chunk)
        if c is None:
            # one rule over the six kernels: no cotangent is summed, and no
            # running sum taken, between them
            out = gated_delta_kernels.channel_gated(
                q, k, v, g, beta, kernels, interpret)
        elif kernels is not None:
            with jax.named_scope("within_chunks"):
                u, w = gated_delta_kernels.within_chunks(
                    k, v, c, beta, kernels, interpret)
            with jax.named_scope("across_chunks"):
                out = gated_delta_kernels.across_chunks(
                    q, k, u, w, c, kernels, interpret)
        else:
            kk, qk = None, ()
            with jax.named_scope("within_chunks"):
                if channel:
                    with jax.named_scope("grams"):
                        kk, qk = jax.checkpoint(_channel_grams)(
                            q[:, :, None], k[:, :, None], c)
                    qk = (jnp.moveaxis(qk, 3, 0),)
                u, w = jax.checkpoint(
                    _within_chunks,
                    policy=jax.checkpoint_policies.save_only_these_names(
                        _INVERSE))(k, v, c, beta, kk)
            # the chunk axis first: what the scan walks
            chunks = (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0),
                      jnp.moveaxis(u, 3, 0), jnp.moveaxis(w, 3, 0),
                      jnp.moveaxis(c, 3, 0)) + qk
            state = jnp.zeros((B, Hk, G, Dk, Dv), jnp.float32)
            with jax.named_scope("across_chunks"):
                _, out = lax.scan(jax.checkpoint(_chunk_step), state, chunks)
    if kernels is None:
        out = jnp.moveaxis(out, 0, 3)
    out = out.reshape(B, Hv, N * chunk, Dv)
    return out[:, :, :T] if pad else out
