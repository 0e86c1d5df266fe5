"""The gated delta rule's chunk-local algebra as Pallas TPU kernels: a chunk
lives in VMEM from its operands to its results.

``within_chunks(k, v, c, beta, plan)`` is ``gated_delta._within_chunks`` where
the rule (``plan``) says so: k (B, Hk, N, C, Dk) shared by the G value heads
of its group, v (B, Hk, G, N, C, Dv), c and beta (B, Hk, G, N, C) float32 ->
``U`` (N, B, Hk, G, C, Dv) float32 and ``W`` (N, B, Hk, G, C, Dk) in the
operands' dtype, the chunk axis first as the scan over chunks walks it. The
mathematics is ``_within_chunks``' and ``_inverse_bwd``'s::

    L = tril(beta (K K^T) exp(c_i - c_j), -1)       X = (I + L)^-1
    U = X (beta V)                                  W = X (beta e^c K)
    d rhs = X^T d solved    dX = d solved rhs^T     dL = -tril(X^T dX X^T, -1)

``K K^T`` (and its gradient) is a product of the operands' dtype with float32
accumulation; ``c``, every decay, ``beta``, ``L``, ``X`` and ``U`` are
float32, and every product that has ``L`` or ``X`` as an operand is a float32
contraction at ``precision=HIGHEST``. ``X`` is by block forward substitution
(``gated_delta._unit_lower_inverse`` says why not a product of powers).

What the kernels do that the ``jax.numpy`` form does not:

* A grid step takes ``plan.chunks`` chunks of one (batch, key head), forms
  ``K K^T`` once for the group and then, a value head at a time, ``L``, ``X``,
  ``U`` and ``W`` without leaving VMEM; of everything (C x C) only ``X``, the
  residual backward reads, reaches HBM. The ``jax.numpy`` form writes and
  re-reads a (B, Hk, G, N, C, C) float32 array between each of its ten
  matmuls, masks included.
* Two chunks are worked side by side in the lanes, a *pair* ``[A0 | A1]`` (C,
  2C): whole vector registers, and one (C, 2C) x (2C, 2C) product against the
  block diagonal of another pair is both chunks' (C, C) x (C, C) products. A
  64-wide float32 product costs the MXU what a 128-wide one does (measured on
  a v5e, PERF.md section 6, PR 35: the forward 7.8 ms a layer of the cell a
  chunk at a time, 4.8 by pairs), so the ten products of the inverse are paid
  once for two chunks. ``X`` is kept as pairs too: (N / 2, C, 2C), no lane
  padded in HBM.
* ``U`` and ``W`` are written chunk-major, as the scan over chunks reads
  them: no ``moveaxis`` between the two halves of the operator.

``plan`` is the one rule that says whether the kernels engage, as
``flash_attention.plan`` is attention's; traced kernels are kept by
``grouped_matmul._kernel``'s store.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import grouped_matmul as _gmm
from .registry import keep

_LANES = 128
_HIGHEST = lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))   # x @ y.T
# Tokens a chunk the kernels hold: two chunks fill the 128 lanes.
_CHUNKS = (64,)
# Chunks a grid step: ``c`` and ``beta`` arrive a row a pair of chunks, whole
# groups of 8 sublanes. 16 and 32 read the same on a v5e (4.82 / 4.82 ms
# forward, 7.80 / 7.78 forward + backward a layer of the cell; PERF.md
# section 6, PR 35), and T is padded to whole steps: the narrower.
_BLOCK = 16


class Plan(NamedTuple):
    """``chunks`` chunks of a (batch, key head) a grid step."""

    chunks: int
    vmem_limit: int


def plan(platform, vmem_bytes, dtype, Dk, Dv, group, chunk,
         T) -> Optional[Plan]:
    """The rule. The kernels engage where the program is lowered for one
    TPU whose VMEM is known, the operands are bfloat16 (a float32 trunk
    keeps the ``jax.numpy`` form), ``Dk`` and ``Dv`` are multiples of 128,
    ``chunk`` is one of ``_CHUNKS``, and what a grid step of ``_BLOCK``
    chunks moves in the backward kernel (the larger of the two: operands,
    cotangents, inverses and results of the group), twice for the
    pipeline's two buffers, is under half the VMEM. T is padded to whole
    grid steps by the caller (``padded``). None = the ``jax.numpy`` form."""
    if platform != "tpu" or not vmem_bytes:
        return None
    if jnp.dtype(dtype) != jnp.bfloat16 or Dk % _LANES or Dv % _LANES:
        return None
    if chunk not in _CHUNKS:
        return None
    rows = _BLOCK * chunk
    need = 2 * (2 * rows * Dk * 2                       # k, dk
                + group * rows * (2 * Dv * 2 + Dk * 2   # v, dv, dw
                                  + Dv * 4 + chunk * 4  # du, X
                                  + 4 * 4))             # c, beta, dc, dbeta
    if need > vmem_bytes // 2:
        return None
    return Plan(_BLOCK, min(vmem_bytes * 3 // 4, need + (16 << 20)))


def padded(T, chunk, plan):
    """T rounded up to whole grid steps of ``plan.chunks`` chunks."""
    block = chunk * plan.chunks
    return -(-T // block) * block


# --- what both kernels compute of a pair of chunks and one value head -------
def _hi(a, b, dims=(((1,), (0,)), ((), ()))):
    """A float32 product of six bfloat16 passes."""
    return lax.dot_general(a, b, dims, precision=_HIGHEST,
                           preferred_element_type=jnp.float32)


class _Pair(NamedTuple):
    """Index planes of a pair (C, 2C): the row, the column inside its chunk,
    whether the lane is the first chunk's, and the same of the pair's block
    diagonal (2C, 2C)."""

    row: jax.Array
    col: jax.Array
    first: jax.Array
    own: jax.Array     # (2C, 2C): row and lane of the same chunk

    @property
    def eye(self):
        return self.row == self.col

    @property
    def seen(self):
        return self.col < self.row

    def below(self, s):
        """The off-diagonal block of each diagonal block of 2s rows."""
        row, col = self.row, self.col
        return ((row & -(2 * s)) == (col & -(2 * s))) & ((row & s) != 0) \
            & ((col & s) == 0)


def _pair_planes(C):
    row = lax.broadcasted_iota(jnp.int32, (C, 2 * C), 0)
    lane = lax.broadcasted_iota(jnp.int32, (C, 2 * C), 1)
    r2 = lax.broadcasted_iota(jnp.int32, (2 * C, 2 * C), 0)
    l2 = lax.broadcasted_iota(jnp.int32, (2 * C, 2 * C), 1)
    return _Pair(row, lane & (C - 1), lane < C, (r2 < C) == (l2 < C))


def _diagonal(t, p):
    """[A0 | A1] -> [[A0, 0], [0, A1]]."""
    return jnp.where(p.own, jnp.concatenate([t, t], axis=0), 0.0)


def _half(t, j):
    """(C, D) -> (2C, D): the rows a pair's chunk ``j`` multiplies."""
    zero = jnp.zeros_like(t)
    return jnp.concatenate([zero, t] if j else [t, zero], axis=0)


def _row_sums(t, p):
    """The sums over each chunk's columns of a pair: two (C, 1)."""
    return [jnp.sum(jnp.where(p.first == (j == 0), t, 0.0), axis=1,
                    keepdims=True) for j in range(2)]


def _columns(x, p):
    """A pair's row (1, 2C) -> its chunks' columns (C, 1) and the pair of
    them (C, 2C), exactly: the diagonal of the row's broadcast."""
    cols = _row_sums(jnp.where(p.eye, x, 0.0), p)
    return cols, jnp.where(p.first, *cols)


def _rows(cols, p):
    """Two columns (C, 1) -> the pair's row (1, 2C)."""
    return jnp.sum(jnp.where(p.eye, jnp.where(p.first, *cols), 0.0), axis=0,
                   keepdims=True)


def _inverse(low, p):
    """``(I + low)^-1`` of both chunks of a pair, ``low`` strictly lower
    triangular float32: ``gated_delta._forward_substitution``."""
    c = low.shape[0]
    inverse = jnp.where(p.eye, 1.0, 0.0) - jnp.where(p.below(1), low, 0.0)
    s = 2
    while s < c:
        moved = _diagonal(jnp.where(p.below(s), low, 0.0), p)
        inverse = inverse - _hi(_hi(inverse, moved), _diagonal(inverse, p))
        s *= 2
    return inverse


def _pair_head(kk, c_row, beta_row, p):
    """Of a pair of chunks and one value head: beta and c as columns (two
    (C, 1) and their pair, each), the strictly lower decay and ``K K^T``
    times it."""
    cs, c = _columns(c_row, p)
    betas, beta = _columns(beta_row, p)
    decay = jnp.where(p.seen,
                      jnp.exp(jnp.where(p.seen, c - c_row, 0.0)), 0.0)
    return betas, beta, cs, decay, kk * decay


def _keys(k_ref, i):
    """The keys of the pair ``i`` of a block: as stored, float32, and the
    pair of their ``K K^T``."""
    kb = [k_ref[2 * i + j] for j in range(2)]
    kk = jnp.concatenate(
        [lax.dot_general(x, x, _NT, preferred_element_type=jnp.float32)
         for x in kb], axis=1)
    return kb, [x.astype(jnp.float32) for x in kb], kk


def _for_the_pairs(chunks, pair):
    def body(i, carry):
        pair(i)
        return carry

    lax.fori_loop(0, chunks // 2, body, None)


def _specs(chunks, group, C, Dk, Dv):
    """BlockSpecs over the grid (batch, key head, block of chunks): the key
    head's rows, a value-wide and a key-wide block of the group chunk-major
    as the scan reads them, the same the group's heads first, and a row of
    vectors and a (C, 2C) inverse a pair of chunks."""
    pl, _ = _gmm._pallas()
    return dict(
        k=pl.BlockSpec((None, None, chunks, C, Dk),
                       lambda b, h, i: (b, h, i, 0, 0)),
        v=pl.BlockSpec((None, None, group, chunks, C, Dv),
                       lambda b, h, i: (b, h, 0, i, 0, 0)),
        u=pl.BlockSpec((chunks, None, None, group, C, Dv),
                       lambda b, h, i: (i, b, h, 0, 0, 0)),
        w=pl.BlockSpec((chunks, None, None, group, C, Dk),
                       lambda b, h, i: (i, b, h, 0, 0, 0)),
        vec=pl.BlockSpec((None, None, group, chunks // 2, 2 * C),
                         lambda b, h, i: (b, h, 0, i, 0)),
        x=pl.BlockSpec((None, None, group, chunks // 2, C, 2 * C),
                       lambda b, h, i: (b, h, 0, i, 0, 0)))


def _cost(k, v, matmuls, passes):
    """``matmuls`` (C x C) x (C x C) float32 products of six bfloat16
    passes a chunk and value head, ``passes`` over the wide operands."""
    pl, _ = _gmm._pallas()
    heads, C = v.size // (v.shape[-1] * v.shape[-2]), v.shape[-2]
    wide = k.shape[-1] + v.shape[-1]
    return pl.CostEstimate(
        flops=heads * 2 * C * C * 6 * (matmuls * C + passes * wide),
        transcendentals=heads * C * C,
        bytes_accessed=(k.size + v.size) * 2 * (passes + 1)
        + heads * C * C * 4)


def _pairs(x):
    """(..., N, C) -> (..., N / 2, 2C): a row a pair of chunks."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] // 2, 2 * x.shape[-1]))


# --- forward -----------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("chunks", "vmem_limit",
                                             "interpret"))
def _fwd(k, v, c, beta, *, chunks, vmem_limit, interpret):
    """(U, W chunk-major, X (B, Hk, G, N / 2, C, 2C) float32: the inverses
    of a pair of chunks side by side)."""
    pl, pltpu = _gmm._pallas()
    B, Hk, N, C, Dk = k.shape
    G, Dv = v.shape[2], v.shape[-1]

    def kernel(k_ref, v_ref, c_ref, beta_ref, u_ref, w_ref, x_ref):
        p = _pair_planes(C)

        def pair(i):
            _, kf, kk = _keys(k_ref, i)
            at = pl.ds(i, 1)
            for g in range(G):
                betas, beta, cs, _, kd = _pair_head(
                    kk, c_ref[g, at, :], beta_ref[g, at, :], p)
                x = _inverse(beta * kd, p)
                x_ref[g, i] = x
                for j in range(2):
                    n = 2 * i + j
                    rhs = jnp.concatenate(
                        [v_ref[g, n].astype(jnp.float32) * betas[j],
                         kf[j] * (betas[j] * jnp.exp(cs[j]))], axis=1)
                    solved = _hi(x, _half(rhs, j))
                    u_ref[n, g] = solved[:, :Dv]
                    w_ref[n, g] = solved[:, Dv:].astype(w_ref.dtype)

        _for_the_pairs(chunks, pair)

    s = _specs(chunks, G, C, Dk, Dv)
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((N, B, Hk, G, C, Dv), jnp.float32),
                   jax.ShapeDtypeStruct((N, B, Hk, G, C, Dk), k.dtype),
                   jax.ShapeDtypeStruct((B, Hk, G, N // 2, C, 2 * C),
                                        jnp.float32)),
        grid=(B, Hk, N // chunks),
        in_specs=[s["k"], s["v"], s["vec"], s["vec"]],
        out_specs=[s["u"], s["w"], s["x"]],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=_cost(k, v, matmuls=10, passes=1),
        interpret=interpret,
        name="gated_delta_chunks_fwd",
    )(k, v, _pairs(c), _pairs(beta))


# --- backward ----------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("chunks", "vmem_limit",
                                             "interpret"))
def _bwd(k, v, c, beta, x, du, dw, *, chunks, vmem_limit, interpret):
    """(dk, dv, dc, dbeta) in the operands' shapes and dtypes."""
    pl, pltpu = _gmm._pallas()
    B, Hk, N, C, Dk = k.shape
    G, Dv = v.shape[2], v.shape[-1]

    def kernel(k_ref, v_ref, c_ref, beta_ref, x_ref, du_ref, dw_ref,
               dk_ref, dv_ref, dc_ref, dbeta_ref):
        p = _pair_planes(C)

        def pair(i):
            kb, kf, kk = _keys(k_ref, i)
            dk = [jnp.zeros((C, Dk), jnp.float32) for _ in range(2)]
            dkk = jnp.zeros((C, 2 * C), jnp.float32)
            at = pl.ds(i, 1)
            for g in range(G):
                betas, beta, cs, decay, kd = _pair_head(
                    kk, c_ref[g, at, :], beta_ref[g, at, :], p)
                xt = x_ref[g, i].T                    # [[X0^T], [X1^T]]
                dx, dbeta, dc = [], [], []
                for j in range(2):
                    n = 2 * i + j
                    rise = jnp.exp(cs[j])
                    vf = v_ref[g, n].astype(jnp.float32)
                    d_solved = jnp.concatenate(
                        [du_ref[n, g], dw_ref[n, g].astype(jnp.float32)],
                        axis=1)
                    rhs = jnp.concatenate(
                        [vf * betas[j], kf[j] * (betas[j] * rise)], axis=1)
                    # d rhs = X^T d solved, dX = d solved rhs^T
                    d_rhs = _hi(xt[j * C:(j + 1) * C], d_solved)
                    dx.append(_hi(d_solved, rhs, _NT))
                    dv_rhs, dk_rhs = d_rhs[:, :Dv], d_rhs[:, Dv:]
                    key = jnp.sum(dk_rhs * kf[j], axis=1, keepdims=True)
                    dbeta.append(jnp.sum(dv_rhs * vf, axis=1, keepdims=True)
                                 + rise * key)
                    dc.append(betas[j] * rise * key)
                    dv_ref[g, n] = (dv_rhs * betas[j]).astype(dv_ref.dtype)
                    dk[j] = dk[j] + dk_rhs * (betas[j] * rise)
                xts = jnp.concatenate([xt[:C], xt[C:]], axis=1)
                dlow = -jnp.where(
                    p.seen, _hi(_hi(xts, _diagonal(
                        jnp.concatenate(dx, axis=1), p)),
                        _diagonal(xts, p)), 0.0)
                # L = beta (K K^T) decay, the decay exp(c_i - c_j)
                moved = dlow * kd              # dL x dL / dbeta
                faded = moved * beta           # dL x L
                dkk = dkk + dlow * beta * decay
                dbeta = [a + b for a, b in zip(dbeta, _row_sums(moved, p))]
                dc = [a + b for a, b in zip(dc, _row_sums(faded, p))]
                dbeta_ref[g, at, :] = _rows(dbeta, p)
                dc_ref[g, at, :] = _rows(dc, p) - jnp.sum(
                    faded, axis=0, keepdims=True)
            # K K^T's own gradient, a product of the operands' dtype
            dkkt = dkk.T
            for j in range(2):
                both = (dkk[:, j * C:(j + 1) * C]
                        + dkkt[j * C:(j + 1) * C]).astype(kb[j].dtype)
                dk_ref[2 * i + j] = (dk[j] + jnp.dot(
                    both, kb[j], preferred_element_type=jnp.float32)).astype(
                        dk_ref.dtype)

        _for_the_pairs(chunks, pair)

    s = _specs(chunks, G, C, Dk, Dv)
    dk, dv, dc, dbeta = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(_pairs(c).shape, jnp.float32),
                   jax.ShapeDtypeStruct(_pairs(c).shape, jnp.float32)),
        grid=(B, Hk, N // chunks),
        in_specs=[s["k"], s["v"], s["vec"], s["vec"], s["x"], s["u"],
                  s["w"]],
        out_specs=[s["k"], s["v"], s["vec"], s["vec"]],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=_cost(k, v, matmuls=4, passes=2),
        interpret=interpret,
        name="gated_delta_chunks_bwd",
    )(k, v, _pairs(c), _pairs(beta), x, du, dw)
    return dk, dv, dc.reshape(c.shape), dbeta.reshape(c.shape)


# --- what chunk_gated_delta_rule calls ----------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def within_chunks(k, v, c, beta, plan, interpret=False):
    """(U, W), the chunk axis first: the forward kernel at ``plan``'s block.
    N is a multiple of ``plan.chunks`` (``padded``). Backward keeps the
    operands and the chunks' inverses; under per-operator recomputation
    (``MXNET_BACKWARD_DO_MIRROR``) the inverses, ``U`` and ``W`` are what
    the operator names (``registry.keep``), so that the forward kernel runs
    once a step and not again in backward. ``interpret`` runs the kernels
    in Pallas's interpreter (tests on the CPU)."""
    return _within_fwd(k, v, c, beta, plan, interpret)[0]


def _static(plan, interpret):
    return dict(chunks=plan.chunks, vmem_limit=plan.vmem_limit,
                interpret=interpret)


def _within_fwd(k, v, c, beta, plan, interpret):
    # the scan over chunks reads u and w again in its backward, this
    # rule's backward x: kept under per-operator recomputation
    u, w, x = keep(_gmm._kernel(_fwd, (k, v, c, beta),
                                **_static(plan, interpret)))
    return (u, w), (k, v, c, beta, x)


def _within_bwd(plan, interpret, res, g):
    return _gmm._kernel(_bwd, (*res, *g), **_static(plan, interpret))


within_chunks.defvjp(_within_fwd, _within_bwd)
