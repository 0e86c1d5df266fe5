"""The gated delta rule as Pallas TPU kernels: a chunk lives in VMEM from its
operands to its results, and a head's state from a row's first chunk to its
last.

**The chunk-local algebra.** ``within_chunks(k, v, c, beta, plan)`` is
``gated_delta._within_chunks`` where the rule (``plan``) says so: k (B, Hk, N,
C, Dk) shared by the G value heads of its group, v (B, Hk, G, N, C, Dv), c and
beta (B, Hk, G, N, C) float32 -> ``U`` (B, Hk, G, N, C, Dv) float32 and ``W``
(B, Hk, G, N, C, Dk) in the operands' dtype. The mathematics is
``_within_chunks``' and ``_inverse_bwd``'s::

    L = tril(beta (K K^T) exp(c_i - c_j), -1)       X = (I + L)^-1
    U = X (beta V)                                  W = X (beta e^c K)
    d rhs = X^T d solved    dX = d solved rhs^T     dL = -tril(X^T dX X^T, -1)

``K K^T`` (and its gradient) is a product of the operands' dtype with float32
accumulation; ``c``, every decay, ``beta``, ``L``, ``X`` and ``U`` are
float32, and every product that has ``L`` or ``X`` as an operand is a float32
contraction at ``precision=HIGHEST``. ``X`` is by block forward substitution
(``gated_delta._unit_lower_inverse`` says why not a product of powers).

What these kernels do that the ``jax.numpy`` form does not:

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

**The scan over chunks.** ``across_chunks(q, k, U, W, c, plan)`` is
``gated_delta._chunk_step`` under its ``lax.scan``: the outputs (B, Hk, G, N,
C, Dv), which reshape to (B, Hv, T, Dv) with no transpose. A forward and a
backward kernel (``gated_delta_scan_fwd`` / ``_bwd``) walk the chunks of a
(batch, key head) in order (backward: from the last), ``plan.chunks`` a grid
step, with the group's states (G, Dk, Dv) float32 (backward: their
cotangents) in VMEM scratch over the whole walk. The ``lax.scan`` moves the
states of every head out to HBM and back each of its T / C trips, writes them
once more as its residual, and pays a ``while`` trip around a handful of small
fusions: ten times the trip's arithmetic on a v5e (PERF.md section 6, PR 42).
The precision is ``_chunk_step``'s and no other: the state, ``V' = U - W S``
and every decay float32 (a difference of ``c`` masked before its ``exp``),
every product's operands cast to the trunk's dtype, the state and the
cotangents too, with float32 accumulation, the output rounded once. Forward
also writes the state every chunk STARTED from ((T / C) x Dk x Dv float32 a
value head: the size of ``X``); backward reads it, makes the chunk's ``V'``
and ``A`` again from it, and never runs the walk forward.

**A gate a key channel** (``g`` (B, Hk, G, N, C, Dk): Kimi Delta Attention's
decay, inside the Gram matrices' sum over the keys) runs six kernels under
ONE differentiation rule, ``channel_gated(q, k, v, g, beta, plan)``, so that
nothing that only joins them is computed, summed or cast in ``jax.numpy``.

The two more are the Gram matrices' (``gated_delta_grams_fwd`` / ``_bwd``,
``gated_delta._channel_grams``): a chunk's strictly lower ``sum_d k_id k_jd
exp(c_id - c_jd)`` and lower ``sum_d q_id k_jd exp(c_id - c_jd)`` from three
(C, Dk) tiles in VMEM, with no positive exponent and nothing (C, C, Dk): the
diagonal blocks of 8 tokens a column at a time on the VPU in float32, all of
a chunk's sub-chunks at once (a sub-chunk is one vector register), and what
lies below them by LEVELS, s = 8, 16, 32: in each block of 2s tokens the rows
``[s, 2s)`` against the columns ``[0, s)`` as one MXU product of two operands
decayed toward the first of those rows and rounded once, as operands
(``_channel_grams`` takes seven products of growing width a chunk, a
reference a sub-chunk; the levels are three products of the whole chunk under
a mask). Backward makes every decay again from q, k and c. The other four
take the matrices as given: ``_fwd`` / ``_bwd`` in place of ``(K K^T) *
decay``, ``_scan_fwd`` / ``_scan_bwd`` in place of ``(Q K^T) * decay``;
``e^c``, ``e^(last - c)`` and the state's fade ``Diag(e^last) S`` are then (C,
Dk) blocks and a column a key, and dc is (C, Dk) a chunk, elementwise where
the scalar gate takes a row sum.

What crosses HBM between the six, and in which order they run. Forward:
``_grams_fwd`` reads q, k and ``g`` and takes ``c``, g's running sum from the
chunk's first token, on the (C, Dk) tile where it is first read (float32
additions: log2(C) steps of a roll over the sublanes and an add,
``_running_sum``); it writes ``c`` once, and the two matrices as pairs of
chunks side by side, (N / 2, C, 2C) float32 like ``X`` (32 MiB each a layer of
the Kimi-Linear cell). ``_fwd`` reads k, v, ``c``, beta and the first matrix
and writes ``U``, ``W`` and ``X``; ``_scan_fwd`` reads q, k, ``U``, ``W``,
``c`` and the second and writes the outputs and a start state a chunk. All
seven (``c``, both matrices, ``U``, ``W``, ``X``, the states) are the
residuals, and what the operator names (``registry.keep``). Backward runs
``_scan_bwd``, then ``_bwd``, then ``_grams_bwd``: the scan's hands on dU,
dW, the second matrix's cotangent and its shares of dq, dk and dc; ``_bwd``
takes that dk and dc as operands and writes them back, its own shares added
in float32 on the tile, into the buffers they came in
(``input_output_aliases``), with dv, dbeta and the first matrix's cotangent;
``_grams_bwd`` takes dq, dk and dc the same way, adds the matrices' shares,
and turns the summed dc into dg by the running sum up the tile's rows, the
transpose of forward's. dq and dk cross from kernel to kernel in the trunk's
dtype, rounded as each rule's own result was when ``jax.numpy`` added them;
dc in float32. A gate a head lowers to what it lowered to before a gate a
channel had kernels (``within_chunks`` and ``across_chunks``, a rule each,
its ``cumsum`` outside): the branch is on the rank of ``g``, at trace.

``plan`` is the one rule that says whether the kernels engage, all of them or
none, as ``flash_attention.plan`` is attention's; traced kernels are kept by
``pallas_support._kernel``'s store.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import pallas_support as _ps
from .registry import keep

_LANES = 128
_HIGHEST = lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))   # x @ y.T
_TN = (((0,), (0,)), ((), ()))   # x.T @ y
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


def plan(platform, vmem_bytes, dtype, Dk, Dv, group, chunk, T,
         channel_gate=False) -> Optional[Plan]:
    """The rule, of all the kernels: a node runs every one of them or
    none. They engage where the program is lowered for one TPU whose VMEM
    is known, the operands are bfloat16 (a float32 trunk keeps the
    ``jax.numpy`` form), ``Dk`` and ``Dv`` are multiples of 128, ``chunk``
    is one of ``_CHUNKS``, and what a grid step of ``_BLOCK`` chunks moves
    in the largest of the backward kernels (the chunk-local algebra's:
    operands, cotangents, inverses and results of the group; the scan's:
    q, k, ``U``, ``W``, the outputs' cotangent, a start state a chunk and
    the five results), twice for the pipeline's two buffers, is under half
    the VMEM. T is padded to whole grid steps by the caller (``padded``).
    None = the ``jax.numpy`` form. A gate a key channel (``channel_gate``)
    runs the same four kernels and the Gram matrices' two under the same
    conditions, reckoned with ``c`` and ``dc`` as (chunks, C, Dk) float32
    blocks and a chunk's Gram matrix and its cotangent beside them."""
    if platform != "tpu" or not vmem_bytes:
        return None
    if jnp.dtype(dtype) != jnp.bfloat16 or Dk % _LANES or Dv % _LANES:
        return None
    if chunk not in _CHUNKS:
        return None
    rows = _BLOCK * chunk
    # c and dc a row of the group; a gate a channel: dc as it comes and as
    # it leaves, a Gram matrix and its cotangent (the Gram kernels hold
    # both matrices, and q, k, dq, dk), and the dq and dk that come
    gate = (3 * Dk + 2 * chunk) * 4 if channel_gate else 2 * 4
    shares = rows * Dk * 2 if channel_gate else 0
    within = (2 * rows * Dk * 2 + shares              # k, dk
              + group * rows * (2 * Dv * 2 + Dk * 2   # v, dv, dw
                                + Dv * 4 + chunk * 4  # du, X
                                + 2 * 4 + gate))      # beta, dbeta
    scan = (4 * rows * Dk * 2                         # q, k, dq, dk
            + group * rows * (2 * Dv * 4 + 2 * Dk * 2  # u, du, w, dw
                              + Dv * 2 + gate)         # do
            + group * _BLOCK * Dk * Dv * 4)            # a start state a chunk
    grams = (4 * rows * Dk * 2 + 2 * shares
             + group * rows * (gate + 2 * chunk * 4)) if channel_gate else 0
    need = 2 * max(within, scan, grams) + group * Dk * Dv * 4  # the states
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


def _for_each(count, step):
    """``step(i)`` for i in 0 .. count - 1, in order, as one loop."""
    def body(i, carry):
        step(i)
        return carry

    lax.fori_loop(0, count, body, None)


def _specs(chunks, group, C, Dk, Dv, block=lambda i: i):
    """BlockSpecs over the grid (batch, key head, block of chunks), the grid
    step ``i`` taking the block ``block(i)`` (the scan's backward walks from
    the last): the key head's rows, a value-wide and a key-wide block of
    the group, a row of vectors and a (C, 2C) inverse a pair of chunks, a
    row of vectors a chunk, and a (Dk, Dv) state a chunk."""
    pl, _ = _ps._pallas()
    return dict(
        k=pl.BlockSpec((None, None, chunks, C, Dk),
                       lambda b, h, i: (b, h, block(i), 0, 0)),
        v=pl.BlockSpec((None, None, group, chunks, C, Dv),
                       lambda b, h, i: (b, h, 0, block(i), 0, 0)),
        w=pl.BlockSpec((None, None, group, chunks, C, Dk),
                       lambda b, h, i: (b, h, 0, block(i), 0, 0)),
        vec=pl.BlockSpec((None, None, group, chunks // 2, 2 * C),
                         lambda b, h, i: (b, h, 0, block(i), 0)),
        x=pl.BlockSpec((None, None, group, chunks // 2, C, 2 * C),
                       lambda b, h, i: (b, h, 0, block(i), 0, 0)),
        c=pl.BlockSpec((None, None, group, chunks, C),
                       lambda b, h, i: (b, h, 0, block(i), 0)),
        s=pl.BlockSpec((None, None, group, chunks, Dk, Dv),
                       lambda b, h, i: (b, h, 0, block(i), 0, 0)))


def _cost(k, v, matmuls, passes):
    """``matmuls`` (C x C) x (C x C) float32 products of six bfloat16
    passes a chunk and value head, ``passes`` over the wide operands."""
    pl, _ = _ps._pallas()
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


# --- a gate a key channel: the two Gram matrices ------------------------------
_SUB = 8    # tokens a sub-chunk: the sublanes of a float32 vector register


class _Gram(NamedTuple):
    """Index planes of a chunk's Gram matrix (C, C), of its tokens (C, 1)
    and of a sub-chunk's (1, _SUB, 1)."""

    row: jax.Array
    col: jax.Array
    token: jax.Array
    sub: jax.Array

    def column(self, j):
        """Where the column is token ``j`` of the row's own sub-chunk."""
        return self.col == (self.row & -_SUB) + j

    below = _Pair.below


def _gram_planes(C):
    return _Gram(lax.broadcasted_iota(jnp.int32, (C, C), 0),
                 lax.broadcasted_iota(jnp.int32, (C, C), 1),
                 lax.broadcasted_iota(jnp.int32, (C, 1), 0),
                 lax.broadcasted_iota(jnp.int32, (1, _SUB, 1), 1))


def _sub_chunks(x):
    """(C, D) -> (C / _SUB, _SUB, D): a vector register a sub-chunk."""
    return x.reshape(x.shape[0] // _SUB, _SUB, x.shape[1])


def _running_sum(x, p, reverse=False):
    """The inclusive running sum down a tile's rows, (C, D) float32 (up
    them: ``reverse``, its transpose), float32 additions: log2(C) steps of
    a roll over the sublanes, a mask and an add."""
    _, pltpu = _ps._pallas()
    C = x.shape[0]
    s = 1
    while s < C:
        shift, seen = (C - s, p.token < C - s) if reverse \
            else (s, p.token >= s)
        x = x + jnp.where(seen, pltpu.roll(x, shift, 0), 0.0)
        s *= 2
    return x


def _fade_from(c3, j, p):
    """``gated_delta._fade_from`` of every sub-chunk: ``exp(c_i - c_j)``
    for the rows ``i >= j``, 0 above, the difference masked before its
    ``exp``."""
    seen = p.sub >= j
    return jnp.where(
        seen, jnp.exp(jnp.where(seen, c3 - c3[:, j:j + 1], 0.0)), 0.0)


def _toward(c, s, p):
    """The decays of one level of the Gram matrices, (C, D): in each block
    of ``2s`` tokens the rows ``[s, 2s)`` meet the columns ``[0, s)`` at
    the reference ``r``, the first of those rows; a row's operand takes
    ``exp(c_i - c_r)``, a column's ``exp(c_r - c_j)``, and their product
    under the sum over the keys is ``exp(c_i - c_j)`` with neither exponent
    positive. Also whether a token is such a row (C, 1)."""
    C, D = c.shape
    later = (p.token & s) != 0
    apart = c - jnp.concatenate(
        [jnp.broadcast_to(c[b + s:b + s + 1], (2 * s, D))
         for b in range(0, C, 2 * s)], axis=0)
    return jnp.exp(jnp.where(later, apart, -apart)), later


def _chunk_grams(qb, kb, c, p):
    """``gated_delta._channel_grams`` of one chunk in VMEM: the strictly
    lower ``sum_d k_id k_jd exp(c_id - c_jd)`` and the lower ``sum_d q_id
    k_jd exp(c_id - c_jd)``, (C, C) float32 each. The diagonal blocks of
    ``_SUB`` tokens a column at a time in float32, all sub-chunks at once
    (``_diagonal_columns``); below them by levels, s = 8, 16, 32: one
    product of operands decayed toward a reference between them
    (``_toward``), rounded once, as operands."""
    C = c.shape[0]
    dt = kb.dtype
    qf, kf = qb.astype(jnp.float32), kb.astype(jnp.float32)
    q3, k3, c3 = _sub_chunks(qf), _sub_chunks(kf), _sub_chunks(c)
    kk = jnp.zeros((C, C), jnp.float32)
    qk = jnp.zeros((C, C), jnp.float32)
    for j in range(_SUB):
        col = k3[:, j:j + 1] * _fade_from(c3, j, p)
        at = p.column(j)
        kk = kk + jnp.where(
            at & (p.col < p.row),
            jnp.sum(k3 * col, axis=2, keepdims=True).reshape(C, 1), 0.0)
        qk = qk + jnp.where(
            at, jnp.sum(q3 * col, axis=2, keepdims=True).reshape(C, 1), 0.0)
    s = _SUB
    while s < C:
        toward, _ = _toward(c, s, p)
        keys = (kf * toward).astype(dt)
        both = _mm(jnp.concatenate([keys, (qf * toward).astype(dt)], axis=0),
                   keys, _NT)
        below = p.below(s)
        kk = kk + jnp.where(below, both[:C], 0.0)
        qk = qk + jnp.where(below, both[C:], 0.0)
        s *= 2
    return kk, qk


def _chunk_grams_bwd(qb, kb, c, dkk, dqk, p):
    """(dq, dk, dc) (C, D) float32 of ``_chunk_grams`` under the matrices'
    cotangents (C, C): every decay is made again from q, k and c. The
    diagonal blocks are ``gated_delta._diagonal_bwd``. A level's reference
    gets no gradient: the product of a pair's two decays does not depend
    on it."""
    C = c.shape[0]
    dt = kb.dtype
    qf, kf = qb.astype(jnp.float32), kb.astype(jnp.float32)
    q3, k3, c3 = _sub_chunks(qf), _sub_chunks(kf), _sub_chunks(c)
    dq3, dk3, dc3 = (jnp.zeros_like(c3) for _ in range(3))

    def column(x, at):
        return _sub_chunks(jnp.sum(jnp.where(at, x, 0.0), axis=1,
                                   keepdims=True))

    for j in range(_SUB):
        fade = _fade_from(c3, j, p)
        k_j = k3[:, j:j + 1]
        col = k_j * fade
        at = p.column(j)
        to_kk = column(dkk, at & (p.col < p.row))
        to_qk = column(dqk, at)
        d_col = (to_kk * k3 + to_qk * q3) * fade
        d_c = d_col * k_j
        # column j's own key and decays: row j of dk and of dc
        own = p.sub == j
        dq3 = dq3 + to_qk * col
        dk3 = dk3 + to_kk * col + jnp.where(
            own, jnp.sum(d_col, axis=1, keepdims=True), 0.0)
        dc3 = dc3 + d_c - jnp.where(
            own, jnp.sum(d_c, axis=1, keepdims=True), 0.0)
    dq, dk, dc = (x.reshape(c.shape) for x in (dq3, dk3, dc3))
    s = _SUB
    while s < C:
        toward, later = _toward(c, s, p)
        keys, queries = (kf * toward).astype(dt), (qf * toward).astype(dt)
        below = p.below(s)
        to_kk = jnp.where(below, dkk, 0.0).astype(dt)
        to_qk = jnp.where(below, dqk, 0.0).astype(dt)
        d_queries = _mm(to_qk, keys)
        d_keys = _mm(to_kk, keys) + _mm(to_kk, keys, _TN) \
            + _mm(to_qk, queries, _TN)
        d_toward = (d_keys * kf + d_queries * qf) * toward
        dq = dq + d_queries * toward
        dk = dk + d_keys * toward
        dc = dc + jnp.where(later, d_toward, -d_toward)
        s *= 2
    return dq, dk, dc


def _gram_cost(k, c, passes):
    pl, _ = _ps._pallas()
    C, Dk = c.shape[-2:]
    chunks = c.size // (C * Dk)
    return pl.CostEstimate(
        flops=chunks * passes * (3 * 2 * 2 * C * C * Dk + 12 * _SUB * C * Dk),
        transcendentals=chunks * passes * (_SUB + 3) * C * Dk,
        bytes_accessed=passes * (2 * k.size * 2 + c.size * 4)
        + chunks * 2 * C * C * 4)


@functools.partial(jax.jit, static_argnames=("chunks", "vmem_limit",
                                             "interpret"))
def _grams_fwd(q, k, g, *, chunks, vmem_limit, interpret):
    """(c, the decayed ``K K^T``, the decayed ``Q K^T``) of the log decay
    ``g`` (B, Hk, G, N, C, Dk) float32: ``c`` its running sum from each
    chunk's first token, in g's shape, taken on the chunk's tile where it
    is first read; the matrices (B, Hk, G, N / 2, C, 2C) float32 each, a
    pair of chunks side by side, as ``_fwd`` reads the first and keeps its
    inverses."""
    pl, pltpu = _ps._pallas()
    B, Hk, N, C, Dk = k.shape
    G = g.shape[2]

    def kernel(q_ref, k_ref, g_ref, c_ref, kk_ref, qk_ref):
        p = _gram_planes(C)

        def pair(i):
            for h in range(G):
                grams = []
                for n in (2 * i, 2 * i + 1):
                    c = _running_sum(g_ref[h, n], p)
                    c_ref[h, n] = c
                    grams.append(_chunk_grams(q_ref[n], k_ref[n], c, p))
                kk, qk = zip(*grams)
                kk_ref[h, i] = jnp.concatenate(kk, axis=1)
                qk_ref[h, i] = jnp.concatenate(qk, axis=1)

        _for_each(chunks // 2, pair)

    s = _specs(chunks, G, C, Dk, Dk)
    pairs = jax.ShapeDtypeStruct((B, Hk, G, N // 2, C, 2 * C), jnp.float32)
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(g.shape, jnp.float32), pairs, pairs),
        grid=(B, Hk, N // chunks),
        in_specs=[s["k"], s["k"], s["w"]],
        out_specs=[s["w"], s["x"], s["x"]],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=_gram_cost(k, g, passes=1),
        interpret=interpret,
        name="gated_delta_grams_fwd",
    )(q, k, g)


@functools.partial(jax.jit, static_argnames=("chunks", "vmem_limit",
                                             "interpret"))
def _grams_bwd(q, k, c, dkk, dqk, dq, dk, dc, *, chunks, vmem_limit,
               interpret):
    """(dq, dk, dg) in the operands' shapes and dtypes, the last of
    backward's kernels: ``dq``, ``dk`` and ``dc`` arrive holding the other
    kernels' shares (``_scan_bwd``'s and ``_bwd``'s) and leave, in the
    same buffers, with the matrices' added in float32 on the tile; ``dc``
    summed leaves as dg, its running sum up the chunk's rows (the
    transpose of ``_grams_fwd``'s)."""
    pl, pltpu = _ps._pallas()
    B, Hk, N, C, Dk = k.shape
    G = c.shape[2]

    def kernel(q_ref, k_ref, c_ref, dkk_ref, dqk_ref, dq_in, dk_in, dc_in,
               dq_ref, dk_ref, dg_ref):
        p = _gram_planes(C)

        def pair(i):
            for j in range(2):
                n = 2 * i + j
                dq = dq_in[n].astype(jnp.float32)
                dk = dk_in[n].astype(jnp.float32)
                for g in range(G):
                    dq_g, dk_g, dc_g = _chunk_grams_bwd(
                        q_ref[n], k_ref[n], c_ref[g, n],
                        dkk_ref[g, i][:, j * C:(j + 1) * C],
                        dqk_ref[g, i][:, j * C:(j + 1) * C], p)
                    dq, dk = dq + dq_g, dk + dk_g
                    dg_ref[g, n] = _running_sum(dc_in[g, n] + dc_g, p,
                                                reverse=True)
                dq_ref[n] = dq.astype(dq_ref.dtype)
                dk_ref[n] = dk.astype(dk_ref.dtype)

        _for_each(chunks // 2, pair)

    s = _specs(chunks, G, C, Dk, Dk)
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(c.shape, jnp.float32)),
        grid=(B, Hk, N // chunks),
        in_specs=[s["k"], s["k"], s["w"], s["x"], s["x"], s["k"], s["k"],
                  s["w"]],
        out_specs=[s["k"], s["k"], s["w"]],
        input_output_aliases={5: 0, 6: 1, 7: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=_gram_cost(k, c, passes=2),
        interpret=interpret,
        name="gated_delta_grams_bwd",
    )(q, k, c, dkk, dqk, dq, dk, dc)


# --- forward -----------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("chunks", "vmem_limit",
                                             "interpret"))
def _fwd(k, v, c, beta, *given, chunks, vmem_limit, interpret):
    """(U, W, X (B, Hk, G, N / 2, C, 2C) float32: the inverses of a pair of
    chunks side by side). A gate a channel: c (B, Hk, G, N, C, Dk) and one
    more operand, ``given`` = (its decayed ``K K^T``: ``_grams_fwd``'s)."""
    pl, pltpu = _ps._pallas()
    B, Hk, N, C, Dk = k.shape
    G, Dv = v.shape[2], v.shape[-1]
    channel = bool(given)

    def kernel(k_ref, v_ref, c_ref, beta_ref, *refs):
        if channel:
            gram_ref, *refs = refs
        u_ref, w_ref, x_ref = refs
        p = _pair_planes(C)

        def pair(i):
            at = pl.ds(i, 1)
            if channel:
                kf = [k_ref[2 * i + j].astype(jnp.float32) for j in range(2)]
            else:
                _, kf, kk = _keys(k_ref, i)
            for g in range(G):
                if channel:
                    betas, beta = _columns(beta_ref[g, at, :], p)
                    cs = [c_ref[g, 2 * i + j] for j in range(2)]
                    kd = gram_ref[g, i]
                else:
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
                    u_ref[g, n] = solved[:, :Dv]
                    w_ref[g, n] = solved[:, Dv:].astype(w_ref.dtype)

        _for_each(chunks // 2, pair)

    s = _specs(chunks, G, C, Dk, Dv)
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((B, Hk, G, N, C, Dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hk, G, N, C, Dk), k.dtype),
                   jax.ShapeDtypeStruct((B, Hk, G, N // 2, C, 2 * C),
                                        jnp.float32)),
        grid=(B, Hk, N // chunks),
        in_specs=[s["k"], s["v"]] + (
            [s["w"], s["vec"], s["x"]] if channel else [s["vec"], s["vec"]]),
        out_specs=[s["v"], s["w"], s["x"]],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=_cost(k, v, matmuls=10, passes=1),
        interpret=interpret,
        name="gated_delta_chunks_fwd",
    )(k, v, *((c, _pairs(beta), *given) if channel
              else (_pairs(c), _pairs(beta))))


# --- backward ----------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("chunks", "vmem_limit",
                                             "interpret"))
def _bwd(k, v, c, beta, x, du, dw, *given, chunks, vmem_limit, interpret):
    """(dk, dv, dc, dbeta) in the operands' shapes and dtypes. A gate a
    channel gives three more operands, ``given`` = (the Gram matrix
    ``_fwd`` read; dk, dc: ``_scan_bwd``'s shares), and has that matrix's
    cotangent too: dk and dc leave in the buffers they came in, with this
    kernel's shares added in float32 on the tile, dk without ``K K^T``'s,
    which is the Gram kernels' to add."""
    pl, pltpu = _ps._pallas()
    B, Hk, N, C, Dk = k.shape
    G, Dv = v.shape[2], v.shape[-1]
    channel = bool(given)

    def kernel(k_ref, v_ref, c_ref, beta_ref, x_ref, du_ref, dw_ref, *refs):
        if channel:
            gram_ref, dk_in, dc_in, *refs, dgram_ref = refs
        dk_ref, dv_ref, dc_ref, dbeta_ref = refs
        p = _pair_planes(C)

        def pair(i):
            if channel:
                kf = [k_ref[2 * i + j].astype(jnp.float32) for j in range(2)]
                dk = [dk_in[2 * i + j].astype(jnp.float32) for j in range(2)]
            else:
                kb, kf, kk = _keys(k_ref, i)
                dk = [jnp.zeros((C, Dk), jnp.float32) for _ in range(2)]
                dkk = jnp.zeros((C, 2 * C), jnp.float32)
            at = pl.ds(i, 1)
            for g in range(G):
                if channel:
                    betas, beta = _columns(beta_ref[g, at, :], p)
                    cs = [c_ref[g, 2 * i + j] for j in range(2)]
                    kd = gram_ref[g, i]
                else:
                    betas, beta, cs, decay, kd = _pair_head(
                        kk, c_ref[g, at, :], beta_ref[g, at, :], p)
                xt = x_ref[g, i].T                    # [[X0^T], [X1^T]]
                dx, dbeta, dc = [], [], []
                for j in range(2):
                    n = 2 * i + j
                    rise = jnp.exp(cs[j])
                    vf = v_ref[g, n].astype(jnp.float32)
                    d_solved = jnp.concatenate(
                        [du_ref[g, n], dw_ref[g, n].astype(jnp.float32)],
                        axis=1)
                    rhs = jnp.concatenate(
                        [vf * betas[j], kf[j] * (betas[j] * rise)], axis=1)
                    # d rhs = X^T d solved, dX = d solved rhs^T
                    d_rhs = _hi(xt[j * C:(j + 1) * C], d_solved)
                    dx.append(_hi(d_solved, rhs, _NT))
                    dv_rhs, dk_rhs = d_rhs[:, :Dv], d_rhs[:, Dv:]
                    if channel:     # W = X (beta e^c . K), a key at a time
                        key = dk_rhs * kf[j] * rise
                        dbeta.append(
                            jnp.sum(dv_rhs * vf, axis=1, keepdims=True)
                            + jnp.sum(key, axis=1, keepdims=True))
                        dc_ref[g, n] = dc_in[g, n] + betas[j] * key
                    else:
                        key = jnp.sum(dk_rhs * kf[j], axis=1, keepdims=True)
                        dbeta.append(
                            jnp.sum(dv_rhs * vf, axis=1, keepdims=True)
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
                if channel:     # L = beta x ``gram``, the decay inside it
                    dgram_ref[g, i] = dlow * beta
                    dbeta_ref[g, at, :] = _rows(
                        [a + b for a, b in zip(dbeta, _row_sums(moved, p))],
                        p)
                    continue
                faded = moved * beta           # dL x L
                dkk = dkk + dlow * beta * decay
                dbeta = [a + b for a, b in zip(dbeta, _row_sums(moved, p))]
                dc = [a + b for a, b in zip(dc, _row_sums(faded, p))]
                dbeta_ref[g, at, :] = _rows(dbeta, p)
                dc_ref[g, at, :] = _rows(dc, p) - jnp.sum(
                    faded, axis=0, keepdims=True)
            if channel:
                for j in range(2):
                    dk_ref[2 * i + j] = dk[j].astype(dk_ref.dtype)
                return
            # K K^T's own gradient, a product of the operands' dtype
            dkkt = dkk.T
            for j in range(2):
                both = (dkk[:, j * C:(j + 1) * C]
                        + dkkt[j * C:(j + 1) * C]).astype(kb[j].dtype)
                dk_ref[2 * i + j] = (dk[j] + jnp.dot(
                    both, kb[j], preferred_element_type=jnp.float32)).astype(
                        dk_ref.dtype)

        _for_each(chunks // 2, pair)

    s = _specs(chunks, G, C, Dk, Dv)
    gate = s["w"] if channel else s["vec"]
    dk, dv, dc, dbeta, *dgram = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((c if channel else _pairs(c)).shape,
                                        jnp.float32),
                   jax.ShapeDtypeStruct(_pairs(beta).shape, jnp.float32))
        + ((jax.ShapeDtypeStruct(given[0].shape, jnp.float32),) if channel
           else ()),
        grid=(B, Hk, N // chunks),
        in_specs=[s["k"], s["v"], gate, s["vec"], s["x"], s["v"], s["w"]]
        + [s["x"], s["k"], gate] * channel,
        out_specs=[s["k"], s["v"], gate, s["vec"]] + [s["x"]] * channel,
        input_output_aliases={8: 0, 9: 2} if channel else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=_cost(k, v, matmuls=4, passes=2),
        interpret=interpret,
        name="gated_delta_chunks_bwd",
    )(k, v, c if channel else _pairs(c), _pairs(beta), x, du, dw, *given)
    return (dk, dv, dc.reshape(c.shape), dbeta.reshape(beta.shape), *dgram)


# --- the scan over chunks ----------------------------------------------------
def _mm(a, b, dims=(((1,), (0,)), ((), ()))):
    """A product of the trunk's dtype with float32 accumulation:
    ``gated_delta._chunk_step``'s ``mm``, the casts made by the caller."""
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


class _Chunk(NamedTuple):
    """Index planes of one chunk (C, C) and what they make of a row."""

    eye: jax.Array
    seen: jax.Array    # the column's token is the row's or before it
    final: jax.Array   # (1, C): the chunk's last token

    def column(self, x):
        """A row (1, C) -> the column (C, 1), exactly."""
        return jnp.sum(jnp.where(self.eye, x, 0.0), axis=1, keepdims=True)

    def row(self, x):
        """A column (C, 1) -> the row (1, C), exactly."""
        return jnp.sum(jnp.where(self.eye, x, 0.0), axis=0, keepdims=True)

    def decays(self, c_row):
        """Of a head's cumulative log decay (1, C): it as a column, its
        last entry (1, 1), and ``exp(c_i - c_j)`` where j <= i, masked
        before the ``exp`` (``gated_delta._decay``)."""
        c = self.column(c_row)
        last = jnp.sum(jnp.where(self.final, c_row, 0.0), axis=1,
                       keepdims=True)
        decay = jnp.where(
            self.seen, jnp.exp(jnp.where(self.seen, c - c_row, 0.0)), 0.0)
        return c, last, decay


def _chunk_planes(C):
    row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    lane = lax.broadcasted_iota(jnp.int32, (1, C), 1)
    return _Chunk(row == col, col <= row, lane == C - 1)


def _channel_decays(c):
    """Of a head's cumulative log decay a key channel (C, Dk): it, its last
    row (1, Dk), and that row as a column (Dk, 1), which fades the state's
    rows."""
    C = c.shape[0]
    return c, c[C - 1:], c.T[:, C - 1:]


def _to_pair(ref, g, n, x):
    """Chunk ``n``'s (C, C) into its half of the pair that holds it."""
    pl, _ = _ps._pallas()
    C = x.shape[0]
    for j in range(2):
        @pl.when(n % 2 == j)
        def _(j=j):
            ref[g, n // 2, :, j * C:(j + 1) * C] = x


def _of_pair(pair, n):
    """Chunk ``n``'s (C, C) of the pair (C, 2C) that holds it."""
    C = pair.shape[0]
    return jnp.where(n % 2 == 0, pair[:, :C], pair[:, C:])


def _scan_cost(q, u, matmuls, passes):
    """``matmuls`` (C x D) x (D x D) products a chunk and value head,
    ``passes`` over the operands and a start state a chunk."""
    pl, _ = _ps._pallas()
    B, Hk, G, N, C, Dv = u.shape
    Dk = q.shape[-1]
    heads = B * Hk * G * N
    return pl.CostEstimate(
        flops=heads * matmuls * 2 * C * Dk * Dv,
        transcendentals=heads * C * (C + 2),
        bytes_accessed=passes * (2 * q.size * 2 + u.size * 4
                                 + heads * C * (2 * Dk + 2 * Dv))
        + heads * Dk * Dv * 4)


@functools.partial(jax.jit, static_argnames=("chunks", "vmem_limit",
                                             "interpret"))
def _scan_fwd(q, k, u, w, c, *given, chunks, vmem_limit, interpret):
    """(the outputs (B, Hk, G, N, C, Dv) in q's dtype, the state every
    chunk STARTED from (B, Hk, G, N, Dk, Dv) float32): ``_chunk_step`` over
    the chunks of a (batch, key head) in order, the group's states in VMEM
    scratch from the first block of chunks to the last. A gate a channel: c
    (B, Hk, G, N, C, Dk) and one more operand, ``given`` = (its decayed ``Q
    K^T``: ``_grams_fwd``'s)."""
    pl, pltpu = _ps._pallas()
    B, Hk, N, C, Dk = q.shape
    G, Dv = u.shape[2], u.shape[-1]
    dt = q.dtype
    channel = bool(given)

    def kernel(q_ref, k_ref, u_ref, w_ref, c_ref, *refs):
        if channel:
            gram_ref, *refs = refs
        o_ref, s_ref, state = refs

        @pl.when(pl.program_id(2) == 0)
        def _():
            state[...] = jnp.zeros_like(state)

        p = _chunk_planes(C)

        def chunk(n):
            qb, kb = q_ref[n], k_ref[n]
            qf, kf = qb.astype(jnp.float32), kb.astype(jnp.float32)
            if not channel:
                qk = _mm(qb, kb, _NT)
            for g in range(G):
                if channel:
                    cs, last, gain = _channel_decays(c_ref[g, n])
                else:
                    cs, last, decay = p.decays(c_ref[g, pl.ds(n, 1), :])
                    gain = last
                s = state[g]
                s_ref[g, n] = s
                sb = s.astype(dt)
                written = (u_ref[g, n] - _mm(w_ref[g, n], sb)).astype(dt)
                out = _mm((qf * jnp.exp(cs)).astype(dt), sb) + _mm(
                    (_of_pair(gram_ref[g, n // 2], n) if channel
                     else qk * decay).astype(dt), written)
                o_ref[g, n] = out.astype(o_ref.dtype)
                state[g] = s * jnp.exp(gain) + _mm(
                    (kf * jnp.exp(last - cs)).astype(dt), written, _TN)

        _for_each(chunks, chunk)

    s = _specs(chunks, G, C, Dk, Dv)
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(u.shape, dt),
                   jax.ShapeDtypeStruct((B, Hk, G, N, Dk, Dv), jnp.float32)),
        grid=(B, Hk, N // chunks),
        in_specs=[s["k"], s["k"], s["v"], s["w"]] + (
            [s["w"], s["x"]] if channel else [s["c"]]),
        out_specs=[s["v"], s["s"]],
        scratch_shapes=[pltpu.VMEM((G, Dk, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=_scan_cost(q, u, matmuls=4, passes=1),
        interpret=interpret,
        name="gated_delta_scan_fwd",
    )(q, k, u, w, c, *given)


@functools.partial(jax.jit, static_argnames=("chunks", "vmem_limit",
                                             "interpret"))
def _scan_bwd(q, k, u, w, c, states, do, *given, chunks, vmem_limit,
              interpret):
    """(dq, dk, dU, dW, dc) in the operands' shapes and dtypes: the chunks
    of a (batch, key head) from the last to the first, the cotangent of
    the group's states in VMEM scratch; a chunk's forward values are made
    again from the state it started from. With ``V' = U - W S``, ``A = (Q
    K^T) * decay``, ``O = (e^c Q) S + A V'`` and ``S' = e^last S + (e^(last
    - c) K)^T V'``, every product's operands cast as forward casts them::

        dV' = A^T dO + (e^(last - c) K) dS'        dU = dV'   dW = -dV' S^T
        dS  = e^last dS' + (e^c Q)^T dO - W^T dV'
        dA  = dO V'^T     d(Q K^T) = sum over the group of dA * decay
        dc  = rows(dA * A) - columns(dA * A) + e^c (dO S^T . Q)
              - e^(last - c) (V' dS'^T . K), and at the chunk's last token
              + e^last (dS' . S) + sum(e^(last - c) (V' dS'^T . K))

    A gate a channel (``given``: ``_scan_fwd``'s) has ``A`` given: dA is a
    sixth result, the cotangent of that operand, and its shares of dq, dk and
    dc are the Gram kernels' to give; the sums over the keys in dc's other
    terms are not taken (``.`` is then elementwise, a key at a time)."""
    pl, pltpu = _ps._pallas()
    B, Hk, N, C, Dk = q.shape
    G, Dv = u.shape[2], u.shape[-1]
    dt = q.dtype
    blocks = N // chunks
    channel = bool(given)

    def kernel(q_ref, k_ref, u_ref, w_ref, c_ref, s_ref, do_ref, *refs):
        if channel:
            gram_ref, *refs, dgram_ref, dstate = refs
        else:
            *refs, dstate = refs
        dq_ref, dk_ref, du_ref, dw_ref, dc_ref = refs

        @pl.when(pl.program_id(2) == 0)
        def _():
            dstate[...] = jnp.zeros_like(dstate)

        p = _chunk_planes(C)

        def total(x):
            return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0,
                           keepdims=True)

        def chunk(i):
            n = chunks - 1 - i
            qb, kb = q_ref[n], k_ref[n]
            qf, kf = qb.astype(jnp.float32), kb.astype(jnp.float32)
            if not channel:
                qk = _mm(qb, kb, _NT)
                dqk = jnp.zeros((C, C), jnp.float32)
            dq = jnp.zeros((C, Dk), jnp.float32)
            dk = jnp.zeros((C, Dk), jnp.float32)
            for g in range(G):
                if channel:
                    cs, last, gain = _channel_decays(c_ref[g, n])
                else:
                    cs, last, decay = p.decays(c_ref[g, pl.ds(n, 1), :])
                    gain = last
                rise, fade, gain = (jnp.exp(cs), jnp.exp(last - cs),
                                    jnp.exp(gain))
                s, ds = s_ref[g, n], dstate[g]
                sb, dsb = s.astype(dt), ds.astype(dt)
                wb, gb = w_ref[g, n], do_ref[g, n]
                written = (u_ref[g, n] - _mm(wb, sb)).astype(dt)
                a = _of_pair(gram_ref[g, n // 2], n) if channel \
                    else qk * decay
                risen, faded = (qf * rise).astype(dt), (kf * fade).astype(dt)
                d_risen = _mm(gb, sb, _NT)
                d_faded = _mm(written, dsb, _NT)
                da = _mm(gb, written, _NT)
                d_written = _mm(a.astype(dt), gb, _TN) + _mm(faded, dsb)
                du_ref[g, n] = d_written
                d_written = d_written.astype(dt)
                dw_ref[g, n] = (-_mm(d_written, sb, _NT)).astype(dw_ref.dtype)
                dstate[g] = ds * gain + _mm(risen, gb, _TN) \
                    - _mm(wb, d_written, _TN)
                if channel:
                    d_rise, d_fade = d_risen * rise, d_faded * fade
                    dq, dk = dq + d_rise, dk + d_fade
                    d_fade = d_fade * kf
                    # the last token's row: what fades the state, a key at
                    # a time, and every key operand's share of ``last``
                    d_last = jnp.sum((ds * s).T, axis=0, keepdims=True) \
                        * jnp.exp(last) + jnp.sum(d_fade, axis=0,
                                                  keepdims=True)
                    dc_ref[g, n] = d_rise * qf - d_fade + jnp.where(
                        lax.broadcasted_iota(jnp.int32, (C, 1), 0) == C - 1,
                        d_last, 0.0)
                    _to_pair(dgram_ref, g, n, da)
                    continue
                moved = da * a
                d_rise = jnp.sum(d_risen * qf, axis=1, keepdims=True) * rise
                d_fade = jnp.sum(d_faded * kf, axis=1, keepdims=True) * fade
                d_last = total(ds * s) * gain + jnp.sum(
                    d_fade, axis=0, keepdims=True)
                dc_ref[g, pl.ds(n, 1), :] = p.row(
                    d_rise - d_fade + jnp.sum(moved, axis=1, keepdims=True)) \
                    - jnp.sum(moved, axis=0, keepdims=True) \
                    + jnp.where(p.final, d_last, 0.0)
                dqk = dqk + da * decay
                dq = dq + d_risen * rise
                dk = dk + d_faded * fade
            if channel:
                dq_ref[n] = dq.astype(dq_ref.dtype)
                dk_ref[n] = dk.astype(dk_ref.dtype)
                return
            dqk = dqk.astype(dt)
            dq_ref[n] = (dq + _mm(dqk, kb)).astype(dq_ref.dtype)
            dk_ref[n] = (dk + _mm(dqk, qb, _TN)).astype(dk_ref.dtype)

        _for_each(chunks, chunk)

    s = _specs(chunks, G, C, Dk, Dv, lambda i: blocks - 1 - i)
    gate = s["w"] if channel else s["c"]
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(q.shape, dt),
                   jax.ShapeDtypeStruct(k.shape, dt),
                   jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct(w.shape, w.dtype),
                   jax.ShapeDtypeStruct(c.shape, jnp.float32))
        + ((jax.ShapeDtypeStruct(given[0].shape, jnp.float32),) if channel
           else ()),
        grid=(B, Hk, blocks),
        in_specs=[s["k"], s["k"], s["v"], s["w"], gate, s["s"], s["v"]]
        + [s["x"]] * channel,
        out_specs=[s["k"], s["k"], s["v"], s["w"], gate] + [s["x"]] * channel,
        scratch_shapes=[pltpu.VMEM((G, Dk, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=_scan_cost(q, u, matmuls=10, passes=2),
        interpret=interpret,
        name="gated_delta_scan_bwd",
    )(q, k, u, w, c, states, do, *given)


# --- what chunk_gated_delta_rule calls ----------------------------------------
def _static(plan, interpret):
    return dict(chunks=plan.chunks, vmem_limit=plan.vmem_limit,
                interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def within_chunks(k, v, c, beta, plan, interpret=False):
    """(U, W) with a gate a head: the forward kernel at ``plan``'s block. N
    is a multiple of ``plan.chunks`` (``padded``). Backward keeps the
    operands and the chunks' inverses; under per-operator recomputation
    (``MXNET_BACKWARD_DO_MIRROR``) the inverses, ``U`` and ``W`` are what
    the operator names (``registry.keep``), so that the forward kernel runs
    once a step and not again in backward. ``interpret`` runs the kernels
    in Pallas's interpreter (tests on the CPU)."""
    return _within_fwd(k, v, c, beta, plan, interpret)[0]


def _within_fwd(k, v, c, beta, plan, interpret):
    # the scan over chunks reads u and w again in its backward, this
    # rule's backward x: kept under per-operator recomputation
    u, w, x = keep(_ps._kernel(_fwd, (k, v, c, beta),
                                **_static(plan, interpret)))
    return (u, w), (k, v, c, beta, x)


def _within_bwd(plan, interpret, res, g):
    return _ps._kernel(_bwd, (*res, *g), **_static(plan, interpret))


within_chunks.defvjp(_within_fwd, _within_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def across_chunks(q, k, u, w, c, plan, interpret=False):
    """The outputs (B, Hk, G, N, C, Dv) of the scan over chunks with a gate
    a head: q, k (B, Hk, N, C, Dk), ``within_chunks``' ``U`` and ``W`` and c
    (B, Hk, G, N, C) float32, the state 0 before a row's first chunk: the
    forward kernel at ``plan``'s block. Backward keeps the operands and the
    state every chunk started from, which the operator names
    (``registry.keep``): under per-operator recomputation the forward
    kernel runs once a step."""
    return _across_fwd(q, k, u, w, c, plan, interpret)[0]


def _across_fwd(q, k, u, w, c, plan, interpret):
    out, states = _ps._kernel(_scan_fwd, (q, k, u, w, c),
                               **_static(plan, interpret))
    return out, (q, k, u, w, c, keep(states))


def _across_bwd(plan, interpret, res, g):
    return _ps._kernel(_scan_bwd, (*res, g), **_static(plan, interpret))


across_chunks.defvjp(_across_fwd, _across_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def channel_gated(q, k, v, g, beta, plan, interpret=False):
    """The outputs (B, Hk, G, N, C, Dv) with a gate a key channel, all six
    kernels under this one rule: q, k (B, Hk, N, C, Dk), v (B, Hk, G, N, C,
    Dv), the log decay g (B, Hk, G, N, C, Dk) and beta (B, Hk, G, N, C),
    both float32; N a multiple of ``plan.chunks`` (``padded``). Forward
    runs ``_grams_fwd`` (which takes g's running sum ``c``), ``_fwd`` and
    ``_scan_fwd``. Backward keeps the operands and what the kernels made
    of them, ``c``, the two Gram matrices, ``U``, ``W``, the chunks'
    inverses and the state every chunk started from: all the operator
    names (``registry.keep``), so that under per-operator recomputation
    (``MXNET_BACKWARD_DO_MIRROR``) no forward kernel runs again. It runs
    ``_scan_bwd``, ``_bwd`` and ``_grams_bwd`` in that order, each handed
    the dq, dk and dc of those before it to add its own to on the tile,
    and the last turns dc into dg: no cotangent is summed, and no running
    sum taken, outside a kernel."""
    return _channel_fwd(q, k, v, g, beta, plan, interpret)[0]


def _channel_fwd(q, k, v, g, beta, plan, interpret):
    static = _static(plan, interpret)
    c, kk, qk = keep(_ps._kernel(_grams_fwd, (q, k, g), **static))
    u, w, x = keep(_ps._kernel(_fwd, (k, v, c, beta, kk), **static))
    out, states = _ps._kernel(_scan_fwd, (q, k, u, w, c, qk), **static)
    return out, (q, k, v, beta, c, kk, qk, u, w, x, keep(states))


def _channel_bwd(plan, interpret, res, do):
    q, k, v, beta, c, kk, qk, u, w, x, states = res
    static = _static(plan, interpret)
    dq, dk, du, dw, dc, dqk = _ps._kernel(
        _scan_bwd, (q, k, u, w, c, states, do, qk), **static)
    dk, dv, dc, dbeta, dkk = _ps._kernel(
        _bwd, (k, v, c, beta, x, du, dw, kk, dk, dc), **static)
    dq, dk, dg = _ps._kernel(
        _grams_bwd, (q, k, c, dkk, dqk, dq, dk, dc), **static)
    return dq, dk, dv, dg, dbeta


channel_gated.defvjp(_channel_fwd, _channel_bwd)
