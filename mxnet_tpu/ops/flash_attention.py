"""One-device attention as Pallas TPU kernels: a (queries x keys) score tile
lives only in VMEM.

``attention(q, k, v, plan, ...)`` and ``attention_grads(...)`` are the forward
and backward of ``parallel/ring_attention.blockwise_attention`` where the rule
(``plan``) says so: q (B, H, T, Dk), k (B, Hkv, T, Dk) and v (B, Hkv, T, Dv),
Hkv dividing H. There are two widths: the scores contract over the key's Dk,
the output and ``p @ v`` run at the value's Dv (a latent-attention head
scores over 192 = 128 + 64 rotated and weighs values of 128); the program
pads neither (the v5e's own tiled layout stores a minor dimension of 192 in
256 lanes). The mathematics is ``_softmax_block``'s and
``_blockwise_bwd``'s: bfloat16 operands on the MXU with float32 accumulation;
scale, max, ``exp`` and sums in float32; ``p`` and ``ds`` cast to the
operands' dtype only for their matmuls; the residuals are q, k, v, the output
and the rows' log-sum-exp.

What the kernels do that the ``jax.numpy`` blocks do not:

* One grid step is one block of ``bq`` positions of the G = H / Hkv query
  heads that share a key/value head, G * bq rows of the same matmuls against
  a key/value block (the fold of ``ring_attention._fold``, with no repeated
  copy); its scores, mask, running max, ``exp``, normaliser and ``p @ v``
  run over the key blocks in a loop inside the step. No score tile reaches
  HBM, forward or backward.
* The keys and values of a key/value head (T x D each) stay in VMEM over the
  head's query blocks. Backward is one kernel: ``dk`` and ``dv`` of the head
  accumulate in float32 VMEM over its query blocks (summed over the group by
  the fold) and ``dq`` over a query block's key blocks, so the scores are
  recomputed once and five matmuls run a tile, not seven. It scores
  transposed tiles (keys x rows) so that ``dv`` and ``dk`` are plain
  matmuls; ``dq`` contracts the tile's first axis.
* The block plan is a visit list (``visits``: first and end key block of a
  query block, scalar-prefetched): key blocks above the diagonal and outside
  the band are never visited, and only a block the diagonal or the band's
  edge cuts applies a mask.

``plan`` is the one rule that says whether the kernels engage and with which
tiles, from what is observable where the op is traced: the platform its
program is lowered for (the executor's context, ``OpMode.platform``), the one
TPU the process holds, the operands' dtype and shapes. Traced kernels are
kept by ``pallas_support._kernel``'s store.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import pallas_support as _ps

_LANES = 128
# A masked score: finite, so that a row whose first visited block is wholly
# outside its band (max still this value, p = 1) is wiped by
# exp(_MASKED - a real max) = 0 when its first real key arrives, with no
# guard on the way. Every causal row sees its own position.
_MASKED = float(-0.7 * np.finfo(np.float32).max)
_NT = (((1,), (1,)), ((), ()))   # x @ y.T
_TN = (((0,), (0,)), ((), ()))   # x.T @ y
# Tiles, measured on a v5e at T 4096, head 128 (PERF.md section 6, PR 33;
# forward / forward + backward ms). Positions a query block: 32 heads over
# 4, full causal, at 64 / 128 / 256: 1.19 / 3.60, 1.18 / 3.49, 1.20 / 3.46,
# and under a band of 2048 keys at 128 / 256: 1.05 / 2.90, 0.90 / 2.70; 16
# heads over 16 at 512 / 1024 / 2048: 0.60 / 1.79, 0.66 / 1.91, 0.78 / 2.25
# (a wider block scores more of the triangle's far side): the widest of
# ``_POSITIONS`` whose tile over the group has at most ``_ROWS`` rows.
# Powers of two only: a row's position is ``row & (bq - 1)``, and a block of
# under 128 positions would be thousands of grid steps. Splitting a tile's
# rows into independent strips moved nothing either way (-8% to +10%).
_ROWS = 2048
_POSITIONS = (512, 256, 128)
# Keys a block, widest first. Full causal at 256 / 512 / 1024: 1.28 / 3.54,
# 1.18 / 3.49, 1.34 / 3.87. Under a band of 2048 keys, 256 / 512: 1.05 /
# 2.92, 1.05 / 3.06: the block the band's edge cuts is scored whole, so a
# band holds at least ``_BAND_BLOCKS`` key blocks.
_KEY_BLOCKS = (512, 256, 128)
_BAND_BLOCKS = 8


class Plan(NamedTuple):
    """Tiles of one attention layer: ``bq`` positions a query block (its
    tile has ``group * bq`` rows), ``bk`` keys a block."""

    bq: int
    bk: int
    vmem_limit: int


def plan(platform, vmem_bytes, dtype, heads, kv_heads, T, D, causal=True,
         window=0, value_dim=None, select_top_k=0) -> Optional[Plan]:
    """The rule. ``D`` is the width of queries and keys, ``value_dim`` that
    of the values and the output (None: ``D``). The kernels engage where
    the program is lowered for one TPU whose VMEM is known, the operands
    are bfloat16 (a float32 trunk keeps the ``jax.numpy`` blocks at
    ``precision=HIGHEST``), the value width is a multiple of 128 and the
    key width one of 128 or of 128 plus a half (192: a half tile of lanes
    is the narrowest Mosaic contracts over unpadded), the key/value heads
    divide the query heads, T is a multiple of a key block, and what
    backward keeps in VMEM for one key/value head (k and dk, v and dv in
    and out, float32 accumulators: 12 T bytes a lane of either width as
    the VMEM holds it, in whole tiles of 128, beside the tiles; 24 T D at
    one width) is under half of it. Tiles: the widest key
    block of ``_KEY_BLOCKS`` dividing T of which a band holds
    ``_BAND_BLOCKS``; the widest query block of ``_POSITIONS`` dividing T
    whose tile over the group (any group: 7 query heads a key/value head
    are 7 x 256 rows) has at most ``_ROWS`` rows, else the narrowest; if
    that does not fit the VMEM, the next narrower query blocks.
    A selection (``select_top_k``: each query keeps its own keys, chosen
    from scores computed in the program) is no visit list of whole key
    blocks: the kernels do not take one.
    None = the ``jax.numpy`` blocks."""
    if platform != "tpu" or not vmem_bytes or select_top_k:
        return None
    value_dim = value_dim or D
    if jnp.dtype(dtype) != jnp.bfloat16 or heads % kv_heads:
        return None
    if value_dim % _LANES or D % _LANES not in (0, _LANES // 2) or D < _LANES:
        return None
    if window and not causal:
        return None
    blocks = [b for b in _KEY_BLOCKS if T % b == 0]
    if not blocks:
        return None
    bk = next((b for b in blocks if not window or window >= _BAND_BLOCKS * b),
              blocks[-1])
    group = heads // kv_heads
    fit = [b for b in _POSITIONS if T % b == 0]   # 128 does: a key block does
    widest = next((b for b in fit if group * b <= _ROWS), fit[-1])
    # where that tile does not fit beside a long and wide head (T 8192 at
    # head 256: 50 MB of keys, values and their gradients), the next
    # narrower query blocks
    lanes = -(-D // _LANES) * _LANES + value_dim   # of both, as VMEM pads
    for bq in (b for b in fit if b <= widest):
        rows = group * bq
        need = 12 * T * lanes + 8 * rows * lanes + 6 * rows * bk * 4
        if need <= vmem_bytes // 2:
            return Plan(bq, bk, min(vmem_bytes * 3 // 4, need + (16 << 20)))
    return None


def visits(T, bq, bk, causal, window=0):
    """(first, end) int32 arrays over the query blocks: the key blocks
    ``first[i] <= j < end[i]`` are the ones query block ``i`` scores. A
    causal block stops at the key block that holds its last position; under
    a ``window`` it starts at the one that holds the first key of its first
    position's band."""
    a = np.arange(0, T, bq)
    first = np.maximum(0, a - window + 1) // bk if window \
        else np.zeros_like(a)
    end = (a + bq - 1) // bk + 1 if causal else np.full_like(a, T // bk)
    return first.astype(np.int32), end.astype(np.int32)


def scored_pairs(T, bq, bk, causal, window=0):
    """Query-key pairs one head scores under ``visits``, forward."""
    first, end = visits(T, bq, bk, causal, window)
    return int(bq * bk * (end - first).sum())


def _for_the_key_blocks(first, end, i, bq, bk, rows_axis, shape, causal,
                        window, step):
    """``step(j, mask)`` for each key block ``first <= j < end`` of query
    block ``i``: ``mask`` is None for a block neither the diagonal nor the
    band's edge cuts, else a function of the scores' tile that writes
    ``_MASKED`` where a query may not see a key. ``shape`` is the tile's,
    with its rows (G x bq, position = row mod bq) on axis ``rows_axis``."""
    pl, _ = _ps._pallas()
    if bq & (bq - 1):
        raise ValueError(f"attention: {bq} positions a query block, not a "
                         "power of two")
    if causal:
        # position of the row less position of the key, at i = j = 0
        apart = (lax.broadcasted_iota(jnp.int32, shape, rows_axis)
                 & (bq - 1)) \
            - lax.broadcasted_iota(jnp.int32, shape, 1 - rows_axis)

    def body(j, carry):
        if not causal:
            step(j, None)
            return carry
        # query positions i*bq .. i*bq + bq - 1, keys j*bk .. j*bk + bk - 1
        shift = i * bq - j * bk
        cut = shift < bk - 1                       # a key after a query
        if window:
            cut = jnp.logical_or(cut, shift + bq - 1 >= window)

        def mask(s):
            ok = apart >= -shift
            if window:
                ok = jnp.logical_and(ok, apart < window - shift)
            return jnp.where(ok, s, _MASKED)

        @pl.when(cut)
        def _():
            step(j, mask)

        @pl.when(jnp.logical_not(cut))
        def _():
            step(j, None)

        return carry

    lax.fori_loop(first, end, body, None)


def _block_specs(group, bq, T):
    """BlockSpecs over the grid (batch, key/value head, query block):
    ``folded(D)``, a query block of the head's group folded to rows, and
    ``whole(D)``, the head's whole keys or values, each at the width it is
    asked for; and a row of lanes a query block (log-sum-exp, delta)."""
    pl, _ = _ps._pallas()

    def folded(D):
        return pl.BlockSpec((None, None, group, bq, D),
                            lambda b, h, i, *_: (b, h, 0, i, 0))

    def whole(D):
        return pl.BlockSpec((None, None, T, D),
                            lambda b, h, i, *_: (b, h, 0, 0))

    return folded, whole, pl.BlockSpec((None, None, None, 1, group * bq),
                                       lambda b, h, i, *_: (b, h, i, 0, 0))


# --- forward -----------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "window", "bq", "bk", "vmem_limit", "interpret"))
def _fwd(q, k, v, first, end, *, scale, causal, window, bq, bk, vmem_limit,
         interpret):
    """(out (B, H, T, Dv) in q's dtype, log-sum-exp (B, H, T) float32)."""
    pl, pltpu = _ps._pallas()
    B, H, T, D = q.shape
    kv, Dv = k.shape[1], v.shape[-1]
    group, nq = H // kv, T // bq
    rows = group * bq

    def kernel(first_ref, end_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
               m_ref, l_ref, acc_ref):
        i = pl.program_id(2)
        qb = q_ref[...].reshape(rows, D)
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def step(j, mask):
            at = pl.ds(pl.multiple_of(j * bk, bk), bk)
            s = lax.dot_general(qb, k_ref[at, :], _NT,
                                preferred_element_type=jnp.float32) * scale
            if mask is not None:
                s = mask(s)
            # m and l are kept across the 128 lanes of a row
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, s.max(axis=-1)[:, None])
            p = jnp.exp(s - jnp.tile(m_new, (1, bk // _LANES)))
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1)[:, None]
            m_ref[...] = m_new
            acc_ref[...] = jnp.tile(alpha, (1, Dv // _LANES)) * acc_ref[...] \
                + lax.dot_general(p.astype(v_ref.dtype), v_ref[at, :],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

        _for_the_key_blocks(first_ref[i], end_ref[i], i, bq, bk, 0,
                            (rows, bk), causal, window, step)
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] * jnp.tile(1.0 / l, (1, Dv // _LANES))) \
            .astype(o_ref.dtype).reshape(group, bq, Dv)
        # the rows' log-sum-exp, from a column to a row of lanes
        lse_ref[...] = jnp.transpose(m_ref[...] + jnp.log(l))[:1, :]

    folded, whole, row = _block_specs(group, bq, T)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((B, kv, group, T, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B, kv, nq, 1, rows), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[folded(D), whole(D), whole(Dv)],
            out_specs=[folded(Dv), row],
            grid=(B, kv, nq),
            scratch_shapes=[pltpu.VMEM((rows, _LANES), jnp.float32),
                            pltpu.VMEM((rows, _LANES), jnp.float32),
                            pltpu.VMEM((rows, Dv), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=_cost(q, k, v, bq, bk, causal, window, 1, 1),
        interpret=interpret,
        name="attention_fwd",
    )(first, end, q.reshape(B, kv, group, T, D), k, v)
    return out.reshape(B, H, T, Dv), _rows_to_heads(lse, H)


def _cost(q, k, v, bq, bk, causal, window, over_keys, over_values):
    """A kernel's matmuls a scored pair, at their two widths: ``over_keys``
    run at the key's (q.k, and backward dk and dq), ``over_values`` at the
    value's (p.v, and backward d_out.v and dv); the bytes are the tensors
    of either width, each read or written once a matmul of its width."""
    pl, _ = _ps._pallas()
    B, H, T, D = q.shape
    Dv = v.shape[-1]
    pairs = B * H * scored_pairs(T, bq, bk, causal, window)
    return pl.CostEstimate(
        flops=2 * pairs * (over_keys * D + over_values * Dv),
        transcendentals=pairs,
        bytes_accessed=(over_keys * (q.size + 2 * k.size) + over_values
                        * (q.size // D * Dv + 2 * v.size))
        * q.dtype.itemsize)


def _rows_to_heads(x, H):
    """(B, Hkv, query blocks, 1, G x bq), a row of lanes a query block as
    the kernels read and write it -> (B, H, T)."""
    B, kv, nq, _, rows = x.shape
    group = H // kv
    return x.reshape(B, kv, nq, group, rows // group).transpose(
        0, 1, 3, 2, 4).reshape(B, H, -1)


def _heads_to_rows(x, kv, bq):
    B, H, T = x.shape
    group = H // kv
    return x.reshape(B, kv, group, T // bq, bq).transpose(
        0, 1, 3, 2, 4).reshape(B, kv, T // bq, 1, group * bq)


# --- backward ----------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "window", "bq", "bk", "vmem_limit", "interpret"))
def _bwd(q, k, v, out, lse, d_out, first, end, *, scale, causal, window, bq,
         bk, vmem_limit, interpret):
    """(dq, dk, dv) in the operands' dtypes."""
    pl, pltpu = _ps._pallas()
    B, H, T, D = q.shape
    kv, Dv = k.shape[1], v.shape[-1]
    group, nq = H // kv, T // bq
    rows = group * bq
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)

    def kernel(first_ref, end_ref, q_ref, k_ref, v_ref, g_ref, lse_ref,
               delta_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc):
        i = pl.program_id(2)

        @pl.when(i == 0)
        def _():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        qb = q_ref[...].reshape(rows, D)
        gb = g_ref[...].reshape(rows, Dv)
        row_lse, row_delta = lse_ref[...], delta_ref[...]    # (1, rows)
        dq_acc[...] = jnp.zeros_like(dq_acc)

        def step(j, mask):
            at = pl.ds(pl.multiple_of(j * bk, bk), bk)
            kb, vb = k_ref[at, :], v_ref[at, :]
            # tiles are (keys, rows): dv and dk come out of plain matmuls
            s = lax.dot_general(kb, qb, _NT,
                                preferred_element_type=jnp.float32) * scale
            if mask is not None:
                s = mask(s)
            p = jnp.exp(s - row_lse)
            ds = p * (lax.dot_general(vb, gb, _NT,
                                      preferred_element_type=jnp.float32)
                      - row_delta) * scale
            p, ds = p.astype(qb.dtype), ds.astype(qb.dtype)
            dv_acc[at, :] += jnp.dot(p, gb,
                                     preferred_element_type=jnp.float32)
            dk_acc[at, :] += jnp.dot(ds, qb,
                                     preferred_element_type=jnp.float32)
            dq_acc[...] += lax.dot_general(
                ds, kb, _TN, preferred_element_type=jnp.float32)

        _for_the_key_blocks(first_ref[i], end_ref[i], i, bq, bk, 1,
                            (bk, rows), causal, window, step)
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype).reshape(group, bq, D)

        @pl.when(i == nq - 1)
        def _():
            dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    folded, whole, row = _block_specs(group, bq, T)
    dq, dk, dv = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((B, kv, group, T, D), q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[folded(D), whole(D), whole(Dv), folded(Dv), row, row],
            out_specs=[folded(D), whole(D), whole(Dv)],
            grid=(B, kv, nq),
            scratch_shapes=[pltpu.VMEM((rows, D), jnp.float32),
                            pltpu.VMEM((T, D), jnp.float32),
                            pltpu.VMEM((T, Dv), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=_cost(q, k, v, bq, bk, causal, window, 3, 2),
        interpret=interpret,
        name="attention_bwd",
    )(first, end, q.reshape(B, kv, group, T, D), k, v,
      d_out.reshape(B, kv, group, T, Dv), _heads_to_rows(lse, kv, bq),
      _heads_to_rows(delta, kv, bq))
    return dq.reshape(B, H, T, D), dk, dv


# --- what blockwise_attention calls -------------------------------------------
def _static(plan, scale, causal, window, interpret):
    return dict(scale=float(scale), causal=bool(causal), window=int(window),
                bq=plan.bq, bk=plan.bk, vmem_limit=plan.vmem_limit,
                interpret=interpret)


def attention(q, k, v, plan, scale, causal, window=0, interpret=False):
    """(out, log-sum-exp): the forward kernel at ``plan``'s tiles."""
    first, end = visits(q.shape[2], plan.bq, plan.bk, causal, window)
    return _ps._kernel(_fwd, (q, k, v, jnp.asarray(first), jnp.asarray(end)),
                        **_static(plan, scale, causal, window, interpret))


def attention_grads(q, k, v, out, lse, d_out, plan, scale, causal, window=0,
                    interpret=False):
    """(dq, dk, dv): the backward kernel, from the forward's residuals."""
    first, end = visits(q.shape[2], plan.bq, plan.bk, causal, window)
    return _ps._kernel(
        _bwd, (q, k, v, out, lse, d_out.astype(q.dtype), jnp.asarray(first),
               jnp.asarray(end)),
        **_static(plan, scale, causal, window, interpret))
