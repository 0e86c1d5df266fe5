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

A selection (``select_top_k``: each query keeps the keys a small indexer
scores highest; ``ring_attention.selected_attention`` is the specification)
runs in the same two kernels and two more, and no (queries x keys) array in
float32, the main heads' or the indexer's, reaches HBM either:

* ``select`` (a block of ``bq`` queries a grid step) forms the block's index
  scores a key block at a time into a row buffer in VMEM (bq x T, as int32
  keys that order as the floats do), finds each row's ``top_k``-th largest
  there by ``kth_largest``'s 32 passes of compare and count, and writes the
  rows' thresholds, the log-sum-exp of their kept index scores and the
  block's kept pairs as int8 (T x T a batch row, transient: written once and
  read once a key/value head).
* ``attention`` / ``attention_grads`` take those pairs as one more operand
  (``kept``): a query block's tile of them (bq x T int8) rides beside the
  block, every visited key block is masked by its slice, the same for the G
  heads of the group and for every key/value head. Without the operand the
  kernels trace to what they were: the branch is Python's.
* ``index_grads`` runs first in backward: it forms the index scores again,
  writes the kept pairs from the kept thresholds KEYS by queries (the
  backward kernel's tiles are transposed), and pulls the indexer's own term
  back through them. That term needs ``P``, the mean probability over ALL
  the query heads, which the backward kernel sees a key/value head at a time:
  this kernel forms every head's scores of a tile again (q against every
  key/value head's keys in VMEM, the kept log-sum-exp; no ``p.v``) and sums
  them in float32 on the tile.

The block-diffusion mask (``diffusion=``; ``ring_attention.diffusion_attention``
is the specification) is the causal walk with its diagonal cut by blocks of
positions, q of one copy of a row over k and v of the other. On the strict
walk (the noised copy on the clean one) the kernels take the noised copy's
OWN keys and values as two more operands (``own``): a query block's ``bq`` of
them ride beside it and are one more tile of the block's loop, masked to
``b(key) == b(row)``, so a noised row's softmax over both copies is formed
once, in VMEM; backward writes that tile's ``dk`` and ``dv`` as two more
results, once a grid step. Without the operands the kernels trace to what
they were.

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
         window=0, value_dim=None, select_top_k=0,
         index=None, diffusion_block=0) -> Optional[Plan]:
    """The rule. ``D`` is the width of queries and keys, ``value_dim`` that
    of the values and the output (None: ``D``). The kernels engage where
    the program is lowered for one TPU whose VMEM is known, the operands
    are bfloat16 (a float32 trunk keeps the ``jax.numpy`` blocks at
    ``precision=HIGHEST``), the value width is a multiple of 128 and the
    key width a multiple of a half tile of lanes (64 alone, a differential
    pair's queries and keys under its two value heads side by side; 128s;
    128s plus a half, 192: a half tile of lanes is the narrowest Mosaic
    contracts over unpadded), the key/value heads
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
    A selection (``select_top_k`` keys a query, chosen by the indexer
    ``index`` = (dtype, heads J, width Di)) is four kernels (``select``,
    the forward and backward above reading its kept tiles, ``index_grads``)
    and engages where the same operands would without one, causal and with
    no window, and the index operands are the trunk's bfloat16 with Di a
    half tile of lanes or more: the first query block, then the widest key
    block, at which the forward and backward hold their kept tiles (int8,
    bq x T, twice) in the same half, and at which what the other two hold
    (``_select_bytes``) is, with the 16 MiB every kernel leaves Mosaic,
    within the three quarters ``vmem_limit`` is capped at.
    ``diffusion_block`` (the block-diffusion mask: the causal walk with its
    diagonal cut by blocks of so many positions) engages where plain causal
    attention over the same operands would and the block is a power of two
    that divides every tile (at most 128).
    None = the ``jax.numpy`` blocks."""
    if platform != "tpu" or not vmem_bytes:
        return None
    if diffusion_block and (
            not causal or window or select_top_k
            or diffusion_block & (diffusion_block - 1)
            or diffusion_block > _LANES):
        return None
    if select_top_k and (
            not causal or window or index is None
            or jnp.dtype(index[0]) != jnp.bfloat16
            or index[2] % (_LANES // 2)):
        return None
    value_dim = value_dim or D
    if jnp.dtype(dtype) != jnp.bfloat16 or heads % kv_heads:
        return None
    if value_dim % _LANES or D % _LANES not in (0, _LANES // 2):
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
    selecting = 0 < select_top_k < T
    for bq in (b for b in fit if b <= widest):
        rows = group * bq
        for keys in (blocks[blocks.index(bk):] if selecting else (bk,)):
            need = 12 * T * lanes + 8 * rows * lanes + 6 * rows * keys * 4 \
                + 2 * bq * T * selecting
            most = max(need, _select_bytes(kv_heads, group, T, D, index, bq,
                                           keys)) if select_top_k else need
            if need <= vmem_bytes // 2 \
                    and most + (16 << 20) <= vmem_bytes * 3 // 4:
                return Plan(bq, keys,
                            min(vmem_bytes * 3 // 4, most + (16 << 20)))
    return None


def _select_bytes(kv_heads, group, T, D, index, bq, bk):
    """What the larger of ``_select`` and ``_index_grads`` holds in VMEM at
    these tiles (a width under 128 lanes takes 128): the first its row
    buffer (bq x T keys of 4 bytes), the index keys and its kept tiles
    twice; the second every key/value head's keys twice, the index keys and
    their gradient twice each and once in float32, its kept tiles twice,
    the index heads' tiles (float32 and bfloat16) and four tiles of the
    main heads' scores."""
    _, J, Di = index
    lanes = -(-D // _LANES) * _LANES
    wide = -(-Di // _LANES) * _LANES
    select = 4 * bq * T + 4 * T * wide + 2 * bq * T
    grads = 4 * kv_heads * T * lanes + 12 * T * wide + 2 * bq * T \
        + 6 * J * bk * bq + 16 * bk * group * bq
    return max(select, grads)


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


def _loop(first, end, step, carry=None):
    """``carry = step(j, carry)`` for ``first <= j < end``, in order, as one
    loop whose steps store into the kernel's refs."""
    return lax.fori_loop(first, end, lambda j, carry: step(j, carry), carry)


def _for_the_key_blocks(first, end, i, bq, bk, rows_axis, shape, causal,
                        window, step, kept=None, diffusion=None):
    """``step(j, mask)`` for each key block ``first <= j < end`` of query
    block ``i``: ``mask`` is None for a block neither the diagonal nor the
    band's edge cuts, else a function of the scores' tile that writes
    ``_MASKED`` where a query may not see a key. ``shape`` is the tile's,
    with its rows (G x bq, position = row mod bq) on axis ``rows_axis``.
    Under a selection ``kept(j)`` is that function for key block ``j``
    (the kept tiles hold the diagonal too) and no tile goes unmasked.
    ``diffusion`` = (block, strict): the diagonal is cut by blocks of
    ``block`` positions (a power of two that divides both tiles) and not by
    position: a query sees the keys up to the end of its own block, or
    (``strict``) only those before its block."""
    pl, _ = _ps._pallas()
    if kept is not None:
        _loop(first, end, lambda j, _: step(j, kept(j)))
        return
    if bq & (bq - 1):
        raise ValueError(f"attention: {bq} positions a query block, not a "
                         "power of two")
    edge = 0    # of a tile's first row: the last key it sees, less its own
    if causal:
        # the last key a row sees, as a position of its own tile
        last = lax.broadcasted_iota(jnp.int32, shape, rows_axis) & (bq - 1)
        if diffusion is not None:
            block, strict = diffusion
            last = (last & ~(block - 1)) - 1 if strict \
                else last | (block - 1)
            edge = -1 if strict else block - 1
        # that position less the position of the key, at i = j = 0
        apart = last - lax.broadcasted_iota(jnp.int32, shape, 1 - rows_axis)

    def body(j, carry):
        if not causal:
            step(j, None)
            return carry
        # query positions i*bq .. i*bq + bq - 1, keys j*bk .. j*bk + bk - 1
        shift = i * bq - j * bk
        # a key of the block after the last one its first row sees
        cut = (shift + edge if edge else shift) < bk - 1
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
    asked for; a row of lanes a query block (log-sum-exp, delta); and
    ``mine(D)``, the ``bq`` keys or values of the query block's own
    positions (of the head's (T, D))."""
    pl, _ = _ps._pallas()

    def folded(D):
        return pl.BlockSpec((None, None, group, bq, D),
                            lambda b, h, i, *_: (b, h, 0, i, 0))

    def whole(D):
        return pl.BlockSpec((None, None, T, D),
                            lambda b, h, i, *_: (b, h, 0, 0))

    def mine(D):
        return pl.BlockSpec((None, None, bq, D),
                            lambda b, h, i, *_: (b, h, i, 0))

    return folded, whole, pl.BlockSpec(
        (None, None, None, 1, group * bq),
        lambda b, h, i, *_: (b, h, i, 0, 0)), mine


def _kept_tile(tile, group=1):
    """Whether a pair is kept, from a tile of int8 to the scores' lanes,
    ``group`` times side by side."""
    return jnp.tile(tile.astype(jnp.int32).astype(jnp.float32),
                    (1, group)) > 0


def _own_block(shape, rows_axis, bq, block):
    """The mask of the noised copy's own tile: a query block's rows (G x bq
    on axis ``rows_axis``, position = row mod bq) against the keys of its
    own ``bq`` positions see each other where they share a block of
    ``block`` positions, before or after: two positions of one tile share a
    block iff they differ under its bits alone."""
    apart = (lax.broadcasted_iota(jnp.int32, shape, rows_axis) & (bq - 1)) \
        ^ lax.broadcasted_iota(jnp.int32, shape, 1 - rows_axis)
    return lambda s: jnp.where(apart < block, s, _MASKED)


def _check_own(own, diffusion):
    if own is not None and (diffusion is None or not diffusion[1]):
        raise ValueError("attention: own keys and values ride the strict "
                         f"block-diffusion walk alone, not {diffusion}")


# --- forward -----------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "window", "bq", "bk", "vmem_limit", "interpret",
    "diffusion"))
def _fwd(q, k, v, first, end, kept=None, own=None, *, scale, causal, window,
         bq, bk, vmem_limit, interpret, diffusion=None):
    """(out (B, H, T, Dv) in q's dtype, log-sum-exp (B, H, T) float32).
    ``kept`` (B, T, T) int8, queries by keys: under a selection, what
    ``_select`` wrote: 1 where a query keeps a key. ``diffusion``:
    ``_for_the_key_blocks``'s. ``own`` (the strict walk alone): the keys (B,
    Hkv, T, Dk) and values (B, Hkv, T, Dv) of the queries' OWN copy of the
    row; a query block's ``bq`` of them ride beside it, and after the walk
    over k and v the block scores that one tile more under
    :func:`_own_block`'s mask, into the same running max, sum and
    accumulator: the rows' softmax is formed once over both copies and the
    output and log-sum-exp come out joint. Without ``own`` a row that sees
    no key at all (``strict``, the first block) comes back with a
    log-sum-exp near ``_MASKED``."""
    pl, pltpu = _ps._pallas()
    _check_own(own, diffusion)
    B, H, T, D = q.shape
    kv, Dv = k.shape[1], v.shape[-1]
    group, nq = H // kv, T // bq
    rows = group * bq

    def kernel(first_ref, end_ref, q_ref, k_ref, v_ref, *refs):
        kept_ref = refs[0] if kept is not None else None
        own_refs = refs[kept is not None:][:2]      # where ``own``
        o_ref, lse_ref, m_ref, l_ref, acc_ref = refs[-5:]
        i = pl.program_id(2)
        qb = q_ref[...].reshape(rows, D)
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def tile(keys, values, at, mask):
            s = lax.dot_general(qb, keys[at, :], _NT,
                                preferred_element_type=jnp.float32) * scale
            if mask is not None:
                s = mask(s)
            # m and l are kept across the 128 lanes of a row
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, s.max(axis=-1)[:, None])
            p = jnp.exp(s - jnp.tile(m_new, (1, s.shape[1] // _LANES)))
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1)[:, None]
            m_ref[...] = m_new
            acc_ref[...] = jnp.tile(alpha, (1, Dv // _LANES)) * acc_ref[...] \
                + lax.dot_general(p.astype(values.dtype), values[at, :],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

        def step(j, mask):
            tile(k_ref, v_ref, pl.ds(pl.multiple_of(j * bk, bk), bk), mask)

        def kept_of(j):
            # a tile of positions by keys, the same for every head of the
            # group
            ok = _kept_tile(
                kept_ref[:, pl.ds(pl.multiple_of(j * bk, bk), bk)])
            return lambda s: jnp.where(
                ok[None], s.reshape(group, bq, bk), _MASKED).reshape(rows, bk)

        _for_the_key_blocks(first_ref[i], end_ref[i], i, bq, bk, 0,
                            (rows, bk), causal, window, step,
                            None if kept is None else kept_of, diffusion)
        if own is not None:
            tile(*own_refs, slice(None),
                 _own_block((rows, bq), 0, bq, diffusion[0]))
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] * jnp.tile(1.0 / l, (1, Dv // _LANES))) \
            .astype(o_ref.dtype).reshape(group, bq, Dv)
        # the rows' log-sum-exp, from a column to a row of lanes
        lse_ref[...] = jnp.transpose(m_ref[...] + jnp.log(l))[:1, :]

    folded, whole, row, mine = _block_specs(group, bq, T)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((B, kv, group, T, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B, kv, nq, 1, rows), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[folded(D), whole(D), whole(Dv)] + [pl.BlockSpec(
                (None, bq, T), lambda b, h, i, *_: (b, i, 0))] * (
                    kept is not None)
            + [mine(D), mine(Dv)] * (own is not None),
            out_specs=[folded(Dv), row],
            grid=(B, kv, nq),
            scratch_shapes=[pltpu.VMEM((rows, _LANES), jnp.float32),
                            pltpu.VMEM((rows, _LANES), jnp.float32),
                            pltpu.VMEM((rows, Dv), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=_cost(q, k, v, bq, bk, causal, window, 1, 1,
                            own is not None),
        interpret=interpret,
        name="attention_fwd",
    )(first, end, q.reshape(B, kv, group, T, D), k, v,
      *(() if kept is None else (kept,)), *(own or ()))
    return out.reshape(B, H, T, Dv), _rows_to_heads(lse, H)


def _cost(q, k, v, bq, bk, causal, window, over_keys, over_values,
          own=False):
    """A kernel's matmuls a scored pair, at their two widths: ``over_keys``
    run at the key's (q.k, and backward dk and dq), ``over_values`` at the
    value's (p.v, and backward d_out.v and dv); the bytes are the tensors
    of either width, each read or written once a matmul of its width (the
    ``own`` operands are k's and v's size again). ``own``: a query block
    scores the ``bq`` keys of its own positions too."""
    pl, _ = _ps._pallas()
    B, H, T, D = q.shape
    Dv = v.shape[-1]
    pairs = B * H * (scored_pairs(T, bq, bk, causal, window) + T * bq * own)
    return pl.CostEstimate(
        flops=2 * pairs * (over_keys * D + over_values * Dv),
        transcendentals=pairs,
        bytes_accessed=(over_keys * (q.size + 2 * k.size * (1 + own))
                        + over_values * (q.size // D * Dv
                                         + 2 * v.size * (1 + own)))
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
    "scale", "causal", "window", "bq", "bk", "vmem_limit", "interpret",
    "diffusion"))
def _bwd(q, k, v, out, lse, d_out, first, end, kept=None, own=None, *,
         scale, causal, window, bq, bk, vmem_limit, interpret,
         diffusion=None):
    """(dq, dk, dv) in the operands' dtypes. ``kept`` (B, T, T) int8, KEYS
    by queries as this kernel's tiles are: under a selection, what
    ``_index_grads`` wrote. ``diffusion``: ``_for_the_key_blocks``'s; ``out``
    and ``lse`` are then the rows' over ALL the keys they see, so the
    gradients are those of the joint softmax. ``own``: ``_fwd``'s; the
    query block's own tile adds to its ``dq``, and its ``dk`` and ``dv``
    come back as two more results, (dq, dk, dv, dk_own, dv_own): a block
    of ``bq`` own keys is seen by its one query block, so they are written
    once a grid step from the tile's float32 products and not
    accumulated."""
    pl, pltpu = _ps._pallas()
    _check_own(own, diffusion)
    B, H, T, D = q.shape
    kv, Dv = k.shape[1], v.shape[-1]
    group, nq = H // kv, T // bq
    rows = group * bq
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)

    def kernel(first_ref, end_ref, q_ref, k_ref, v_ref, g_ref, lse_ref,
               delta_ref, *refs):
        kept_ref = refs[0] if kept is not None else None
        own_refs = refs[kept is not None:][:2]      # where ``own``
        dq_ref, dk_ref, dv_ref, *own_out, dq_acc, dk_acc, dv_acc = \
            refs[(kept is not None) + 2 * (own is not None):]
        i = pl.program_id(2)

        @pl.when(i == 0)
        def _():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        qb = q_ref[...].reshape(rows, D)
        gb = g_ref[...].reshape(rows, Dv)
        row_lse, row_delta = lse_ref[...], delta_ref[...]    # (1, rows)
        dq_acc[...] = jnp.zeros_like(dq_acc)

        def weights(kb, vb, mask):
            # tiles are (keys, rows): dv and dk come out of plain matmuls
            s = lax.dot_general(kb, qb, _NT,
                                preferred_element_type=jnp.float32) * scale
            if mask is not None:
                s = mask(s)
            p = jnp.exp(s - row_lse)
            ds = p * (lax.dot_general(vb, gb, _NT,
                                      preferred_element_type=jnp.float32)
                      - row_delta) * scale
            return p.astype(qb.dtype), ds.astype(qb.dtype)

        def step(j, mask):
            at = pl.ds(pl.multiple_of(j * bk, bk), bk)
            kb, vb = k_ref[at, :], v_ref[at, :]
            p, ds = weights(kb, vb, mask)
            dv_acc[at, :] += jnp.dot(p, gb,
                                     preferred_element_type=jnp.float32)
            dk_acc[at, :] += jnp.dot(ds, qb,
                                     preferred_element_type=jnp.float32)
            dq_acc[...] += lax.dot_general(
                ds, kb, _TN, preferred_element_type=jnp.float32)

        def kept_of(j):
            # a tile of keys by positions, the same for every head of the
            # group
            ok = _kept_tile(
                kept_ref[pl.ds(pl.multiple_of(j * bk, bk), bk), :], group)
            return lambda s: jnp.where(ok, s, _MASKED)

        _for_the_key_blocks(first_ref[i], end_ref[i], i, bq, bk, 1,
                            (bk, rows), causal, window, step,
                            None if kept is None else kept_of, diffusion)
        if own is not None:
            kb = own_refs[0][...]
            p, ds = weights(kb, own_refs[1][...],
                            _own_block((bq, rows), 1, bq, diffusion[0]))
            dk_own_ref, dv_own_ref = own_out
            dv_own_ref[...] = jnp.dot(
                p, gb, preferred_element_type=jnp.float32
            ).astype(dv_own_ref.dtype)
            dk_own_ref[...] = jnp.dot(
                ds, qb, preferred_element_type=jnp.float32
            ).astype(dk_own_ref.dtype)
            dq_acc[...] += lax.dot_general(
                ds, kb, _TN, preferred_element_type=jnp.float32)
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype).reshape(group, bq, D)

        @pl.when(i == nq - 1)
        def _():
            dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    folded, whole, row, mine = _block_specs(group, bq, T)
    dq, *rest = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((B, kv, group, T, D), q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype))
        + (() if own is None else tuple(
            jax.ShapeDtypeStruct(x.shape, x.dtype) for x in own)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[folded(D), whole(D), whole(Dv), folded(Dv), row, row]
            + [pl.BlockSpec((None, T, bq), lambda b, h, i, *_: (b, 0, i))] * (
                kept is not None)
            + [mine(D), mine(Dv)] * (own is not None),
            out_specs=[folded(D), whole(D), whole(Dv)]
            + [mine(D), mine(Dv)] * (own is not None),
            grid=(B, kv, nq),
            scratch_shapes=[pltpu.VMEM((rows, D), jnp.float32),
                            pltpu.VMEM((T, D), jnp.float32),
                            pltpu.VMEM((T, Dv), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=_cost(q, k, v, bq, bk, causal, window, 3, 2,
                            own is not None),
        interpret=interpret,
        name="attention_bwd",
    )(first, end, q.reshape(B, kv, group, T, D), k, v,
      d_out.reshape(B, kv, group, T, Dv), _heads_to_rows(lse, kv, bq),
      _heads_to_rows(delta, kv, bq), *(() if kept is None else (kept,)),
      *(own or ()))
    return (dq.reshape(B, H, T, D), *rest)


# --- a selection: the threshold of a row, and the indexer's gradient -----------
_LOWEST = -(1 << 31)            # under every key: a pair no query may see
_KEY_OF_MINUS_INF = -(1 << 31) + (1 << 23) - 1


def _key(x):
    """float32 -> int32 that orders as the floats do (a float's bits, those
    under the sign flipped where it is negative), and back: the map is its
    own inverse on the bits. Mosaic compares signed integers only."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits >= 0, bits, bits ^ jnp.int32(0x7fffffff))


def _value(key):
    return lax.bitcast_convert_type(
        jnp.where(key >= 0, key, key ^ jnp.int32(0x7fffffff)), jnp.float32)


def _lanes_of(j, bk):
    """Key block ``j`` of a ref of positions by keys, 128 lanes at a time."""
    pl, _ = _ps._pallas()
    return [(slice(None), pl.ds(pl.multiple_of(j * bk + c * _LANES, _LANES),
                                _LANES)) for c in range(bk // _LANES)]


@functools.partial(jax.jit, static_argnames=(
    "top_k", "bq", "bk", "write", "vmem_limit", "interpret"))
def _select(iq, ik, iw, end, *, top_k, bq, bk, write, vmem_limit, interpret):
    """(thresholds (B, T), log-sum-exp of the kept index scores (B, T), both
    float32, kept (B, T, T) int8 queries by keys where ``write``) of the
    indexer ``iq`` (B, J, T, Di), ``ik`` (B, 1, T, Di), ``iw`` (B, J, T):
    ``ring_attention.index_scores``, ``_threshold`` and ``_selection`` a
    block of ``bq`` queries a grid step. The block's scores are formed a key
    block at a time into a row buffer in VMEM (bq x T, as ordered keys);
    the ``top_k``-th largest of a row is found there by ``kth_largest``'s 32
    passes of compare and count, over the block's causal extent alone and
    not at all where no row of the block has more than ``top_k`` keys."""
    pl, pltpu = _ps._pallas()
    B, J, T, Di = iq.shape
    nq, nk = T // bq, T // bk
    f32, i32 = jnp.float32, jnp.int32

    def kernel(end_ref, iq_ref, ik_ref, iw_ref, tau_ref, lse_ref, *refs):
        kept_ref, buf = refs if write else (None,) + refs
        i = pl.program_id(1)
        n = end_ref[i]
        weight = iw_ref[...].astype(f32)                        # (bq, J)
        apart = lax.broadcasted_iota(i32, (bq, bk), 0) \
            - lax.broadcasted_iota(i32, (bq, bk), 1)

        def form(j, top):
            at = pl.ds(pl.multiple_of(j * bk, bk), bk)
            keys = ik_ref[at, :]
            index = jnp.zeros((bq, bk), f32)
            for h in range(J):
                index = index + weight[:, h:h + 1] * jnp.maximum(
                    lax.dot_general(iq_ref[h], keys, _NT,
                                    preferred_element_type=f32), 0.0)
            key = jnp.where(apart >= j * bk - i * bq, _key(index), _LOWEST)
            buf[:, at] = key
            return jnp.maximum(top, key.max(axis=-1, keepdims=True))

        top = _loop(0, n, form, jnp.full((bq, 1), _LOWEST, i32))

        def narrow(bit, found):     # the largest pattern top_k keys reach
            tried = found | lax.shift_left(i32(1), 31 - bit)
            probe = tried ^ i32(_LOWEST)     # unsigned order, signed compare

            def count(j, reach):
                for at in _lanes_of(j, bk):
                    reach = reach + jnp.where(buf[at] >= probe, 1, 0)
                return reach

            reach = lax.fori_loop(0, n, count, jnp.zeros((bq, _LANES), i32))
            return jnp.where(reach.sum(axis=-1, keepdims=True) >= top_k,
                             tried, found)

        found = lax.fori_loop(0, jnp.where((i + 1) * bq > top_k, 32, 0),
                              narrow, jnp.zeros((bq, _LANES), i32))
        # minus infinity for a row of no more than top_k keys
        tau = jnp.where(
            i * bq + lax.broadcasted_iota(i32, (bq, _LANES), 0) >= top_k,
            jnp.maximum(found ^ i32(_LOWEST), _KEY_OF_MINUS_INF),
            _KEY_OF_MINUS_INF)
        highest = _value(top)

        def emit(j, total):
            for at in _lanes_of(j, bk):
                key = buf[at]
                kept = key >= tau
                total = total + jnp.where(
                    kept, jnp.exp(_value(key) - highest), 0.0)
                if write:
                    kept_ref[at] = jnp.where(kept, 1, 0).astype(jnp.int8)
            return total

        total = _loop(0, n, emit, jnp.zeros((bq, _LANES), f32))
        if write:
            def none(j, carry):
                for at in _lanes_of(j, bk):
                    kept_ref[at] = jnp.zeros((bq, _LANES), jnp.int8)
                return carry

            _loop(n, nk, none)
        # from a column to a row of lanes
        tau_ref[...] = jnp.transpose(_value(tau))[:1, :]
        lse_ref[...] = jnp.transpose(jnp.broadcast_to(
            highest + jnp.log(total.sum(axis=-1, keepdims=True)),
            (bq, _LANES)))[:1, :]

    row = pl.BlockSpec((None, None, 1, bq), lambda b, i, *_: (b, i, 0, 0))
    got = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((B, nq, 1, bq), f32),) * 2 + (
            jax.ShapeDtypeStruct((B, T, T), jnp.int8),) * write,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((None, J, bq, Di),
                                   lambda b, i, *_: (b, 0, i, 0)),
                      pl.BlockSpec((None, None, T, Di),
                                   lambda b, i, *_: (b, 0, 0, 0)),
                      pl.BlockSpec((None, bq, J),
                                   lambda b, i, *_: (b, i, 0))],
            out_specs=[row, row] + [pl.BlockSpec(
                (None, bq, T), lambda b, i, *_: (b, i, 0))] * write,
            grid=(B, nq),
            scratch_shapes=[pltpu.VMEM((bq, T), i32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=_index_cost(iq, bq, bk, 1, 2 * 32 + 8),
        interpret=interpret,
        name="attention_select",
    )(end, iq, ik, jnp.swapaxes(iw, 1, 2))
    return (got[0].reshape(B, T), got[1].reshape(B, T)) + tuple(got[2:])


def _index_cost(iq, bq, bk, matmuls, passes, q=None):
    """An index kernel's work: ``matmuls`` products over Di a scored pair
    and index head, ``passes`` element operations a scored pair; with ``q``
    also the main heads' scores of every pair, formed again for ``P``."""
    pl, _ = _ps._pallas()
    B, J, T, Di = iq.shape
    pairs = B * scored_pairs(T, bq, bk, True)
    heads, width, size = (0, 0, 0) if q is None else (
        q.shape[1], q.shape[-1], q.size)
    return pl.CostEstimate(
        flops=pairs * (2 * J * Di * matmuls + 3 * J + passes
                       + heads * (2 * width + 4)),
        transcendentals=pairs * (1 + heads),
        bytes_accessed=2 * (iq.size + size) * iq.dtype.itemsize)


@functools.partial(jax.jit, static_argnames=(
    "scale", "coef", "bq", "bk", "write", "vmem_limit", "interpret"))
def _index_grads(q, k, iq, ik, iw, lse, tau, index_lse, end, *, scale, coef,
                 bq, bk, write, vmem_limit, interpret):
    """((d_iq, d_ik, d_iw) where ``coef``, kept (B, T, T) int8 KEYS by
    queries where ``write``): a block of ``bq`` queries a grid step, its
    tiles keys by positions as ``_bwd``'s. A tile's index scores are formed
    again from the operands, its kept pairs from the kept thresholds
    ``tau``; where ``coef``, ``P`` of the tile is summed in float32 over ALL
    the query heads (their scores formed again from q, every key/value
    head's keys in VMEM, and the kept log-sum-exp ``lse``; no ``p.v``), and
    ``coef * (softmax_S(I) - P)`` (``index_lse``: the kept scores'
    log-sum-exp) is pulled back through ``I`` there: cast to the operands'
    dtype for its two matmuls as ``ds`` is, ``d_ik`` accumulated in float32
    over the query blocks as ``dk`` is."""
    pl, pltpu = _ps._pallas()
    B, J, T, Di = iq.shape
    H, kv, D = q.shape[1], k.shape[1], q.shape[-1]
    group, nq = H // kv, T // bq
    rows = group * bq
    f32 = jnp.float32

    def kernel(end_ref, *refs):
        refs = list(refs)
        iq_ref, ik_ref, iw_ref, tau_ref = refs[:4]
        del refs[:4]
        if coef:
            q_ref, k_ref, lse_ref, index_lse_ref = refs[:4]
            del refs[:4]
            d_iq_ref, d_ik_ref, d_iw_ref = refs[:3]
            del refs[:3]
        if write:
            kept_ref = refs.pop(0)
        if coef:
            z_ref, dz_ref, d_iq_acc, d_ik_acc, d_iw_acc = refs
        i = pl.program_id(1)
        weight = iw_ref[...].astype(f32)                         # (J, bq)
        row_tau = tau_ref[...]                                   # (1, bq)
        apart = lax.broadcasted_iota(jnp.int32, (bk, bq), 1) \
            - lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
        if coef:
            d_iq_acc[...] = jnp.zeros_like(d_iq_acc)
            d_iw_acc[...] = jnp.zeros_like(d_iw_acc)

            @pl.when(i == 0)
            def _():
                d_ik_acc[...] = jnp.zeros_like(d_ik_acc)

        def step(j, carry):
            at = pl.ds(pl.multiple_of(j * bk, bk), bk)
            keys = ik_ref[at, :]
            index = jnp.zeros((bk, bq), f32)
            for h in range(J):
                z = lax.dot_general(keys, iq_ref[h], _NT,
                                    preferred_element_type=f32)
                if coef:
                    z_ref[h] = z
                index = index + weight[h:h + 1, :] * jnp.maximum(z, 0.0)
            kept = jnp.logical_and(apart >= j * bk - i * bq,
                                   index >= row_tau)
            if write:
                kept_ref[at, :] = jnp.where(kept, 1, 0).astype(jnp.int8)
            if not coef:
                return carry
            # the heads' summed probability of each pair, a constant
            target = jnp.zeros((bk, bq), f32)
            for h in range(kv):
                p = jnp.exp(lax.dot_general(
                    k_ref[h, at, :], q_ref[h].reshape(rows, D), _NT,
                    preferred_element_type=f32) * scale - lse_ref[h])
                for g in range(group):
                    target = target + p[:, g * bq:(g + 1) * bq]
            d_index = jnp.where(
                kept, coef * (jnp.exp(index - index_lse_ref[...])
                              - target / H), 0.0)
            for h in range(J):
                z = z_ref[h]
                d_iw_acc[h:h + 1, :] += jnp.sum(
                    d_index * jnp.maximum(z, 0.0), axis=0, keepdims=True)
                dz_ref[:, h * bq:(h + 1) * bq] = jnp.where(
                    z > 0, d_index * weight[h:h + 1, :], 0.0
                ).astype(dz_ref.dtype)
            dz = dz_ref[...]
            d_ik_acc[at, :] += jnp.dot(dz, iq_ref[...].reshape(J * bq, Di),
                                       preferred_element_type=f32)
            d_iq_acc[...] += lax.dot_general(dz, keys, _TN,
                                             preferred_element_type=f32)
            return carry

        _loop(0, end_ref[i], step)
        if write:
            def none(j, carry):
                at = pl.ds(pl.multiple_of(j * bk, bk), bk)
                kept_ref[at, :] = jnp.zeros((bk, bq), jnp.int8)
                return carry

            _loop(end_ref[i], T // bk, none)
        if coef:
            d_iq_ref[...] = d_iq_acc[...].astype(d_iq_ref.dtype).reshape(
                J, bq, Di)
            d_iw_ref[...] = d_iw_acc[...].astype(d_iw_ref.dtype)

            @pl.when(i == nq - 1)
            def _():
                d_ik_ref[...] = d_ik_acc[...].astype(d_ik_ref.dtype)

    def block(at, *shape):      # query block i along axis ``at``
        return pl.BlockSpec((None,) + shape, lambda b, i, *_: (b,) + tuple(
            i if n == at else 0 for n in range(len(shape))))

    whole_keys = pl.BlockSpec((None, None, T, Di),
                              lambda b, i, *_: (b, 0, 0, 0))
    row = pl.BlockSpec((None, None, 1, bq), lambda b, i, *_: (b, i, 0, 0))
    in_specs = [block(1, J, bq, Di), whole_keys, block(1, J, bq), row]
    operands = [iq, ik, iw, tau.reshape(B, nq, 1, bq)]
    out_shape, out_specs, scratch = [], [], []
    if coef:
        in_specs += [
            block(2, kv, group, bq, D),
            pl.BlockSpec((None, kv, T, D), lambda b, i, *_: (b, 0, 0, 0)),
            pl.BlockSpec((None, kv, None, 1, rows),
                         lambda b, i, *_: (b, 0, i, 0, 0)), row]
        operands += [q.reshape(B, kv, group, T, D), k,
                     _heads_to_rows(lse, kv, bq),
                     index_lse.reshape(B, nq, 1, bq)]
        out_shape += [jax.ShapeDtypeStruct(x.shape, x.dtype)
                      for x in (iq, ik, iw)]
        out_specs += [block(1, J, bq, Di), whole_keys, block(1, J, bq)]
        scratch = [pltpu.VMEM((J, bk, bq), f32),
                   pltpu.VMEM((bk, J * bq), iq.dtype),
                   pltpu.VMEM((J * bq, Di), f32), pltpu.VMEM((T, Di), f32),
                   pltpu.VMEM((J, bq), f32)]
    if write:
        out_shape.append(jax.ShapeDtypeStruct((B, T, T), jnp.int8))
        out_specs.append(block(1, T, bq))
    got = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shape),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, in_specs=in_specs, out_specs=out_specs,
            grid=(B, nq), scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=_index_cost(iq, bq, bk, 3, 12, q) if coef
        else _index_cost(iq, bq, bk, 1, 3),
        interpret=interpret,
        name="attention_index_bwd",
    )(end, *operands)
    return tuple(got)


# --- what blockwise_attention calls -------------------------------------------
def _static(plan, scale, causal, window, interpret, diffusion=None):
    """The kernels' static arguments; ``diffusion`` only where it is set, so
    that every other call is keyed (``pallas_support._kernel``) as it was."""
    return dict(scale=float(scale), causal=bool(causal), window=int(window),
                bq=plan.bq, bk=plan.bk, vmem_limit=plan.vmem_limit,
                interpret=interpret,
                **({} if diffusion is None else {"diffusion": diffusion}))


def _optional(kept, own):
    """The kernels' trailing operands, each only where it is set (the same
    reason)."""
    if own is not None:
        return kept, tuple(own)
    return () if kept is None else (kept,)


def attention(q, k, v, plan, scale, causal, window=0, interpret=False,
              kept=None, diffusion=None, own=None):
    """(out, log-sum-exp): the forward kernel at ``plan``'s tiles; under a
    selection over ``select``'s ``kept`` pairs; ``diffusion`` = (block,
    strict): the causal walk with its diagonal cut by blocks (q may be
    another copy of the row than k and v); ``own`` = (keys, values) of q's
    own copy, under ``strict``: each query also sees its own block of
    them, in the same softmax."""
    first, end = visits(q.shape[2], plan.bq, plan.bk, causal, window)
    return _ps._kernel(
        _fwd, (q, k, v, jnp.asarray(first), jnp.asarray(end))
        + _optional(kept, own),
        **_static(plan, scale, causal, window, interpret, diffusion))


def attention_grads(q, k, v, out, lse, d_out, plan, scale, causal, window=0,
                    interpret=False, kept=None, diffusion=None, own=None):
    """(dq, dk, dv): the backward kernel, from the forward's residuals;
    under a selection over ``index_grads``'s ``kept`` pairs; ``diffusion``
    and ``own`` as :func:`attention`'s; with ``own`` (dq, dk, dv, dk_own,
    dv_own)."""
    first, end = visits(q.shape[2], plan.bq, plan.bk, causal, window)
    return _ps._kernel(
        _bwd, (q, k, v, out, lse, d_out.astype(q.dtype), jnp.asarray(first),
               jnp.asarray(end)) + _optional(kept, own),
        **_static(plan, scale, causal, window, interpret, diffusion))


def select(iq, ik, iw, plan, top_k, interpret=False):
    """(thresholds (B, T), the kept index scores' log-sum-exp (B, T), kept
    (B, T, T) int8 queries by keys, or None where ``top_k >= T``: every
    earlier key is kept and the dense kernels run): the select kernel at
    ``plan``'s tiles."""
    T = iq.shape[2]
    _, end = visits(T, plan.bq, plan.bk, True)
    got = _ps._kernel(
        _select, (iq, ik, iw, jnp.asarray(end)), top_k=int(top_k),
        bq=plan.bq, bk=plan.bk, write=top_k < T, vmem_limit=plan.vmem_limit,
        interpret=interpret)
    return got if top_k < T else got + (None,)


def index_grads(q, k, iq, ik, iw, lse, tau, index_lse, plan, scale, top_k,
                coef, interpret=False):
    """((d_iq, d_ik, d_iw), kept (B, T, T) int8 KEYS by queries): the
    indexer's kernel of backward at ``plan``'s tiles. The gradients are
    zeros where ``coef`` is 0, ``kept`` None where ``top_k >= T``."""
    T = iq.shape[2]
    zeros = tuple(jnp.zeros_like(x) for x in (iq, ik, iw))
    if not coef and top_k >= T:
        return zeros, None
    _, end = visits(T, plan.bq, plan.bk, True)
    got = _ps._kernel(
        _index_grads, (q, k, iq, ik, iw, lse, tau, index_lse,
                       jnp.asarray(end)),
        scale=float(scale), coef=float(coef), bq=plan.bq, bk=plan.bk,
        write=top_k < T, vmem_limit=plan.vmem_limit, interpret=interpret)
    return (got[:3] if coef else zeros), (got[-1] if top_k < T else None)
