"""Grouped matmul for ``MoE``'s experts: Pallas TPU kernels that read the
expert weights as the parameter is stored.

``grouped_matmul(rows, w, groups(counts, M, plan), plan)``: ``rows`` (M, K)
sorted by expert, ``w`` (E, K, N) in the dtype and row-major layout of the
parameter (float32 masters under a bfloat16 trunk), ``counts`` (E,) int32
summing to M or less. Expert ``e`` multiplies its own ``counts[e]`` rows by
``w[e]``; float32 accumulation; the result comes back in ``rows.dtype``. No
capacity and no dropped row: an expert with no rows, one expert with every
row and group boundaries inside a row tile all work.

The rows past ``counts.sum()`` are no group's: the dead rows of a held
round of ``MoE``. The kernels own them, so a caller masks nothing. Forward
and dgrad return zeros there, in every row tile (one no group visits is
visited once more for its zeros, ``_visit_lists``), and read none of them
into a live row: what ``rows`` and the cotangent hold there may be anything,
NaN too. wgrad multiplies no dead row of the cotangent (zeroed in VMEM, NaN
too) and asks of ``rows`` there finite numbers only. Counts that sum to M
visit what they did before.

What the kernels do that ``_castp`` + ``jax.lax.ragged_dot`` + autodiff
does not:

* One expert's matrix is copied HBM -> VMEM once a group, as stored, one
  group ahead of the matmuls that use it (two buffers, manual DMA), and
  cast float32 -> bfloat16 there: the rounding ``w.astype(bfloat16)``
  gives, with no bfloat16 copy of the weights in HBM and no relayout.
* dgrad is the same kernel with the other ``dot_general`` dimension
  numbers: ``w[e]`` is read as stored and contracted over its last axis.
* wgrad is the transposed grouped matmul (``rows^T . g`` a group),
  written in ``rows.dtype`` as autodiff of the cast writes it today.

The shape follows ``jax.experimental.pallas.ops.tpu.megablox``: row tiles
visited group by group, a masked store where a boundary falls inside a
tile. megablox itself computes in float32 whenever the operands' dtypes
differ, fetches a weight block a visit, multiplies a whole row tile for
every group that touches it (here: the run of 128-row chunks that holds the
group's rows) and differentiates by calling itself on transposed copies.

``plan`` is the one rule that says whether the kernels engage and with
which tiles; everything it reads is observable where the op is traced, the
platform its program is lowered for among it (``OpMode.platform``). With a
plan ``MoE`` calls ``grouped_matmul``, without one ``ragged_dot``
(``defs_transformer._expert_matmul``): one choice, made in Python where the
op is traced. Pallas and the store of traced kernels are
``pallas_support``'s.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import pallas_support as _ps

_LANES = 128
_CHUNK = 128   # rows: the grain of a matmul inside a row tile
# Columns of the output one matmul writes, in a loop over the panel: the
# kernels' code lives in HBM beside the model. Measured on a v5e at 32 768
# rows, 64 experts, 2048 x 1024 (PERF.md section 6, PR 30; forward / dgrad
# / wgrad, ms): the whole panel unrolled 1.07 / 1.09 / 1.32 and 17 MB more
# peak HBM than ragged_dot's program, 512 columns 1.08 / 1.11 / 1.35, 256
# columns 1.11 / 1.17 / 1.41 and 1.8 MB more, 128 columns 1.17 / 1.30 / 1.62.
_SLAB = 256
# Row tiles (the rows DMAed a visit), best first, same sweep: forward and
# dgrad 1.10 ms at 512, 1.39 at 256, 5.2 at 1024; wgrad 1.35 ms at 256,
# 1.42 at 128, 4.4 at 512 (its transposed-left matmul).
_ROW_TILES = (512, 256, 128)
_WGRAD_ROW_TILES = (256, 128)


class Plan(NamedTuple):
    """Tiles of one grouped matmul (M, K) x (E, K, N) and its two
    gradients. ``tm`` / ``tmw``: row tile of the forward and dgrad kernels
    / of wgrad. ``tn`` / ``tk``: width of the weight panel held in VMEM by
    the forward / dgrad kernel (N / K when one expert's matrix fits
    whole); ``tw``: the wgrad accumulator's width."""

    tm: int
    tmw: int
    tn: int
    tk: int
    tw: int
    vmem_limit: int




def _panel(width, fits):
    """Largest multiple of 128 dividing ``width`` for which ``fits``."""
    for parts in range(1, width // _LANES + 1):
        if width % parts == 0 and (width // parts) % _LANES == 0 \
                and fits(width // parts):
            return width // parts
    return None


def plan(platform, vmem_bytes, rows_dtype, w_dtype, m, k, n) -> Optional[Plan]:
    """The rule. The kernels engage where the program is lowered for a TPU
    whose VMEM is known, the rows are bfloat16 (a float32 trunk keeps
    ``ragged_dot`` at ``precision=HIGHEST``), the weights float32 or
    bfloat16, K and N multiples of 128 and M a multiple of a row tile.
    Tiles: the first row tile of ``_ROW_TILES`` (``_WGRAD_ROW_TILES``)
    dividing M; the widest weight panel such that what a kernel keeps in
    VMEM (two buffers of the panel as stored, its bfloat16 copy, two row
    tiles in and out, the float32 product) is under half of it. None =
    ``ragged_dot``."""
    rows_dtype, w_dtype = jnp.dtype(rows_dtype), jnp.dtype(w_dtype)
    if platform != "tpu" or not vmem_bytes:
        return None
    if rows_dtype != jnp.bfloat16 or w_dtype not in (jnp.float32,
                                                     jnp.bfloat16):
        return None
    if k % _LANES or n % _LANES:
        return None
    tm = next((t for t in _ROW_TILES if m % t == 0), None)
    tmw = next((t for t in _WGRAD_ROW_TILES if m % t == 0), None)
    if tm is None:
        return None
    budget = vmem_bytes // 2
    wb, rb = w_dtype.itemsize, rows_dtype.itemsize
    cast = rb if w_dtype != rows_dtype else 0

    def gmm_bytes(depth, width):  # contraction depth, panel width
        return (depth * width * (2 * wb + cast)
                + 2 * tm * (depth + width) * rb + tm * width * 4)

    def tgmm_bytes(width):
        return (k * width * (4 + 2 * rb + 4)
                + 2 * tmw * (k + width) * rb)

    tn = _panel(n, lambda t: gmm_bytes(k, t) <= budget)
    tk = _panel(k, lambda t: gmm_bytes(n, t) <= budget)
    tw = _panel(n, lambda t: tgmm_bytes(t) <= budget)
    if None in (tn, tk, tw):
        return None
    need = max(gmm_bytes(k, tn), gmm_bytes(n, tk), tgmm_bytes(tw))
    return Plan(tm, tmw, tn, tk, tw,
                min(vmem_bytes * 3 // 4, need + (16 << 20)))


# --- the groups, once a layer -----------------------------------------------
class Groups(NamedTuple):
    """What the kernels of one layer need of ``counts``, computed once for
    its three matmuls and their gradients (int32 arrays; ``visits`` and
    ``wgrad_visits`` are the dynamic grid sizes)."""

    counts: jax.Array        # (E,) rows a group: what ragged_dot reads
    offsets: jax.Array       # (E + 1,) first row of each group
    group_ids: jax.Array     # a visit's group, forward and dgrad
    m_tile_ids: jax.Array    # a visit's row tile; the dead tiles' come last
    ordinal: jax.Array       # a visit's group, counted among the visited
    order: jax.Array         # (E,) the visited groups in order
    nvisited: jax.Array      # (1,) how many groups have rows
    visits: jax.Array
    wgrad_group_ids: jax.Array   # wgrad visits every group, at its own tile
    wgrad_m_tile_ids: jax.Array
    wgrad_visits: jax.Array


def _visit_lists(counts, m, tm, wgrad):
    """(offsets (E + 1,), a visit's group, its row tile, how many visits):
    one visit a (group, row tile) pair that share a row, groups in order,
    so a tile that holds a boundary is visited once a group. ``wgrad``'s
    lists give an empty group one visit too (it has its zeros to write);
    the forward's and dgrad's give one to each row tile past
    ``counts.sum()``, after the live ones, under the last group that has
    rows (they have its zeros to write: the group's rows end before the
    tile, so no product runs). At most ``m / tm + E - 1`` visits either
    way (the live ones are at most the live tiles + E - 1), the lists'
    static length; entries past the count are never run. Where the counts
    fill ``m`` megablox's ``make_group_metadata`` gives the same lists;
    this one is dense compares over (visits, E), a fraction of its program
    text."""
    e, tiles = counts.shape[0], m // tm
    ends = jnp.cumsum(counts)
    starts = ends - counts
    first = jnp.minimum(starts // tm, tiles - 1)
    n = jnp.where(counts > 0, (ends - 1) // tm - first + 1,
                  1 if wgrad else 0)
    upto = jnp.cumsum(n)                  # visits of groups 0 .. g
    i = jnp.arange(tiles + e - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(i[:, None] >= upto[None, :], axis=1), e - 1)
    tile = _pick(first - (upto - n), group) + i
    visits = upto[-1]
    if not wgrad:
        live_tiles = (ends[-1] + tm - 1) // tm
        dead = i >= visits
        group = jnp.where(dead, jnp.max(jnp.where(counts > 0, jnp.arange(e),
                                                  0)), group)
        tile = jnp.where(dead, live_tiles + i - visits, tile)
        visits = visits + tiles - live_tiles
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return (offsets.astype(jnp.int32), group.astype(jnp.int32),
            jnp.clip(tile, 0, tiles - 1).astype(jnp.int32),
            visits.astype(jnp.int32))


def _pick(values, index):
    """``values[index]`` for small int vectors, as a masked sum."""
    hot = index[:, None] == jnp.arange(values.shape[0])[None, :]
    return jnp.sum(jnp.where(hot, values[None, :], 0), axis=1)


@functools.partial(jax.jit, static_argnames=("m", "plan"))
def groups(counts, m, plan) -> Groups:
    """The visit lists of ``counts`` at the plan's row tiles, and what the
    weight prefetch needs beside them."""
    e = counts.shape[0]
    offsets, group_ids, m_tile_ids, visits = _visit_lists(
        counts, m, plan.tm, False)
    _, wgrad_group_ids, wgrad_m_tile_ids, wgrad_visits = _visit_lists(
        counts, m, plan.tmw, True)
    visited = counts > 0
    rank = jnp.cumsum(visited) - 1        # of a group among the visited
    ranks = jnp.arange(e)
    order = jnp.sum(jnp.where(
        jnp.logical_and(visited[None, :], rank[None, :] == ranks[:, None]),
        ranks[None, :], 0), axis=1)
    return Groups(
        counts, offsets, group_ids, m_tile_ids,
        _pick(rank, group_ids).astype(jnp.int32), order.astype(jnp.int32),
        jnp.sum(visited).astype(jnp.int32)[None], visits,
        wgrad_group_ids, wgrad_m_tile_ids, wgrad_visits)


def _for_the_groups_rows(offsets, group, m_tile, tm, piece):
    """Calls ``piece(at, mask)`` once, under the one ``pl.when`` that
    holds: ``at`` is the run of whole 128-row chunks of row tile ``m_tile``
    that holds the rows of ``group`` in it (1 to ``tm / 128`` chunks from a
    dynamic chunk: one matmul of just those chunks, so a boundary inside a
    tile costs its chunk and not the tile), ``mask`` (rows, 1) which of the
    run's rows are the group's. No call for a group with no rows."""
    pl, _ = _ps._pallas()
    start, end = offsets[group], offsets[group + 1]
    r0 = m_tile * tm
    lo = jnp.maximum(start, r0) - r0
    hi = jnp.minimum(end, r0 + tm) - r0
    first = lo // _CHUNK
    chunks = (hi + _CHUNK - 1) // _CHUNK - first
    for c in range(1, tm // _CHUNK + 1):
        @pl.when(jnp.logical_and(chunks == c, hi > lo))
        def _(c=c):
            row = first * _CHUNK + lax.broadcasted_iota(
                jnp.int32, (c * _CHUNK, 1), 0)
            piece(pl.ds(pl.multiple_of(first * _CHUNK, _CHUNK), c * _CHUNK),
                  jnp.logical_and(row >= lo, row < hi))


def _for_the_slabs(width, slab):
    """``slab(cols)`` for each ``_SLAB``-wide run of columns of ``width``,
    in a loop: a kernel's code is one slab's matmul a run length, not the
    whole width's (code lives in HBM beside the model)."""
    if width <= _SLAB or width % _SLAB:
        slab(slice(None))
        return
    pl, _ = _ps._pallas()

    def body(s, carry):
        slab(pl.ds(pl.multiple_of(s * _SLAB, _SLAB), _SLAB))
        return carry

    lax.fori_loop(0, width // _SLAB, body, None)


# --- forward and dgrad -----------------------------------------------------
@functools.partial(jax.jit, static_argnames=(
    "tm", "panel", "transposed", "vmem_limit", "interpret"))
def _gmm(rows, w, gr, *, tm, panel, transposed, vmem_limit, interpret):
    """``rows[group e] @ w[e]`` (``transposed``: ``@ w[e].T``, read as
    stored), zeros in every row past ``counts.sum()``. Grid (panels of the
    output width, visits); a panel of one expert's matrix stays in VMEM
    over the visits of its group while the next group's is in flight. The
    visit of a dead row tile (``_visit_lists``) comes under the last
    group, whose rows end before it: no product, no weight copy (the
    ordinal of the visit before it) and no row tile fetched (the input's
    block is the last live tile's again, which the pipeline does not copy
    twice); it costs the store of its zeros."""
    pl, pltpu = _ps._pallas()
    m, depth = rows.shape
    e, k, n = w.shape
    width = k if transposed else n
    assert depth == (n if transposed else k)
    panels = width // panel
    wshape = (panel, n) if transposed else (k, panel)
    cast = w.dtype != rows.dtype
    dims = (((1,), (1,)), ((), ())) if transposed else (((1,), (0,)), ((), ()))

    def kernel(offsets, group_ids, m_tile_ids, ordinal, order, nvisited,
               rows_ref, w_hbm, out_ref, wbuf, sem, wcast=None):
        j, i = pl.program_id(0), pl.program_id(1)
        group, nth = group_ids[i], ordinal[i]
        prev = jnp.maximum(i - 1, 0)
        # no group has rows: every visit is a dead tile's, no weight moves
        first = jnp.logical_and(
            nvisited[0] > 0, jnp.logical_or(i == 0, ordinal[prev] != nth))
        slot = (j * nvisited[0] + nth) % 2

        def fetch(g, p, s):
            at = pl.ds(pl.multiple_of(p * panel, _LANES), panel)
            src = w_hbm.at[g, at, :] if transposed else w_hbm.at[g, :, at]
            return pltpu.make_async_copy(src, wbuf.at[s], sem.at[s])

        @pl.when(first)
        def _next_weights():
            @pl.when(jnp.logical_and(i == 0, j == 0))
            def _():
                fetch(group, j, slot).start()

            wrap = nth + 1 == nvisited[0]
            nxt_panel = j + wrap.astype(jnp.int32)

            @pl.when(nxt_panel < panels)
            def _():
                fetch(order[jnp.where(wrap, 0, nth + 1)], nxt_panel,
                      1 - slot).start()

            fetch(group, j, slot).wait()
            if cast:
                # float32 -> bfloat16 in VMEM, a band of rows at a time
                def band(b, carry):
                    at = pl.ds(pl.multiple_of(b * _CHUNK, _CHUNK), _CHUNK)
                    wcast[at, :] = wbuf[slot, at, :].astype(rows.dtype)  # graftlint: allow=trace-purity(a store into a Pallas scratch ref is the kernel's output, not Python state)
                    return carry

                lax.fori_loop(0, wshape[0] // _CHUNK, band, None)

        wmat = wcast if cast else wbuf.at[slot]
        m_tile = m_tile_ids[i]
        seen = jnp.logical_and(i > 0, m_tile_ids[prev] == m_tile)

        @pl.when(jnp.logical_not(seen))
        def _():  # rows no group owns (past counts.sum()) read zero
            out_ref[...] = jnp.zeros_like(out_ref)

        def piece(at, mask):
            def slab(cols):
                wslab = wmat[cols, :] if transposed else wmat[:, cols]
                prod = lax.dot_general(rows_ref[at, :], wslab, dims,
                                       preferred_element_type=jnp.float32)
                # the run's other rows are other groups', written or to be
                old = out_ref[at, cols].astype(jnp.float32)
                out_ref[at, cols] = jnp.where(mask, prod, old).astype(
                    out_ref.dtype)

            _for_the_slabs(panel, slab)

        _for_the_groups_rows(offsets, group, m_tile, tm, piece)

    scratch = [pltpu.VMEM((2,) + wshape, w.dtype),
               pltpu.SemaphoreType.DMA((2,))]
    if cast:
        scratch.append(pltpu.VMEM(wshape, rows.dtype))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, width), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            in_specs=[
                # a dead tile's visit fetches no rows: the last live tile's
                # block again
                pl.BlockSpec((tm, depth), lambda j, i, o, g, mt, *_: (
                    jnp.minimum(mt[i], jnp.maximum(o[e] - 1, 0) // tm), 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tm, panel),
                                   lambda j, i, o, g, mt, *_: (mt[i], j)),
            grid=(panels, gr.visits),
            scratch_shapes=scratch,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(w.size * w.dtype.itemsize
                            + (m * depth * panels + m * width)
                            * rows.dtype.itemsize)),
        interpret=interpret,
        name="moe_gmm_dgrad" if transposed else "moe_gmm",
    )(gr.offsets, gr.group_ids, gr.m_tile_ids, gr.ordinal, gr.order,
      gr.nvisited, rows, w)


# --- wgrad -----------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=(
    "tm", "panel", "vmem_limit", "interpret"))
def _tgmm(rows, g, gr, *, tm, panel, vmem_limit, interpret):
    """(E, K, N) in ``rows.dtype``: ``rows[group e].T @ g[group e]``,
    zeros for an expert with no rows. Grid (panels of N, visits); a
    float32 (K, panel) accumulator is written out when the group ends."""
    pl, pltpu = _ps._pallas()
    m, k = rows.shape
    n = g.shape[1]
    e = gr.counts.shape[0]
    panels = n // panel

    def kernel(offsets, group_ids, m_tile_ids, rows_ref, g_ref, out_ref, acc):
        i = pl.program_id(1)
        group = group_ids[i]
        first = jnp.logical_or(
            i == 0, group_ids[jnp.maximum(i - 1, 0)] != group)
        last = jnp.logical_or(
            i == pl.num_programs(1) - 1,
            group_ids[jnp.minimum(i + 1, pl.num_programs(1) - 1)] != group)

        @pl.when(first)
        def _():
            acc[...] = jnp.zeros_like(acc)

        def piece(at, mask):
            def slab(cols):
                # zero rows of g outside the group: their products vanish.
                # Always "+=": Mosaic accumulates into the matmul, and an
                # assignment on a group's first visit measured 12% slower
                gt = g_ref[at, cols]
                acc[:, cols] += lax.dot_general(
                    rows_ref[at, :], jnp.where(mask, gt, jnp.zeros_like(gt)),
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

            _for_the_slabs(panel, slab)

        _for_the_groups_rows(offsets, group, m_tile_ids[i], tm, piece)

        @pl.when(last)
        def _():
            out_ref[...] = acc[...].astype(out_ref.dtype)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((e, k, n), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, i, o, gi, mt: (mt[i], 0)),
                pl.BlockSpec((tm, panel), lambda j, i, o, gi, mt: (mt[i], j)),
            ],
            out_specs=pl.BlockSpec(
                (None, k, panel), lambda j, i, o, gi, mt: (gi[i], 0, j)),
            grid=(panels, gr.wgrad_visits),
            scratch_shapes=[pltpu.VMEM((k, panel), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(e * k * n + m * k * panels + m * n)
            * rows.dtype.itemsize),
        interpret=interpret,
        name="moe_gmm_wgrad",
    )(gr.offsets, gr.wgrad_group_ids, gr.wgrad_m_tile_ids, rows, g)




# --- the differentiable op -------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul(rows, w, gr, plan, interpret=False):
    """``rows`` (M, K) sorted by group times ``w`` (E, K, N) as stored;
    ``gr = groups(counts, M, plan)`` with ``counts.sum() <= M``, zeros in
    the rows past it (the module's docstring has the contract); tiles from
    ``plan`` (a ``Plan``): the kernels, in a program lowered for a TPU.
    ``interpret`` runs them in Pallas's interpreter (tests on the CPU)."""
    return _ps._kernel(_gmm, (rows, w, gr), tm=plan.tm, panel=plan.tn,
                       transposed=False, vmem_limit=plan.vmem_limit,
                       interpret=interpret)


def _fwd(rows, w, gr, plan, interpret):
    return grouped_matmul(rows, w, gr, plan, interpret), (rows, w, gr)


def _bwd(plan, interpret, res, g):
    rows, w, gr = res
    kw = dict(vmem_limit=plan.vmem_limit, interpret=interpret)
    g = g.astype(rows.dtype)
    return (_ps._kernel(_gmm, (g, w, gr), tm=plan.tm, panel=plan.tk,
                        transposed=True, **kw),
            _ps._kernel(_tgmm, (rows, g, gr), tm=plan.tmw, panel=plan.tw,
                        **kw).astype(w.dtype), None)


grouped_matmul.defvjp(_fwd, _bwd)
