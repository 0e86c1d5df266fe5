"""The sum of a held round's rows into their tokens as one Pallas TPU kernel.

``sum_rows(a, tok, weight, runs, n, dtype, plan)`` is ``out[t] = sum over the
rows r with tok[r] == t of weight[r] * a[r]`` (``weight`` None: of ``a[r]``)
for the rows of one round of ``MoE``'s held range
(``ops/defs_transformer._held_round``): ``a`` (R, H) bfloat16, the sum in
float32 and rounded once to ``dtype`` (N, H). It is both row scatter-adds of
a round: the combine ``zeros.at[tok].add(y * weight)`` (float32 out) and the
backward of the dispatch ``x[tok]`` (unweighted, in x's dtype). XLA's scatter
moves one row at a time and reads, adds and writes each: 117-147 ns a row on
a v5e, a tenth of the rate at which a kernel copies (PERF.md section 6, PR
63).

What the kernel uses that XLA cannot see: the round's rows are the
assignments sorted by held expert, stably, and a token chooses an expert at
most once, so inside one expert's run the tokens strictly ascend. The rows
that add into a block of ``block`` consecutive tokens are therefore ``held``
contiguous runs of the round, one an expert, and ``runs`` ((N / block + 1) x
held int32, scalar-prefetched) says where each starts: ``runs[b, e]`` is the
first row of expert ``e`` whose token is at least ``b * block``, counted from
the round's first row and clipped to the round. ``MoE`` has them by dense
arithmetic over its routing (``block_runs``: a count a block and a cumulative
sum, no sort and no gather of scalars).

A grid step owns one (block, H) tile of the output. It copies its runs HBM ->
VMEM in chunks of ``chunk`` rows that start at a multiple of 16 (a bfloat16
tile's sublanes; a long run 128 rows a copy while that many are left),
packed one after the other, one block ahead of the sums (two slots, manual
DMA; a block's copies are all waited for before a row of it is read, since
they share semaphores), and with each chunk the same rows of ``meta``: the
row's token, and its weight's bits, repeated over a register's 128 lanes, so
that what says where a row goes travels with the row and lies along the
sublanes as the rows do. Rows a chunk holds beyond its run (the alignment, the
rest of a chunk) get the token -1. The sum is a product on the MXU: ``slab``
packed rows at a time, the (slab, block) matrix ``weight[r] * (tok[r] == t)``,
split into three bfloat16 terms that add up to the float32 weight exactly,
contracted over the rows with the chunk's bfloat16 rows in float32. A product
of two bfloat16 numbers is exact in float32, so the result is the float32 sum
of ``weight[r] * a[r]`` up to the order of the additions; the unweighted sum
needs one term. A block no run touches is written as zeros: nothing
initialises the output first. The kernel visits live rows only (a dead or
padded row is in no run), so its time follows the live count and not the
round's length.

``kernel_plan`` is the one rule that says whether the kernel engages and with
which blocks; the traced kernel is kept by ``pallas_support._kernel``'s
store.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import pallas_support as _ps

_LANES = 128
_GROUP = 16   # rows: where a chunk may start (a bfloat16 tile's sublanes)
_LONG = 128   # rows a DMA of a long run, while that many are left
_SEMS = 4     # DMA semaphores a slot and an array, taken in turn


class Plan(NamedTuple):
    """A grid step sums into ``block`` tokens; it copies ``chunk`` rows a
    DMA into slots of ``cap`` rows and multiplies ``slab`` of them at a
    time."""

    block: int
    chunk: int
    slab: int
    cap: int
    vmem_limit: int


# Tokens a grid step, rows a DMA and rows a product. On a v5e at the Mellum2
# cell's round (32 768 rows of 2304, 16 364 live, 8 experts), ms a weighted /
# an unweighted sum, host-timed (PERF.md section 6, PR 63): XLA's scatter-add
# 4.76 / 4.00, the kernel 0.80 / 0.53 (a weighted sum is bound by its three
# products, an unweighted one by the copy, 0.33); at the SDAR round (16
# experts, runs half as long) 3.69 / 2.98 against 1.22 / 0.81. What set the
# blocks was read on the kernel's first version, which marked a chunk after
# its own wait (0.13 ms a sum faster: 128 x 16 x 256 read 0.65 / 0.41
# there): 128 x 32 x 256 0.70 / 0.44, 256 x 32 x 256 0.82 / 0.40 (a block of
# 128 halves the products), 256 x 32 x 128 0.84 / 0.42; the output in
# products of 1152 / 768 / 256 columns 0.87 / 0.90 / 1.05 against 0.82 whole
# (the left operand is turned for every product: the whole width is one
# product); at the SDAR round 128 x 16 0.97 / 0.59 against 256 x 32's 0.98 /
# 0.48. No size in the rule: a layer alone, forward + backward, XLA's
# scatter-add against the kernel, ms (ibid.): Mellum2 (144 MiB of rows)
# 18.78 / 11.89, SDAR (128) 16.43 / 12.24, Keye-VL-2.0 (64) 9.26 / 6.52,
# ZAYA1 (32) 6.94 / 5.57, Kimi-Linear (9) 3.31 / 2.49, and on the first
# version kanana2-30b (24) 4.33 / 3.14, Qwen3-Next (20) 4.86 / 3.95, Trinity
# (16) 3.45 / 2.75: unlike the convolution and the rotation (PR 46, PR 59:
# level at 32 MiB) the scatter moves a row at a time at 113-293 ns whatever
# the round, and both its neighbours are custom calls already.
_BLOCK, _CHUNK, _SLAB = 128, 16, 256


def kernel_plan(platform, vmem_bytes, dtype, rows, n, h, held,
                top_k) -> Optional[Plan]:
    """The rule: the kernel's blocks for the row sums of a round of ``rows``
    rows of ``dtype`` (R, ``h``) into ``n`` tokens where ``held`` experts
    are held and a token chooses ``top_k``, in a program lowered for
    ``platform`` on a chip of ``vmem_bytes``, or None: XLA's scatter-add.
    ``MoE`` asks it only where its grouped matmuls engage
    (``defs_transformer._expert_plans``). It engages where the rows are
    bfloat16 and whole registers wide, the round whole long copies and the
    tokens whole blocks, and what a grid step keeps in VMEM is under half
    of it. ``cap``: a token is in a run at most once, so a block's runs
    hold at most ``block * min(top_k, held)`` rows, and each run's chunks
    at most ``_GROUP + chunk`` more."""
    if platform != "tpu" or not vmem_bytes:
        return None
    if (jnp.dtype(dtype) != jnp.bfloat16 or h % _LANES or rows % _LONG
            or n % _BLOCK):
        return None
    cap = _BLOCK * min(top_k, held) + held * (_GROUP + _CHUNK)
    cap = -(-cap // _SLAB) * _SLAB
    # two slots of rows and of meta; the output tile twice and a float32
    # one; a slab's rows, its three terms and two products; and 8 MiB of
    # room (what the kernel is allowed, not what it takes: asked for 36 MB
    # and more, libtpu 0.0.34 dies compiling a Mellum2 layer's gradient
    # for a v5e, a segmentation fault and no message; 30-31 MB at the
    # Mellum2 and SDAR rounds compile and run)
    need = (2 * cap * (h * 2 + 2 * _LANES * 4) + 3 * _BLOCK * h * 4
            + _SLAB * h * 2 + 4 * _SLAB * _BLOCK * 4 + 2 * _BLOCK * h * 4
            + (8 << 20))
    if need > vmem_bytes // 2:
        return None
    return Plan(_BLOCK, _CHUNK, _SLAB, cap, need)


def block_runs(member, starts, block):
    """``runs`` (N / block + 1, held) int32, in the rows of the whole sorted
    list: ``runs[b, e]`` = ``starts[e]`` + how many tokens below ``b *
    block`` chose held expert ``e``. ``member`` (N, held) bool: token t
    chose e; ``starts`` (held,): the first row of e's run. A round at
    ``first`` takes ``clip(runs - first, 0, rows)``."""
    n, held = member.shape
    a_block = jnp.sum(member.reshape(n // block, block, held), axis=1,
                      dtype=jnp.int32)
    below = jnp.concatenate([jnp.zeros((1, held), jnp.int32),
                             jnp.cumsum(a_block, axis=0)])
    return below + starts[None, :].astype(jnp.int32)


def row_meta(tok, weight):
    """(R, 128) int32, or (R, 256) with a weight: a row's token, then its
    float32 weight's bits, each over 128 lanes."""
    parts = [tok.astype(jnp.int32)]
    if weight is not None:
        parts.append(lax.bitcast_convert_type(weight.astype(jnp.float32),
                                              jnp.int32))
    return jnp.concatenate(
        [jnp.broadcast_to(p[:, None], (p.shape[0], _LANES)) for p in parts],
        axis=1)


def block_chunks(runs, blk, held, rows, chunk, piece, carry=None):
    """The copies of token block ``blk``: ``carry = piece(carry, at, size,
    first, lo, hi, nth)`` for every chunk of its ``held`` runs, in order:
    ``size`` rows of the round from row ``first`` go to ``at`` in the slot,
    of which rows ``[lo, hi)`` are the run's (and no earlier chunk's), on
    semaphore ``nth``. ``runs``: the flat clipped runs, a ref in the kernel
    or an array (the tests replay the copies of a whole round on the host).
    A chunk starts at a multiple of ``_GROUP`` rows of the round, so a run
    takes up to ``_GROUP - 1`` rows before it and the rest of its last
    chunk; a run is copied ``_LONG`` rows a DMA while that many are left
    and ``chunk`` rows a DMA after, so a run of a whole block of tokens (a
    collapsed router) is two DMAs and not nine. Returns (the rows packed,
    ``carry``)."""
    def run(e, state):
        packed, carry = state
        lo = runs[blk * held + e]
        hi = runs[(blk + 1) * held + e]
        start = lo // _GROUP * _GROUP
        chunks = jnp.where(hi > lo, (hi - start + chunk - 1) // chunk, 0)
        long = chunks // (_LONG // chunk)

        def some(size, due0, at0):
            def one(i, carry):
                due = due0 + i * size
                return piece(carry, at0 + i * size, size,
                             jnp.minimum(due, rows - size),
                             jnp.maximum(lo, due), hi,
                             (at0 // chunk + i) % _SEMS)
            return one

        carry = lax.fori_loop(0, long, some(_LONG, start, packed), carry)
        carry = lax.fori_loop(
            0, chunks - long * (_LONG // chunk),
            some(chunk, start + long * _LONG, packed + long * _LONG), carry)
        return packed + chunks * chunk, carry

    return lax.fori_loop(0, held, run, (0, carry))


@functools.partial(jax.jit, static_argnames=(
    "n", "dtype", "block", "chunk", "slab", "cap", "vmem_limit",
    "interpret"))
def _sum_rows(a, meta, runs, *, n, dtype, block, chunk, slab, cap,
              vmem_limit, interpret):
    pl, pltpu = _ps._pallas()
    rows, h = a.shape
    blocks, held = n // block, runs.shape[0] // (n // block + 1)
    weighted = meta.shape[1] == 2 * _LANES
    terms = 3 if weighted else 1
    wide = jnp.dtype(dtype) == jnp.float32

    def kernel(runs_ref, a_hbm, meta_hbm, out_ref, abuf, mbuf, sem,
               acc=None):
        b = pl.program_id(0)
        acc = out_ref if wide else acc

        def for_the_chunks(blk, slot, piece, copies=True):
            """``piece(copies, at, size, first, lo, hi)`` for every chunk of
            block ``blk``'s runs (``block_chunks``): its two DMAs into
            ``slot`` (``copies``: none where a pass only marks), where it
            sits there, the first row it holds and the rows of it that are
            the run's. Returns the rows packed."""
            def one(_, at, size, first, lo, hi, nth):
                at = pl.ds(pl.multiple_of(at, chunk), size)
                src = pl.ds(pl.multiple_of(first, _GROUP), size)
                piece([] if not copies else [
                    pltpu.make_async_copy(
                        a_hbm.at[src, :], abuf.at[slot, at, :],
                        sem.at[slot, 0, nth]),
                    pltpu.make_async_copy(
                        meta_hbm.at[src, :], mbuf.at[slot, at, :],
                        sem.at[slot, 1, nth])],
                      at, size, first, lo, hi)

            return block_chunks(runs_ref, blk, held, rows, chunk, one)[0]

        def start(copies, *_):
            for c in copies:
                c.start()

        @pl.when(b == 0)
        def _():
            # rows past a block's last chunk are multiplied by zeros: finite
            abuf[...] = jnp.zeros_like(abuf)
            for_the_chunks(0, 0, start)

        @pl.when(b + 1 < blocks)
        def _():
            for_the_chunks(b + 1, (b + 1) % 2, start)

        slot = b % 2

        def wait(copies, *_):
            for c in copies:
                c.wait()

        def mark(copies, at, size, first, lo, hi):
            row = first + lax.broadcasted_iota(jnp.int32, (size, _LANES), 0)
            mine = jnp.logical_and(row >= lo, row < hi)
            mbuf[slot, at, :_LANES] = jnp.where(  # graftlint: allow=trace-purity(a store into a Pallas scratch ref is the kernel's output, not Python state)
                mine, mbuf[slot, at, :_LANES], -1)

        # every copy of the block has landed before any row is marked: the
        # copies share semaphores, so one wait alone names no copy
        for_the_chunks(b, slot, wait)
        packed = for_the_chunks(b, slot, mark, copies=False)
        acc[...] = jnp.zeros_like(acc)  # graftlint: allow=trace-purity(a store into a Pallas ref is the kernel's output, not Python state)
        lane = lax.broadcasted_iota(jnp.int32, (slab, _LANES), 1)
        spot = lax.broadcasted_iota(jnp.int32, (slab, _LANES), 0)

        def a_slab(s, carry):
            at = pl.ds(pl.multiple_of(s * slab, slab), slab)
            tok = mbuf[slot, at, :_LANES] - b * block
            tok = jnp.where(spot + s * slab < packed, tok, -1)
            if weighted:
                w = lax.bitcast_convert_type(mbuf[slot, at, _LANES:],
                                             jnp.float32)
            hot = []
            for q in range(block // _LANES):
                hit = tok == lane + q * _LANES
                hot.append(jnp.where(hit, w, 0.0) if weighted
                           else hit.astype(jnp.float32))
            left = jnp.concatenate(hot, axis=1) if len(hot) > 1 else hot[0]
            parts = []
            for _ in range(terms):   # bfloat16 terms that add up to float32
                parts.append(left.astype(jnp.bfloat16))
                left = left - parts[-1].astype(jnp.float32)

            rows_ = abuf[slot, at, :]
            total = None
            for p in reversed(parts):   # the small terms first: their sum
                # is exact, so a lone row's product is rounded once
                prod = lax.dot_general(
                    p, rows_, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                total = prod if total is None else total + prod
            acc[...] += total  # graftlint: allow=trace-purity(a store into a Pallas ref is the kernel's output, not Python state)
            return carry

        lax.fori_loop(0, (packed + slab - 1) // slab, a_slab, None)
        if not wide:
            out_ref[...] = acc[...].astype(out_ref.dtype)

    scratch = [pltpu.VMEM((2, cap, h), a.dtype),
               pltpu.VMEM((2, cap, meta.shape[1]), jnp.int32),
               pltpu.SemaphoreType.DMA((2, 2, _SEMS))]
    if not wide:
        scratch.append(pltpu.VMEM((block, h), jnp.float32))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, h), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, h), lambda b, runs: (b, 0)),
            grid=(blocks,),
            scratch_shapes=scratch,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=pl.CostEstimate(   # at most: were every row live
            flops=2 * terms * rows * block * h, transcendentals=0,
            bytes_accessed=(rows * (h * a.dtype.itemsize + meta.shape[1] * 4)
                            + n * h * jnp.dtype(dtype).itemsize)),
        interpret=interpret,
        name="moe_row_sum",
    )(runs, a, meta)


def sum_rows(a, tok, weight, runs, n, dtype, plan, interpret=False):
    """(``n``, H) in ``dtype``: ``out[t]`` = the float32 sum over the rows r
    with ``tok[r] == t`` that lie in a run of ``weight[r] * a[r]``
    (``weight`` None: of ``a[r]``), rounded once. ``a`` (R, H) bfloat16,
    ``tok`` (R,) ascending inside each run, ``runs`` (n / plan.block + 1,
    held) int32 in the round's rows (``block_runs``, clipped): the kernel at
    ``plan``'s blocks. ``interpret`` runs it in Pallas's interpreter (tests
    on the CPU)."""
    return _ps._kernel(
        _sum_rows, (a, row_meta(tok, weight), runs.reshape(-1)), n=n,
        dtype=jnp.dtype(dtype).name, interpret=interpret, **plan._asdict())
