"""Ring attention — sequence/context parallelism over a device mesh.

NEW capability beyond the reference (SURVEY.md §2.5: the reference's only
long-sequence tool is bucketing). Implements blockwise ring attention
(Liu et al., "Ring Attention with Blockwise Transformers"): Q/K/V are
sharded along the sequence axis over a mesh axis ``sp``; each device
computes online-softmax partial attention against its local K/V block while
K/V blocks rotate around the ring via ``lax.ppermute`` over ICI, overlapping
communication with the matmuls. Memory per chip is O(T/n), enabling
sequences n× longer than one chip's HBM allows.

Numerics: online softmax (running max + normaliser) in f32 regardless of
input dtype, exact to within reordering — validated against full attention
in tests/test_ring_attention.py on the 8-device CPU mesh.

Without a mesh the same online softmax runs over blocks of queries on one
device (:func:`blockwise_attention`): no ``(T, T)`` score tensor is ever held
or saved for backward, and a causal block reads only the keys at or before
its own end. Where the rule (:func:`kernel_plan`: one TPU, a bfloat16 trunk,
head widths the kernels take, T a multiple of a block) says so, its forward
and backward are the Pallas kernels of ``ops/flash_attention.py``, in which a
score tile lives only in VMEM; otherwise (the CPU, float32, odd shapes)
:func:`_softmax_block` in ``jax.numpy``, whose float32 score tiles XLA
writes to HBM. The ring path is ``jax.numpy`` on every platform.

Two widths: queries and keys (B, H, T, Dk) are scored over Dk, values (B,
Hkv, T, Dv) are weighed into an output (B, H, T, Dv). They are equal in
most models; a latent-attention head (DeepSeek-V3's) has keys of 192 = 128
+ 64 rotated and values of 128. Every path here takes both, unpadded.

A selection (:func:`selected_attention`; DeepSeek-V3.2's sparse attention):
a small indexer scores every earlier token for every query, each query
keeps its ``top_k`` best and the softmax runs over those alone. The mask is
then computed in the program, a row at a time, and differs for every query.
The one-device path takes it: where the rule says so (one TPU, a bfloat16
trunk and indexer) in the kernels (:func:`selected_kernels`: the row's
threshold is found in VMEM and the kept pairs reach the attention kernels as
int8 tiles), else in ``jax.numpy`` blocks (:func:`selected_attention`, which
is also the specification and the tests' oracle). The ring refuses it by
name.

Block diffusion (:func:`diffusion_attention`; Arriola et al. 2025,
arXiv:2503.09573): the batch holds two copies of every row, a noised one and
a clean one; attention is bidirectional inside a block of positions and
causal across blocks, and the noised copy reads its own block and the clean
copy's earlier ones. Two causal walks cut by block, which visit no tile the
mask empties, and the noised copy's own blocks: one more tile of the strict
walk's kernels where they engage, else little ``jax.numpy`` squares joined
to it by their log-sum-exp. One device only; the ring refuses it by name.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
from ..ops.registry import keep, platform_of
from .compat import shard_map as _shard_map


def _softmax_block(q, k_blk, v_blk, mask, scale, o, m, l):
    """One online-softmax step, the body both attentions share: fold the
    keys/values of one block into the running output ``o`` (B, H, Tq, D),
    row maximum ``m`` and normaliser ``l`` (B, H, Tq), all float32.

    float32 operands multiply at HIGHEST precision; bfloat16 operands take
    the MXU's native passes and accumulate in float32. ``mask`` (Tq, Tk),
    or (B, 1, Tq, Tk) where it differs by row of the batch (a selection),
    is True where a query may see a key, or None.
    """
    from ..ops.defs_tensor import matmul_precision

    prec = matmul_precision(q.dtype)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk, precision=prec,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(_over_heads(mask), s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # guard fully-masked rows (m_new == -inf)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l = l * alpha + jnp.sum(p, axis=-1)
    o = o * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v_blk.dtype), v_blk, precision=prec,
        preferred_element_type=jnp.float32)
    return o, m_new, l


def _over_heads(mask):
    """A (Tq, Tk) mask against (B, H, Tq, Tk) scores; one that has a batch
    axis already, (B, 1, Tq, Tk), as it is."""
    return mask[None, None] if mask.ndim == 2 else mask


def _ring_attn_shard(q, k, v, axis_name, causal, scale):
    """Per-device body under shard_map.

    q, k: (B, H, Tl, Dk), v: (B, H, Tl, Dv) local sequence blocks.
    Returns (B, H, Tl, Dv) attention outputs for the local queries.
    """
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, H, Tl, _ = q.shape
    qf = q.astype(jnp.float32)

    # accumulators are per-device state (varying over the ring axis)
    def _vary(x):
        return jax.lax.pcast(x, axis_name, to="varying")

    o = _vary(jnp.zeros((B, H, Tl, v.shape[-1]), jnp.float32))
    m = _vary(jnp.full((B, H, Tl), -jnp.inf, jnp.float32))
    l = _vary(jnp.zeros((B, H, Tl), jnp.float32))
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(i, carry):
        k_blk, v_blk, o, m, l = carry
        src = (my_idx - i) % n  # which sequence block this k/v holds
        mask = None
        if causal:
            q_pos = my_idx * Tl + jnp.arange(Tl)
            k_pos = src * Tl + jnp.arange(Tl)
            mask = q_pos[:, None] >= k_pos[None, :]
        o, m, l = _softmax_block(
            qf, k_blk.astype(jnp.float32), v_blk.astype(jnp.float32), mask,
            scale, o, m, l)
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_next, v_next, o, m, l)

    k_blk, v_blk, o, m, l = jax.lax.fori_loop(
        0, n, body, (k, v, o, m, l)
    )
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


BLOCK_Q = 512  # queries a block: its score tile is (B, H, 512, <= T) float32
# Sizes the blocks of the ``jax.numpy`` fall-back only (the kernels keep a
# tile in VMEM and take their tiles from ``flash_attention.plan``). There a
# block's float32 score tile is written once and re-read by each of the
# softmax's element-wise passes. On a v5e (128 MiB of fast memory) a tile of
# 160 MiB made a window layer's forward 5.09 ms at 32 heads, T 4096, and one
# of 72 MiB 1.64 ms (blocks of 512 / 256 queries; forward + backward 10.8 /
# 7.5 ms; the full layer 12.9 / 9.8 at 256 / 128 MiB; PERF.md section 6,
# PR 32), so a block is the largest whose tile stays within this.
SCORE_TILE_BYTES = 128 << 20


def block_q_of(batch, heads, T, window=0):
    """Queries a block: the largest of 512, 256, 128 whose float32 score
    tile (batch x heads x block x the keys it reads: all T, or the band's
    ``window + block``) is at most ``SCORE_TILE_BYTES``; 128 if none."""
    for block in (BLOCK_Q, 256, 128):
        keys = min(T, window + block) if window else T
        if 4 * batch * heads * block * keys <= SCORE_TILE_BYTES:
            return block
    return 128


def block_plan(T, block_q, causal, window=0):
    """[(first query, end of queries, first key, end of keys)]: the keys a
    block of queries reads. A causal block stops at its own end; under a
    ``window`` (a query reads the keys ``i - window < j <= i``) it starts
    at the query block that holds the first key its band touches, so the
    key blocks outside the band are never read: skipped, not masked."""
    plan = []
    for a in range(0, T, block_q):
        b = min(a + block_q, T)
        first = max(0, a - window + 1) // block_q * block_q if window else 0
        plan.append((a, b, first, b if causal else T))
    return plan


def scored_pairs(T, causal, window=0, block_q=BLOCK_Q):
    """Query-key pairs one head scores under :func:`block_plan`: the sizes
    of the score tiles the ``jax.numpy`` blocks compute, forward (their
    backward recomputes the same tiles)."""
    return sum((b - a) * (end - first)
               for a, b, first, end in block_plan(T, block_q, causal, window))


def kept_pairs(T, n):
    """Query-key pairs one head keeps over ``T`` causal queries that each
    keep at most ``n`` keys, exactly: a band of ``n`` (``window``) or a
    selection of ``n`` (``select_top_k``); query ``t`` its ``min(t + 1,
    n)``."""
    n = min(n, T)
    return n * (n + 1) // 2 + (T - n) * n


def _q_blocks(T, block_q, causal, window=0):
    """:func:`block_plan` with each block's mask (queries, keys), None
    where the attention is not causal."""
    blocks = []
    for a, b, first, end in block_plan(T, block_q, causal, window):
        mask = None
        if causal:
            mask = jnp.arange(a, b)[:, None] >= jnp.arange(first, end)[None, :]
            if window:
                mask = jnp.logical_and(
                    mask, jnp.arange(a, b)[:, None]
                    - jnp.arange(first, end)[None, :] < window)
        blocks.append((a, b, first, end, mask))
    return blocks


def _fold(x, kv_heads):
    """(B, Hq, Tq, D) -> (B, Hkv, G * Tq, D): the G query heads that share
    a key/value head laid end to end along the query axis, so one matmul a
    key/value head serves its whole group and k and v are never repeated
    (their gradients come out summed over the group). Identity where the
    head counts are equal."""
    B, H, T, D = x.shape
    return x if H == kv_heads else x.reshape(B, kv_heads, H // kv_heads * T,
                                             D)


def _unfold(x, heads):
    B, kv, GT, D = x.shape
    return x if kv == heads else x.reshape(B, heads, GT * kv // heads, D)


def _group_mask(mask, group):
    return mask if mask is None or group == 1 else jnp.tile(mask, (group, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def blockwise_attention(q, k, v, causal, scale, block_q=BLOCK_Q, window=0,
                        kernels=None, interpret=False):
    """softmax(q k^T * scale [+ causal mask]) v on one device; q (B, H, T,
    Dk), k (B, Hkv, T, Dk) and v (B, Hkv, T, Dv) with Hkv dividing H (query
    head n reads key/value head n // (H / Hkv)), output (B, H, T, Dv) in
    their dtype. ``window``
    (causal only): a query reads only the ``window`` keys that end at
    itself, and only the key blocks its band touches are visited. Memory is
    linear in T, forward and backward: the backward pass keeps q, k, v, the
    output and the rows' log-sum-exp, and recomputes the scores from them.
    The last two are named (``registry.keep``): under per-operator
    recomputation (``MXNET_BACKWARD_DO_MIRROR``) they are kept and the
    forward does not run again in backward.

    ``kernels`` (a ``flash_attention.Plan``, from the rule
    :func:`kernel_plan`): the Pallas kernels, whose score tiles never leave
    VMEM, in a program lowered for a TPU; None: ``jax.numpy`` blocks of
    ``block_q`` queries (:func:`block_plan`). ``interpret`` runs the
    kernels in Pallas's interpreter (tests on the CPU)."""
    return _blockwise_fwd(q, k, v, causal, scale, block_q, window, kernels,
                          interpret)[0]


def kernel_plan(dtype, q_shape, kv_heads, causal, window=0, platform=None,
                value_dim=None, select_top_k=0, index_query=None,
                diffusion_block=0):
    """The rule of the one-device path: the kernels' tiles
    (``ops/flash_attention.plan``) for queries ``q_shape`` (B, H, T, Dk) of
    ``dtype`` over ``kv_heads`` whose values are ``value_dim`` wide (None:
    Dk) in a program lowered for ``platform`` (the
    executor's, through ``OpMode.platform``; None: jax's default backend)
    in a process that holds one TPU, or None: the ``jax.numpy`` blocks (the
    CPU, several chips, float32, head widths the kernels do not take, T no
    multiple of a block; under a selection, ``select_top_k`` keys a query
    chosen by an indexer whose queries are ``index_query`` (B, J, T, Di),
    an indexer of another dtype or too narrow; under ``diffusion_block`` a
    block that is no power of two or wider than 128). The op and its launch counts
    (``defs_contrib._ring_attention_counts``) ask it with the same
    arguments. A bare traced call (no executor, ``platform`` None)
    assumes the default backend: a plain ``jax.jit`` for the CPU in a
    process that holds a TPU has to say ``platform="cpu"``, or it traces
    Mosaic calls."""
    from ..ops import flash_attention, pallas_support

    _, heads, T, D = q_shape
    return flash_attention.plan(
        platform or jax.default_backend(),
        pallas_support.attached_vmem_bytes(), dtype, heads, kv_heads, T, D,
        causal, window, value_dim, select_top_k,
        None if index_query is None else (
            index_query.dtype, index_query.shape[1], index_query.shape[3]),
        diffusion_block)


def _blockwise_fwd(q, k, v, causal, scale, block_q, window=0, kernels=None,
                   interpret=False):
    if window and not causal:
        raise MXNetError("attention: window needs causal=True")
    H, kv = q.shape[1], k.shape[1]
    if H % kv or v.shape[1] != kv:
        raise MXNetError(f"attention: {H} query heads over {kv} key and "
                         f"{v.shape[1]} value heads")
    if q.shape[-1] != k.shape[-1]:
        raise MXNetError(f"attention: queries of {q.shape[-1]} over keys of "
                         f"{k.shape[-1]}")

    if kernels is None:
        out, lse = _blocks_fwd(q, k, v, causal, scale, block_q, window)
    else:
        from ..ops import flash_attention

        out, lse = flash_attention.attention(q, k, v, kernels, scale, causal,
                                             window, interpret)
    # what backward reads beside the operands: under per-operator
    # recomputation the forward is not run again for them
    out, lse = keep((out, lse))
    return out, (q, k, v, out, lse)


def _blocks_fwd(q, k, v, causal, scale, block_q, window):
    """(out, log-sum-exp (B, H, T) float32) by ``jax.numpy`` blocks."""
    return _walk_fwd(_q_blocks(q.shape[2], block_q, causal, window), scale,
                     q, k, v, q.dtype)


def _walk_fwd(blocks, scale, q, k, v, dtype, empty_rows=False):
    """(out (B, H, T, Dv) in ``dtype``, log-sum-exp (B, H, T) float32) of
    one walk over ``blocks`` (:func:`_walk_grads`'s). ``empty_rows``: the
    masks may leave a row no key (the noised copy's first block on the
    clean one); such a row gives 0 and -inf, as a block with no key at all
    does. (Static, and off for a causal walk, whose every row sees itself:
    its lowered text is the one it had.)"""
    B, H, T, _ = q.shape
    kv, Dv = k.shape[1], v.shape[-1]
    group = H // kv
    f32 = jnp.float32
    outs, lses = [], []
    for a, b, first, end, mask in blocks:
        if end <= first:
            outs.append(jnp.zeros((B, H, b - a, Dv), dtype))
            lses.append(jnp.full((B, H, b - a), -jnp.inf, f32))
            continue
        rows = group * (b - a)
        o, m, l = _softmax_block(
            _fold(q[:, :, a:b], kv), k[:, :, first:end], v[:, :, first:end],
            _group_mask(mask, group), scale,
            jnp.zeros((B, kv, rows, Dv), f32),
            jnp.full((B, kv, rows), -jnp.inf, f32),
            jnp.zeros((B, kv, rows), f32))
        if empty_rows:   # o is 0 and m -inf there already: not 0 / 0
            l = jnp.where(l > 0, l, 1.0)
        outs.append(_unfold((o / l[..., None]).astype(dtype), H))
        lses.append((m + jnp.log(l)).reshape(B, H, b - a))
    return jnp.concatenate(outs, axis=2), jnp.concatenate(lses, axis=2)


def _blockwise_bwd(causal, scale, block_q, window, kernels, interpret, res,
                   d_out):
    if kernels is None:
        return _blocks_bwd(causal, scale, block_q, window, *res, d_out)
    from ..ops import flash_attention

    return flash_attention.attention_grads(*res, d_out, kernels, scale, causal,
                                           window, interpret)


def _blocks_bwd(causal, scale, block_q, window, q, k, v, out, lse, d_out):
    return _walk_grads(
        lambda: _q_blocks(q.shape[2], block_q, causal, window), scale, q, k,
        v, out, lse, d_out)


def _walk_grads(blocks, scale, q, k, v, out, lse, d_out):
    """(dq, dk, dv) of one walk over ``blocks()`` = [(first query, end of
    queries, first key, end of keys, mask or None)] (a function, so that
    the masks are traced where the walk starts, as they always were: a
    causal layer's lowered text is the one it had): the scores of each
    block are formed again from the rows' log-sum-exp ``lse``, which with
    ``out`` may be over MORE keys than this walk's (a row that another walk
    reads too): the gradients are then this walk's part of the joint
    softmax's."""
    from ..ops.defs_tensor import matmul_precision

    prec = matmul_precision(q.dtype)
    f32 = jnp.float32
    H, kv = q.shape[1], k.shape[1]
    group = H // kv

    def dot(spec, x, y):
        return jnp.einsum(spec, x, y, precision=prec,
                          preferred_element_type=f32)

    dq = []
    dk = jnp.zeros(k.shape, f32)
    dv = jnp.zeros(v.shape, f32)
    delta = jnp.sum(d_out.astype(f32) * out.astype(f32), axis=-1)
    for a, b, first, end, mask in blocks():
        if end <= first:    # a block that sees no key of this walk
            dq.append(jnp.zeros((q.shape[0], H, b - a, q.shape[3]), q.dtype))
            continue
        mask = _group_mask(mask, group)
        qb, kb, vb, gb = _fold(q[:, :, a:b], kv), k[:, :, first:end], \
            v[:, :, first:end], _fold(d_out[:, :, a:b], kv)
        s = dot("bhqd,bhkd->bhqk", qb, kb) * scale
        p = jnp.exp(s - _fold(lse[:, :, a:b, None], kv))
        if mask is not None:
            p = jnp.where(_over_heads(mask), p, 0.0)
        ds = p * (dot("bhqd,bhkd->bhqk", gb, vb)
                  - _fold(delta[:, :, a:b, None], kv)) * scale
        p, ds = p.astype(q.dtype), ds.astype(q.dtype)
        dv = dv.at[:, :, first:end].add(dot("bhqk,bhqd->bhkd", p, gb))
        dk = dk.at[:, :, first:end].add(dot("bhqk,bhqd->bhkd", ds, qb))
        dq.append(_unfold(dot("bhqk,bhkd->bhqd", ds, kb).astype(q.dtype), H))
    return (jnp.concatenate(dq, axis=2), dk.astype(k.dtype),
            dv.astype(v.dtype))


blockwise_attention.defvjp(_blockwise_fwd, _blockwise_bwd)


# --- block diffusion: a noised copy of every row reads a clean one ----------

def diffusion_kept_pairs(T, block):
    """Query-key pairs one head keeps over the two copies of a row of ``T``
    positions in blocks of ``block``: the clean copy's ``T (T + block) /
    2`` (block b sees blocks 0..b), the noised copy's ``T (T - block) / 2``
    of the clean one and ``T block`` of its own."""
    return T * (T + block)


def diffusion_plan(T, block_q, block, strict):
    """[(first query, end of queries, 0, end of keys)] of one causal walk
    whose diagonal is cut by blocks of ``block`` positions: a block of
    queries reads the keys up to the end of its last query's block, or
    (``strict``: the noised copy on the clean one) up to the start of it,
    which for the first queries is no key at all."""
    plan = []
    for a in range(0, T, block_q):
        b = min(a + block_q, T)
        start = (b - 1) // block * block
        plan.append((a, b, 0, start if strict else start + block))
    return plan


def diffusion_scored_pairs(T, block, block_q=BLOCK_Q):
    """Query-key pairs one head scores over the two copies of a row under
    the ``jax.numpy`` walks, forward: the tiles of :func:`diffusion_plan`,
    both of them, and the noised copy's little squares."""
    return sum((b - a) * end for strict in (False, True)
               for a, b, _, end in diffusion_plan(T, block_q, block, strict)
               ) + T * block


def _diffusion_blocks(T, block_q, block, strict):
    """:func:`diffusion_plan` with each block's mask (queries, keys)."""
    blocks = []
    for a, b, first, end in diffusion_plan(T, block_q, block, strict):
        rows = jnp.arange(a, b)[:, None] // block
        keys = jnp.arange(first, end)[None, :] // block
        blocks.append((a, b, first, end,
                       keys < rows if strict else keys <= rows))
    return blocks


def _own_scores(q, k, scale, block):
    """(B, Hkv, G, T / block, block, block) float32: every block of
    ``block`` positions of q (B, H, T, D) against the same block of k (B,
    Hkv, T, D), and the two operands as they were cut."""
    from ..ops.defs_tensor import matmul_precision

    B, H, T, D = q.shape
    kv = k.shape[1]
    qb = q.reshape(B, kv, H // kv, T // block, block, D)
    kb = k.reshape(B, kv, T // block, block, D)
    s = jnp.einsum("bhgnqd,bhnkd->bhgnqk", qb, kb,
                   precision=matmul_precision(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    return s, qb, kb


def _in_blocks(x, like):
    """Rows' numbers (B, H, T) as :func:`_own_scores` lays its rows out,
    with a last axis of one."""
    return x.reshape(like.shape[:5] + (1,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def diffusion_attention(q, k, v, scale, block, block_q=BLOCK_Q, kernels=None,
                        interpret=False):
    """Attention of a block-diffusion training step (Arriola et al. 2025,
    arXiv:2503.09573; SDAR, arXiv:2510.06303). The batch axis holds two
    copies of every row: q (2B, H, T, Dk), k (2B, Hkv, T, Dk), v (2B, Hkv,
    T, Dv), rows ``[0, B)`` the NOISED copies and rows ``[B, 2B)`` the
    CLEAN ones, row r and row r + B the same text at the same positions.
    With b(i) = i // ``block``, a query at position i sees a key at j iff

    ============  ==============  ===============
    query \\ key   noised          clean
    ============  ==============  ===============
    noised        b(j) == b(i)    b(j) < b(i)
    clean         never           b(j) <= b(i)
    ============  ==============  ===============

    and a row's softmax runs over everything it sees, of both copies
    together. Output (2B, H, T, Dv) in the operands' dtype. ``T`` is a
    multiple of ``block``.

    Three parts, none of which scores a tile the mask empties: the clean
    copy on itself and the noised copy on the clean one are causal walks
    whose diagonal is cut by block (:func:`diffusion_plan`), and the noised
    copy on its own block. ``kernels`` None: ``jax.numpy`` blocks of
    ``block_q`` queries, the specification; the own blocks are T / block
    little squares a head, ``block / T`` of the pairs, and the two parts of
    a noised row meet by their log-sum-exp; backward hands every part the
    rows' JOINED output and log-sum-exp, so each part's gradients are exact
    under the joint softmax. ``kernels`` (a ``flash_attention.Plan`` of the
    rule :func:`kernel_plan`): the fused kernels with that in-tile mask, q
    of one copy over k and v of the other, and the strict walk's take the
    noised copy's own keys and values beside each query block and score
    them as one more masked tile of the block (``flash_attention._fwd``,
    ``own=``): a noised row's softmax is formed once, jointly, inside the
    kernel, backward's ``dq`` gathers from both copies there and the noised
    copy's ``dk`` and ``dv`` are the kernel's; no square and no join is
    traced. Either way the clean copy's ``dk`` and ``dv`` are the sum over
    both copies' queries. Kept for backward beside the
    operands: the output and the log-sum-exp (``registry.keep``)."""
    return _diffusion_fwd(q, k, v, scale, block, block_q, kernels,
                          interpret)[0]


def _check_diffusion(q, k, v, block):
    H, kv = q.shape[1], k.shape[1]
    if H % kv or v.shape[1] != kv or q.shape[-1] != k.shape[-1]:
        raise MXNetError(f"attention: queries {q.shape} over keys {k.shape} "
                         f"and values {v.shape}")
    if q.shape[0] % 2 or k.shape[0] != q.shape[0] \
            or v.shape[0] != q.shape[0]:
        raise MXNetError(
            f"attention: diffusion_block={block} reads the batch axis as the "
            f"noised copies then the clean ones, an even count: got "
            f"{q.shape[0]} rows of queries, {k.shape[0]} of keys")
    if block < 1 or q.shape[2] % block:
        raise MXNetError(f"attention: diffusion_block={block} does not "
                         f"divide {q.shape[2]} positions")


def _diffusion_walks(q, k, v, scale, block, block_q, kernels, interpret):
    """((out, lse) of the clean copy on itself, (out, lse) of the noised
    copy), each (B, H, T, Dv) and (B, H, T) float32. The noised copy's are
    over the clean keys alone under the ``jax.numpy`` blocks, and over
    everything it sees under ``kernels`` (its own blocks are one more tile
    of the strict walk's kernel)."""
    half = q.shape[0] // 2
    T = q.shape[2]
    got = []
    for rows, strict in ((q[half:], False), (q[:half], True)):
        if kernels is None:
            got.append(_walk_fwd(
                _diffusion_blocks(T, block_q, block, strict), scale, rows,
                k[half:], v[half:], jnp.float32, empty_rows=strict))
        else:
            from ..ops import flash_attention

            got.append(flash_attention.attention(
                rows, k[half:], v[half:], kernels, scale, True, 0, interpret,
                diffusion=(block, strict),
                own=(k[:half], v[:half]) if strict else None))
    return got


def _diffusion_fwd(q, k, v, scale, block, block_q, kernels, interpret):
    from ..ops.defs_tensor import matmul_precision

    _check_diffusion(q, k, v, block)
    half = q.shape[0] // 2
    f32 = jnp.float32
    (clean, clean_lse), (noised, lse) = _diffusion_walks(
        q, k, v, scale, block, block_q, kernels, interpret)
    if kernels is None:
        early, early_lse = noised, lse
        with jax.named_scope("attention.own_block"):
            s, _, _ = _own_scores(q[:half], k[:half], scale, block)
            lse = jnp.logaddexp(early_lse, jax.nn.logsumexp(
                s, axis=-1).reshape(early_lse.shape))
            p = jnp.exp(s - _in_blocks(lse, s))
            vb = v[:half].reshape(s.shape[:2] + s.shape[3:5] + v.shape[-1:])
            own = jnp.einsum("bhgnqk,bhnkd->bhgnqd", p.astype(v.dtype), vb,
                             precision=matmul_precision(v.dtype),
                             preferred_element_type=f32).reshape(early.shape)
            noised = own + early.astype(f32) \
                * jnp.exp(early_lse - lse)[..., None]
    out = jnp.concatenate([noised.astype(q.dtype), clean.astype(q.dtype)])
    lse = jnp.concatenate([lse, clean_lse])
    out, lse = keep((out, lse))
    return out, (q, k, v, out, lse)


def _diffusion_bwd(scale, block, block_q, kernels, interpret, res, d_out):
    from ..ops.defs_tensor import matmul_precision

    q, k, v, out, lse = res
    half, T = q.shape[0] // 2, q.shape[2]
    f32 = jnp.float32
    d_out = d_out.astype(q.dtype)
    grads = []
    for rows, strict in ((slice(half, None), False), (slice(0, half), True)):
        if kernels is None:
            grads.append(_walk_grads(
                lambda strict=strict: _diffusion_blocks(
                    T, block_q, block, strict), scale,
                q[rows], k[half:], v[half:], out[rows], lse[rows],
                d_out[rows]))
        else:
            from ..ops import flash_attention

            grads.append(flash_attention.attention_grads(
                q[rows], k[half:], v[half:], out[rows], lse[rows],
                d_out[rows], kernels, scale, True, 0, interpret,
                diffusion=(block, strict),
                own=(k[:half], v[:half]) if strict else None))
    (dq_clean, dk_clean, dv_clean), (dq_noised, dk_early, dv_early,
                                     *own) = grads
    if kernels is None:
        dq_early = dq_noised
        with jax.named_scope("attention.own_block"):
            prec = matmul_precision(q.dtype)

            def dot(spec, x, y):
                return jnp.einsum(spec, x, y, precision=prec,
                                  preferred_element_type=f32)

            s, qb, kb = _own_scores(q[:half], k[:half], scale, block)
            vb = v[:half].reshape(kb.shape[:4] + v.shape[-1:])
            gb = d_out[:half].reshape(qb.shape[:5] + v.shape[-1:])
            delta = jnp.sum(d_out[:half].astype(f32) * out[:half].astype(f32),
                            axis=-1)
            p = jnp.exp(s - _in_blocks(lse[:half], s))
            ds = p * (dot("bhgnqd,bhnkd->bhgnqk", gb, vb)
                      - _in_blocks(delta, s)) * scale
            p, ds = p.astype(q.dtype), ds.astype(q.dtype)
            dq_own = dot("bhgnqk,bhnkd->bhgnqd", ds, kb).reshape(
                q[:half].shape)
            own = [
                dot("bhgnqk,bhgnqd->bhnkd", ds, qb).reshape(k[:half].shape),
                dot("bhgnqk,bhgnqd->bhnkd", p, gb).reshape(v[:half].shape)]
            dq_noised = (dq_early.astype(f32) + dq_own).astype(q.dtype)
    dk_own, dv_own = own
    return (jnp.concatenate([dq_noised, dq_clean]),
            jnp.concatenate([dk_own.astype(k.dtype), dk_clean + dk_early]),
            jnp.concatenate([dv_own.astype(v.dtype), dv_clean + dv_early]))


diffusion_attention.defvjp(_diffusion_fwd, _diffusion_bwd)


# --- a selection: each query keeps the keys its indexer scores highest -----

# Queries that share one extent of keys under a selection. Inside a span the
# blocks of queries are the steps of ONE loop (``lax.map`` / ``lax.scan``), so
# a row of 16 384 at blocks of 32 lowers 8 bodies a pass and not 512; every
# block of a span reads the keys up to the span's end, masked past its own
# diagonal: half a span more keys a query than the triangle, on average.
SELECT_SPAN = 2048
# What :func:`selected_attention`'s ``jax.numpy`` blocks run at: the programs
# the rule gives no kernels (the CPU, a float32 trunk or indexer, several
# chips, an index width or a T the tiles do not take); since PR 52 the Keye
# cell's bfloat16 layers on one TPU run :func:`selected_kernels` instead.
# Under a selection a block holds the main heads' float32 score tile AND the
# indexer's, each read by several element-wise passes (the mask, the 32
# passes of the threshold's bisection, the KL term). Measured on a v5e at the
# Keye cell's shapes (32 over 4 heads of 128, 16 index heads of 64, T 16 384,
# keep 2048; a layer alone, forward / forward + backward, PERF.md section 6,
# PR 51): blocks of 256 queries 117.7 / 340.4 ms, 128 105.5 / 288.9, 64 61.3
# / 244.1, 32 48.9 / 166.2, 16 58.3 / 191.6: the largest block whose main
# tile over all T keys is within this, half of ``SCORE_TILE_BYTES``.
SELECT_TILE_BYTES = 64 << 20


def select_block_q(batch, heads, T):
    """Queries a block under a selection: the largest of 512 ... 32 whose
    float32 score tile (batch x heads x block x T) is at most
    ``SELECT_TILE_BYTES``; 32 if none."""
    for block in (BLOCK_Q, 256, 128, 64, 32):
        if 4 * batch * heads * block * T <= SELECT_TILE_BYTES:
            return block
    return 32


def select_plan(T, block_q, span=SELECT_SPAN):
    """[(first query, end of queries and of keys, queries a block)] of the
    walk under a selection: spans of ``span`` queries (whole blocks of
    ``block_q``) whose blocks all read the keys ``[0, end)``."""
    span = max(span // block_q, 1) * block_q
    return [(a, min(a + span, T), math.gcd(min(a + span, T) - a, block_q))
            for a in range(0, T, span)]


def selected_scored_pairs(T, block_q, top_k, span=SELECT_SPAN):
    """Query-key pairs one head scores under a selection, forward: the
    tiles of :func:`select_plan`, or where ``top_k >= T`` (nothing to
    select: the dense causal blocks run) those of :func:`block_plan`."""
    if top_k >= T:
        return scored_pairs(T, True, 0, block_q)
    return sum((b - a) * b for a, b, _ in select_plan(T, block_q, span))


def index_scores(iq, ik, iw):
    """The indexer's scores ``I[b, t, s] = sum_j iw[b, j, t] * relu(iq[b, j,
    t] . ik[b, 0, s])``, (B, Tq, Tk) float32: ``iq`` (B, J, Tq, Di) over the
    ONE key head ``ik`` (B, 1, Tk, Di), the heads weighed by ``iw`` (B, J,
    Tq). The products are exact whatever the trunk (float32 operands at
    ``HIGHEST``, bfloat16 ones on the MXU's native pass: a product of two
    bfloat16 numbers is a float32 number) and are summed in float32, as a
    router's logits are: the choice made from them is discrete. The sum
    over the heads is element-wise, not a matmul at the default precision."""
    from ..ops.defs_tensor import matmul_precision

    s = jnp.einsum("bjqd,bkd->bjqk", iq, ik[:, 0],
                   precision=matmul_precision(iq.dtype),
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * iw.astype(jnp.float32)[..., None], axis=1)


def kth_largest(x, k):
    """The k-th largest of each row of float32 ``x`` (..., n), n >= k,
    exactly, by bisection on its bits: a float's bit pattern, its sign bit
    set if it is positive and every bit flipped if not, orders as the float
    does, and 32 passes of compare and count find the largest pattern that
    at least k elements reach. A pass is one fused comparison and sum over
    the row; ``lax.top_k`` at k = 2048 of 16 384 sorts it (a layer's forward
    at the Keye cell's shapes 181 ms against 106 on a v5e; PERF.md section
    6, PR 51). -inf where fewer than k elements are above it."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    top = jnp.uint32(1 << 31)
    ordered = jnp.where(bits >= top, ~bits, bits | top)

    def narrow(i, found):
        tried = found | (top >> i.astype(jnp.uint32))
        reach = jnp.sum(ordered >= tried[..., None], axis=-1)
        return jnp.where(reach >= k, tried, found)

    found = jax.lax.fori_loop(0, 32, narrow,
                              jnp.zeros(x.shape[:-1], jnp.uint32))
    return jax.lax.bitcast_convert_type(
        jnp.where(found >= top, found ^ top, ~found), jnp.float32)


def _causal(first, n_queries, n_keys):
    """(1, Tq, n_keys) bool: the keys at or before each of ``n_queries``
    positions from ``first`` on."""
    rows = first + jnp.arange(n_queries)
    return (rows[:, None] >= jnp.arange(n_keys)[None, :])[None]


def _selection(index, tau, causal):
    """(B, Tq, Tk) bool: the keys a block of queries keeps: the earlier keys
    (``causal``, :func:`_causal`) whose ``index`` reaches the row's threshold
    ``tau`` (B, Tq) (-inf: every earlier key). ``lax.top_k``'s set wherever
    the row's k-th and (k+1)-th scores differ; a row whose scores at the
    threshold are equal keeps them all, so more than k keys (rare: an
    indexer of J heads scores an exact 0 on a pair in 2^J)."""
    return jnp.logical_and(causal, index >= tau[..., None])


def _threshold(index, causal, top_k):
    """(B, Tq) float32: each row's ``top_k``-th largest causal score, -inf
    where the row has no more than ``top_k`` earlier keys."""
    if index.shape[-1] <= top_k:
        return jnp.full(index.shape[:2], -jnp.inf, jnp.float32)
    return kth_largest(jnp.where(causal, index, -jnp.inf), top_k)


def _rows_of(x, first, count):
    return jax.lax.dynamic_slice_in_dim(x, first, count, axis=2)


def _joined(blocks, axis=2):
    """(n, B, H, bq, ...) as a loop stacked its blocks -> (B, H, n * bq,
    ...)."""
    x = jnp.moveaxis(blocks, 0, axis)
    return x.reshape(x.shape[:axis] + (-1,) + x.shape[axis + 2:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def selected_attention(q, k, v, iq, ik, iw, scale, block_q, top_k,
                       loss_coef=0.0, span=SELECT_SPAN):
    """Causal attention in which each query keeps ``top_k`` keys:
    ``softmax_{s in S_t}(q_t . k_s * scale) v_s`` with ``S_t`` the
    ``min(top_k, t + 1)`` positions ``s <= t`` of largest
    :func:`index_scores` ``I[t, s]`` (``iq`` (B, J, T, Di), ``ik`` (B, 1, T,
    Di), ``iw`` (B, J, T): DeepSeek-V3.2's lightning indexer). q, k, v and
    the output as :func:`blockwise_attention`'s.

    A block of queries at a time: the block's ``I`` in float32, each row's
    ``top_k``-th largest, the mask ``causal & (I >= that)``
    (:func:`_selection`: ``lax.top_k``'s set where the row's scores at the
    threshold differ), the softmax under the mask. No (T, T) array
    outlives a block, forward or backward: backward keeps the operands, the
    output, the rows' log-sum-exp and thresholds (B, T), and recomputes
    ``I`` and the mask. The choice passes no gradient.

    ``loss_coef``: backward ADDS to the index operands the gradient of
    ``loss_coef * sum_{b, t} KL(P[b, t] || softmax_{S_t}(I[b, t]))``, ``P``
    the mean over the query heads of the probabilities above, a constant
    (the sparse stage's indexer loss; a sum over the rows, on the scale of
    ``SoftmaxOutput``'s gradient, as ``MoE``'s router terms are):
    ``loss_coef * (softmax_S(I) - P)`` on ``S_t`` pushed through ``I``.
    That is all the index operands ever receive: q, k and v get the
    gradient of the output alone, the index operands of this term alone.

    Where ``top_k >= T`` nothing is selected: output and the gradients of
    q, k, v are :func:`blockwise_attention`'s ``jax.numpy`` blocks bit for
    bit, and the index operands still learn from ``P``."""
    return _selected_fwd(q, k, v, iq, ik, iw, scale, block_q, top_k,
                         loss_coef, span)[0]


def _check_selection(q, iq, ik, iw):
    B, _, T, _ = q.shape
    if (iq.ndim != 4 or iq.shape[0] != B or iq.shape[2] != T
            or ik.shape != (B, 1, T, iq.shape[-1])
            or iw.shape != iq.shape[:3]):
        raise MXNetError(
            f"attention: index_query {iq.shape}, index_key {ik.shape} and "
            f"index_weight {iw.shape} are not (B, J, T, Di), (B, 1, T, Di) "
            f"and (B, J, T) for queries {q.shape}")


def _selected_fwd(q, k, v, iq, ik, iw, scale, block_q, top_k, loss_coef,
                  span):
    _check_selection(q, iq, ik, iw)
    B, H, T, _ = q.shape
    kv, Dv = k.shape[1], v.shape[-1]
    group = H // kv
    if top_k >= T:
        out, lse = _blocks_fwd(q, k, v, True, scale, block_q, 0)
        tau = jnp.full((B, T), -jnp.inf, jnp.float32)
    else:
        outs, lses, taus = [], [], []
        for a, b, bq in select_plan(T, block_q, span):
            keys, values, index_keys = k[:, :, :b], v[:, :, :b], ik[:, :, :b]

            def block(first):     # traced at once, by this span's lax.map
                with jax.named_scope("attention.select"):
                    index = index_scores(_rows_of(iq, first, bq), index_keys,
                                         _rows_of(iw, first, bq))
                    causal = _causal(first, bq, b)
                    tau = _threshold(index, causal, top_k)
                    kept = _selection(index, tau, causal)
                rows = group * bq
                o, m, l = _softmax_block(
                    _fold(_rows_of(q, first, bq), kv), keys, values,
                    jnp.tile(kept, (1, group, 1))[:, None], scale,
                    jnp.zeros((B, kv, rows, Dv), jnp.float32),
                    jnp.full((B, kv, rows), -jnp.inf, jnp.float32),
                    jnp.zeros((B, kv, rows), jnp.float32))
                return (_unfold((o / l[..., None]).astype(q.dtype), H),
                        (m + jnp.log(l)).reshape(B, H, bq), tau)

            o, e, t = jax.lax.map(block, jnp.arange(a, b, bq))
            outs.append(_joined(o))
            lses.append(_joined(e))
            taus.append(_joined(t, 1))
        out, lse, tau = (jnp.concatenate(x, axis=ax) for x, ax in
                         ((outs, 2), (lses, 2), (taus, 1)))
    out, lse, tau = keep((out, lse, tau))
    return out, (q, k, v, iq, ik, iw, out, lse, tau)


def _selected_walk(scale, block_q, loss_coef, span, q, k, v, iq, ik, iw, out,
                   lse, tau, d_out):
    """Gradients of q, k, v and of the index operands by one walk over the
    blocks of :func:`select_plan`: each block recomputes its ``I`` and its
    mask from the kept thresholds, its scores from the kept log-sum-exp,
    and, where ``loss_coef`` is not 0, forms ``P`` from the same
    probabilities for the indexer's term."""
    from ..ops.defs_tensor import matmul_precision

    prec = matmul_precision(q.dtype)
    f32 = jnp.float32
    B, H, T, _ = q.shape
    kv = k.shape[1]
    group = H // kv

    def dot(spec, x, y):
        return jnp.einsum(spec, x, y, precision=prec,
                          preferred_element_type=f32)

    delta = jnp.sum(d_out.astype(f32) * out.astype(f32), axis=-1)
    dk, dv = jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32)
    d_ik = jnp.zeros(ik.shape, f32)
    dqs, d_iqs, d_iws = [], [], []
    for a, b, bq in select_plan(T, block_q, span):
        keys, values, index_keys = k[:, :, :b], v[:, :, :b], ik[:, :, :b]

        def block(carry, first):  # traced at once, by this span's lax.scan
            dk, dv, d_ik = carry
            iq_b, iw_b = _rows_of(iq, first, bq), _rows_of(iw, first, bq)
            with jax.named_scope("attention.select"):
                if loss_coef:
                    index, pull = jax.vjp(index_scores, iq_b, index_keys,
                                          iw_b)
                else:
                    index = index_scores(iq_b, index_keys, iw_b)
                kept = _selection(
                    index, jax.lax.dynamic_slice_in_dim(tau, first, bq, 1),
                    _causal(first, bq, b))
            qb, gb = (_fold(_rows_of(x, first, bq), kv) for x in (q, d_out))
            s = dot("bhqd,bhkd->bhqk", qb, keys) * scale
            p = jnp.exp(s - _fold(_rows_of(lse, first, bq)[..., None], kv))
            p = jnp.where(jnp.tile(kept, (1, group, 1))[:, None], p, 0.0)
            ds = p * (dot("bhqd,bhkd->bhqk", gb, values)
                      - _fold(_rows_of(delta, first, bq)[..., None], kv)
                      ) * scale
            pc, ds = p.astype(q.dtype), ds.astype(q.dtype)
            dv = dv + dot("bhqk,bhqd->bhkd", pc, gb)
            dk = dk + dot("bhqk,bhqd->bhkd", ds, qb)
            dq = _unfold(dot("bhqk,bhkd->bhqd", ds, keys).astype(q.dtype), H)
            if not loss_coef:
                return (dk, dv, d_ik), (dq,)
            with jax.named_scope("attention.select"):
                # the heads' mean probability of each kept key, a constant
                target = jnp.sum(p.reshape(B, H, bq, b), axis=1) / H
                given = jax.nn.softmax(
                    jnp.where(kept, index, -jnp.inf), axis=-1)
                d_iq, d_keys, d_iw = pull(loss_coef * (given - target))
            return (dk, dv, d_ik + d_keys.astype(f32)), (dq, d_iq, d_iw)

        carry = tuple(jnp.zeros(x.shape, f32)
                      for x in (keys, values, index_keys))
        carry, got = jax.lax.scan(block, carry, jnp.arange(a, b, bq))
        dk = dk.at[:, :, :b].add(carry[0])
        dv = dv.at[:, :, :b].add(carry[1])
        d_ik = d_ik.at[:, :, :b].add(carry[2])
        dqs.append(_joined(got[0]))
        if loss_coef:
            d_iqs.append(_joined(got[1]))
            d_iws.append(_joined(got[2]))
    index_grads = (jnp.concatenate(d_iqs, axis=2).astype(iq.dtype),
                   d_ik.astype(ik.dtype),
                   jnp.concatenate(d_iws, axis=2).astype(iw.dtype)) \
        if loss_coef else tuple(jnp.zeros_like(x) for x in (iq, ik, iw))
    return (jnp.concatenate(dqs, axis=2), dk.astype(k.dtype),
            dv.astype(v.dtype)) + index_grads


def _selected_bwd(scale, block_q, top_k, loss_coef, span, res, d_out):
    q, k, v, iq, ik, iw, out, lse, _ = res
    if top_k < q.shape[2]:
        return _selected_walk(scale, block_q, loss_coef, span, *res, d_out)
    # nothing was selected: the dense blocks, and the indexer's term alone
    # from the walk
    main = _blocks_bwd(True, scale, block_q, 0, q, k, v, out, lse, d_out)
    if not loss_coef:
        return main + tuple(jnp.zeros_like(x) for x in (iq, ik, iw))
    return main + _selected_walk(scale, block_q, loss_coef, span, *res,
                                 d_out)[3:]


selected_attention.defvjp(_selected_fwd, _selected_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def selected_kernels(q, k, v, iq, ik, iw, scale, top_k, loss_coef, kernels,
                     interpret=False):
    """:func:`selected_attention` in the Pallas kernels of
    ``ops/flash_attention.py`` at the tiles ``kernels`` (a ``Plan`` of the
    rule :func:`kernel_plan`): no (queries x keys) array in float32, the
    main heads' or the indexer's, reaches HBM. Forward: ``select`` forms a
    query block's index scores into a row buffer in VMEM, finds each row's
    threshold there and writes the block's kept pairs as int8 (T x T a
    batch row, transient), which the attention kernel applies to every tile
    it visits. Backward: ``index_grads`` forms the index scores again,
    writes the kept pairs from the kept thresholds, keys by queries as the
    backward kernel's tiles are, and pulls the indexer's term back through
    them (``P`` from all the query heads' scores, formed again there in
    float32); then the backward kernel over those pairs. Kept beside the
    operands: the output, the rows' log-sum-exp, thresholds and the kept
    index scores' log-sum-exp. Where ``top_k >= T`` the dense kernels run
    as for causal attention, and the indexer's term is added."""
    return _selected_kernels_fwd(q, k, v, iq, ik, iw, scale, top_k,
                                 loss_coef, kernels, interpret)[0]


def _selected_kernels_fwd(q, k, v, iq, ik, iw, scale, top_k, loss_coef,
                          kernels, interpret):
    from ..ops import flash_attention

    _check_selection(q, iq, ik, iw)
    with jax.named_scope("attention.select"):
        tau, index_lse, kept = flash_attention.select(iq, ik, iw, kernels,
                                                      top_k, interpret)
    out, lse = flash_attention.attention(q, k, v, kernels, scale, True, 0,
                                         interpret, kept)
    out, lse, tau, index_lse = keep((out, lse, tau, index_lse))
    return out, (q, k, v, iq, ik, iw, out, lse, tau, index_lse)


def _selected_kernels_bwd(scale, top_k, loss_coef, kernels, interpret, res,
                          d_out):
    from ..ops import flash_attention

    q, k, v, iq, ik, iw, out, lse, tau, index_lse = res
    with jax.named_scope("attention.select"):
        index_grads, kept = flash_attention.index_grads(
            q, k, iq, ik, iw, lse, tau, index_lse, kernels, scale, top_k,
            loss_coef, interpret)
    return flash_attention.attention_grads(
        q, k, v, out, lse, d_out, kernels, scale, True, 0, interpret,
        kept) + tuple(index_grads)


selected_kernels.defvjp(_selected_kernels_fwd, _selected_kernels_bwd)


def _refuse_on_the_ring(q, k, window, select=None, diffusion_block=0):
    """The ring rotates whole key/value blocks of equal head count: it has
    neither the band's block plan nor grouped heads (ROADMAP Reach 3), a
    query's ``select_top_k`` best keys are chosen over the whole row, which
    no device of the ring holds, and under ``diffusion_block`` the noised
    copy reads another row's keys than its own."""
    if diffusion_block:
        raise MXNetError(
            f"RingAttention: diffusion_block={diffusion_block} is not "
            "supported on the sequence-parallel ring path; run it on one "
            "device (no mesh axis for the sequence)")
    if select is not None:
        raise MXNetError(
            f"RingAttention: select_top_k={select[3]} is not supported on "
            "the sequence-parallel ring path; run it on one device (no mesh "
            "axis for the sequence)")
    if window:
        raise MXNetError(
            f"RingAttention: window={window} is not supported on the "
            "sequence-parallel ring path; run it on one device (no mesh "
            "axis for the sequence)")
    if q.shape[1] != k.shape[1]:
        raise MXNetError(
            f"RingAttention: {q.shape[1]} query heads over {k.shape[1]} "
            "key/value heads are not supported on the sequence-parallel "
            "ring path; run it on one device")


def ring_attention(q, k, v, mesh=None, axis="sp", causal=False, scale=None,
                   window=0, select=None, diffusion_block=0):
    """Sequence-parallel attention.

    q, k (B, H, T, Dk) and v (B, H, T, Dv): jax arrays or NDArrays, sharded
    (or to be sharded) along T over mesh axis ``axis``. Returns (B, H, T, Dv)
    with the same sharding. With ``mesh=None`` it is
    :func:`blockwise_attention` on one device (same math), which alone has
    ``window``, key/value heads fewer than the query heads, ``select``
    (jax arrays ``(index_query, index_key, index_weight, top_k,
    loss_coef)``: :func:`selected_attention`) and ``diffusion_block``
    (:func:`diffusion_attention`: the batch axis is the noised copies then
    the clean ones).
    """
    from ..ndarray import NDArray

    wrap = isinstance(q, NDArray)
    if wrap:
        q, k, v = q._data, k._data, v._data
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])

    if mesh is None:
        out = _on_one_device(q, k, v, causal, scale, window, select=select,
                             diffusion_block=diffusion_block)
        return NDArray(out) if wrap else out
    _refuse_on_the_ring(q, k, window, select, diffusion_block)

    from jax.sharding import NamedSharding

    from .mesh import as_graft

    mesh = as_graft(mesh).mesh
    sharding = NamedSharding(mesh, _ring_spec(axis, None))
    q = jax.device_put(q, sharding)
    k = jax.device_put(k, sharding)
    v = jax.device_put(v, sharding)
    out = _jitted_ring(mesh, axis, causal, float(scale))(q, k, v)
    return NDArray(out) if wrap else out


@functools.lru_cache(maxsize=64)
def _jitted_ring(mesh, axis, causal, scale):
    """Compiled eager entry, cached per config — a fresh jit(partial(...))
    per call would retrace and recompile the ring every invocation."""
    return jax.jit(functools.partial(
        ring_attention_traced, mesh=mesh, axis=axis, causal=causal,
        scale=scale,
    ))


def _ring_spec(axis, batch_axis):
    from jax.sharding import PartitionSpec as P

    return P(batch_axis or None, None, axis, None)


def _on_one_device(q, k, v, causal, scale, window, platform=None,
                   select=None, diffusion_block=0):
    """:func:`blockwise_attention` with what the rule and ``block_q_of``
    say for these operands; ``platform`` None: where a concrete q lives,
    jax's default backend for a tracer. Under a selection
    :func:`selected_kernels` where the rule says so, else
    :func:`selected_attention` at ``select_block_q``'s blocks; under
    ``diffusion_block`` :func:`diffusion_attention`, whose rule and blocks
    are asked for ONE copy's rows."""
    platform = platform or platform_of([q])
    if diffusion_block:
        if not causal or window or select is not None:
            raise MXNetError(
                f"attention: diffusion_block={diffusion_block} needs "
                "causal=True, and takes neither window nor select_top_k")
        copy = (q.shape[0] // 2,) + tuple(q.shape[1:])
        return diffusion_attention(
            q, k, v, scale, diffusion_block,
            block_q_of(copy[0], copy[1], copy[2]),
            kernel_plan(q.dtype, copy, k.shape[1], True, 0, platform,
                        v.shape[-1], diffusion_block=diffusion_block))
    if select is not None:
        if not causal or window:
            raise MXNetError("attention: select_top_k needs causal=True and "
                             "no window")
        iq, ik, iw, top_k, loss_coef = select
        kernels = kernel_plan(q.dtype, q.shape, k.shape[1], True, 0,
                              platform, v.shape[-1], top_k, iq)
        if kernels is not None:
            return selected_kernels(q, k, v, iq, ik, iw, scale, top_k,
                                    loss_coef, kernels)
        return selected_attention(
            q, k, v, iq, ik, iw, scale,
            select_block_q(q.shape[0], q.shape[1], q.shape[2]), top_k,
            loss_coef, SELECT_SPAN)
    return blockwise_attention(
        q, k, v, causal, scale,
        block_q_of(q.shape[0], q.shape[1], q.shape[2], window), window,
        kernel_plan(q.dtype, q.shape, k.shape[1], causal, window, platform,
                    v.shape[-1]))


def ring_attention_traced(q, k, v, mesh, axis="sp", causal=False,
                          scale=None, batch_axis=None, window=0,
                          platform=None, select=None, diffusion_block=0):
    """Jit-safe ring attention for use INSIDE a traced program (the
    symbol-level ``_contrib_RingAttention`` op): placement is expressed as
    sharding constraints (not eager ``device_put``) and the ``shard_map``
    nests inside the caller's jit. On a combined mesh (e.g. dp×sp), pass
    ``batch_axis`` so the batch dim keeps its data-parallel sharding
    instead of being gathered/replicated over the other axes. ``platform``:
    what the caller's program is lowered for, where it knows
    (:func:`kernel_plan`); ``select`` and ``diffusion_block``: as
    :func:`ring_attention`'s."""
    from jax.sharding import NamedSharding

    from .mesh import as_graft

    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    mesh = getattr(as_graft(mesh), "mesh", None)
    if mesh is None or axis not in mesh.axis_names:
        return _on_one_device(q, k, v, causal, scale, window, platform,
                              select, diffusion_block)
    _refuse_on_the_ring(q, k, window, select, diffusion_block)
    if batch_axis is not None and batch_axis not in mesh.axis_names:
        raise MXNetError(f"mesh has no axis {batch_axis!r}")
    spec = _ring_spec(axis, batch_axis)
    sharding = NamedSharding(mesh, spec)
    q = jax.lax.with_sharding_constraint(q, sharding)
    k = jax.lax.with_sharding_constraint(k, sharding)
    v = jax.lax.with_sharding_constraint(v, sharding)
    return _shard_map(
        functools.partial(
            _ring_attn_shard, axis_name=axis, causal=causal, scale=scale
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=True,
    )(q, k, v)


def _full_attention(q, k, v, causal, scale):
    """The whole (B, H, T, T) score tensor at once: the tests' oracle for
    the ring and the blockwise paths, which no path of the program runs."""
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32) * scale,
        k.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST,
    )
    T = q.shape[2]
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    return out.astype(q.dtype)


def sequence_parallel_sharding(mesh, axis="sp"):
    """NamedSharding splitting the sequence axis (dim 2 of BHTD)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(None, None, axis, None))
