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
    the MXU's native passes and accumulate in float32. ``mask`` (Tq, Tk)
    is True where a query may see a key, or None.
    """
    from ..ops.defs_tensor import matmul_precision

    prec = matmul_precision(q.dtype)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk, precision=prec,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, -jnp.inf)
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
                value_dim=None):
    """The rule of the one-device path: the kernels' tiles
    (``ops/flash_attention.plan``) for queries ``q_shape`` (B, H, T, Dk) of
    ``dtype`` over ``kv_heads`` whose values are ``value_dim`` wide (None:
    Dk) in a program lowered for ``platform`` (the
    executor's, through ``OpMode.platform``; None: jax's default backend)
    in a process that holds one TPU, or None: the ``jax.numpy`` blocks (the
    CPU, several chips, float32, head widths the kernels do not take, T no
    multiple of a block). The op and its launch counts
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
        causal, window, value_dim)


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
    B, H, T, _ = q.shape
    kv, Dv = k.shape[1], v.shape[-1]
    group = H // kv
    outs, lses = [], []
    for a, b, first, end, mask in _q_blocks(T, block_q, causal, window):
        rows = group * (b - a)
        o, m, l = _softmax_block(
            _fold(q[:, :, a:b], kv), k[:, :, first:end], v[:, :, first:end],
            _group_mask(mask, group), scale,
            jnp.zeros((B, kv, rows, Dv), jnp.float32),
            jnp.full((B, kv, rows), -jnp.inf, jnp.float32),
            jnp.zeros((B, kv, rows), jnp.float32))
        outs.append(_unfold((o / l[..., None]).astype(q.dtype), H))
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
    for a, b, first, end, mask in _q_blocks(q.shape[2], block_q, causal,
                                            window):
        mask = _group_mask(mask, group)
        qb, kb, vb, gb = _fold(q[:, :, a:b], kv), k[:, :, first:end], \
            v[:, :, first:end], _fold(d_out[:, :, a:b], kv)
        s = dot("bhqd,bhkd->bhqk", qb, kb) * scale
        p = jnp.exp(s - _fold(lse[:, :, a:b, None], kv))
        if mask is not None:
            p = jnp.where(mask[None, None], p, 0.0)
        ds = p * (dot("bhqd,bhkd->bhqk", gb, vb)
                  - _fold(delta[:, :, a:b, None], kv)) * scale
        p, ds = p.astype(q.dtype), ds.astype(q.dtype)
        dv = dv.at[:, :, first:end].add(dot("bhqk,bhqd->bhkd", p, gb))
        dk = dk.at[:, :, first:end].add(dot("bhqk,bhqd->bhkd", ds, qb))
        dq.append(_unfold(dot("bhqk,bhkd->bhqd", ds, kb).astype(q.dtype), H))
    return (jnp.concatenate(dq, axis=2), dk.astype(k.dtype),
            dv.astype(v.dtype))


blockwise_attention.defvjp(_blockwise_fwd, _blockwise_bwd)


def _refuse_on_the_ring(q, k, window):
    """The ring rotates whole key/value blocks of equal head count: it has
    neither the band's block plan nor grouped heads (ROADMAP Reach 3)."""
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
                   window=0):
    """Sequence-parallel attention.

    q, k (B, H, T, Dk) and v (B, H, T, Dv): jax arrays or NDArrays, sharded
    (or to be sharded) along T over mesh axis ``axis``. Returns (B, H, T, Dv)
    with the same sharding. With ``mesh=None`` it is
    :func:`blockwise_attention` on one device (same math), which alone has
    ``window`` and key/value heads fewer than the query heads.
    """
    from ..ndarray import NDArray

    wrap = isinstance(q, NDArray)
    if wrap:
        q, k, v = q._data, k._data, v._data
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])

    if mesh is None:
        out = _on_one_device(q, k, v, causal, scale, window)
        return NDArray(out) if wrap else out
    _refuse_on_the_ring(q, k, window)

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


def _on_one_device(q, k, v, causal, scale, window, platform=None):
    """:func:`blockwise_attention` with what the rule and ``block_q_of``
    say for these operands; ``platform`` None: where a concrete q lives,
    jax's default backend for a tracer."""
    platform = platform or platform_of([q])
    return blockwise_attention(
        q, k, v, causal, scale,
        block_q_of(q.shape[0], q.shape[1], q.shape[2], window), window,
        kernel_plan(q.dtype, q.shape, k.shape[1], causal, window, platform,
                    v.shape[-1]))


def ring_attention_traced(q, k, v, mesh, axis="sp", causal=False,
                          scale=None, batch_axis=None, window=0,
                          platform=None):
    """Jit-safe ring attention for use INSIDE a traced program (the
    symbol-level ``_contrib_RingAttention`` op): placement is expressed as
    sharding constraints (not eager ``device_put``) and the ``shard_map``
    nests inside the caller's jit. On a combined mesh (e.g. dp×sp), pass
    ``batch_axis`` so the batch dim keeps its data-parallel sharding
    instead of being gathered/replicated over the other axes. ``platform``:
    what the caller's program is lowered for, where it knows
    (:func:`kernel_plan`)."""
    from jax.sharding import NamedSharding

    from .mesh import as_graft

    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    mesh = getattr(as_graft(mesh), "mesh", None)
    if mesh is None or axis not in mesh.axis_names:
        return _on_one_device(q, k, v, causal, scale, window, platform)
    _refuse_on_the_ring(q, k, window)
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
