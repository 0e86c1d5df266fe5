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

Without a mesh the same online-softmax step (:func:`_softmax_block`) runs
over blocks of queries on one device (:func:`blockwise_attention`): no
``(T, T)`` score tensor is ever held or saved for backward, and a causal
block reads only the keys at or before its own end.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
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

    q, k, v: (B, H, Tl, D) local sequence blocks.
    Returns (B, H, Tl, D) attention outputs for the local queries.
    """
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, H, Tl, D = q.shape
    qf = q.astype(jnp.float32)

    # accumulators are per-device state (varying over the ring axis)
    def _vary(x):
        return jax.lax.pcast(x, axis_name, to="varying")

    o = _vary(jnp.zeros((B, H, Tl, D), jnp.float32))
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


def _q_blocks(T, block_q, causal):
    """[(first query, end of queries, end of the keys they read, mask)]:
    the mask (queries, keys) is None where the attention is not causal."""
    blocks = []
    for a in range(0, T, block_q):
        b = min(a + block_q, T)
        end = b if causal else T
        mask = (jnp.arange(a, b)[:, None] >= jnp.arange(end)[None, :]
                if causal else None)
        blocks.append((a, b, end, mask))
    return blocks


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def blockwise_attention(q, k, v, causal, scale, block_q=BLOCK_Q):
    """softmax(q k^T * scale [+ causal mask]) v on one device, a block of
    ``block_q`` queries at a time; q, k, v (B, H, T, D), output in their
    dtype. Memory is linear in T, forward and backward: the backward pass
    keeps q, k, v, the output and the rows' log-sum-exp, and recomputes
    each block's scores from them."""
    return _blockwise_fwd(q, k, v, causal, scale, block_q)[0]


def _blockwise_fwd(q, k, v, causal, scale, block_q):
    B, H, T, D = q.shape
    outs, lses = [], []
    for a, b, end, mask in _q_blocks(T, block_q, causal):
        o, m, l = _softmax_block(
            q[:, :, a:b], k[:, :, :end], v[:, :, :end], mask, scale,
            jnp.zeros((B, H, b - a, D), jnp.float32),
            jnp.full((B, H, b - a), -jnp.inf, jnp.float32),
            jnp.zeros((B, H, b - a), jnp.float32))
        outs.append((o / l[..., None]).astype(q.dtype))
        lses.append(m + jnp.log(l))
    out = jnp.concatenate(outs, axis=2)
    return out, (q, k, v, out, jnp.concatenate(lses, axis=2))


def _blockwise_bwd(causal, scale, block_q, res, d_out):
    from ..ops.defs_tensor import matmul_precision

    q, k, v, out, lse = res
    prec = matmul_precision(q.dtype)
    f32 = jnp.float32

    def dot(spec, x, y):
        return jnp.einsum(spec, x, y, precision=prec,
                          preferred_element_type=f32)

    dq = []
    dk = jnp.zeros(k.shape, f32)
    dv = jnp.zeros(v.shape, f32)
    delta = jnp.sum(d_out.astype(f32) * out.astype(f32), axis=-1)
    for a, b, end, mask in _q_blocks(q.shape[2], block_q, causal):
        qb, kb, vb, gb = q[:, :, a:b], k[:, :, :end], v[:, :, :end], \
            d_out[:, :, a:b]
        s = dot("bhqd,bhkd->bhqk", qb, kb) * scale
        p = jnp.exp(s - lse[:, :, a:b, None])
        if mask is not None:
            p = jnp.where(mask[None, None], p, 0.0)
        ds = p * (dot("bhqd,bhkd->bhqk", gb, vb)
                  - delta[:, :, a:b, None]) * scale
        p, ds = p.astype(q.dtype), ds.astype(q.dtype)
        dv = dv.at[:, :, :end].add(dot("bhqk,bhqd->bhkd", p, gb))
        dk = dk.at[:, :, :end].add(dot("bhqk,bhqd->bhkd", ds, qb))
        dq.append(dot("bhqk,bhkd->bhqd", ds, kb).astype(q.dtype))
    return (jnp.concatenate(dq, axis=2), dk.astype(k.dtype),
            dv.astype(v.dtype))


blockwise_attention.defvjp(_blockwise_fwd, _blockwise_bwd)


def ring_attention(q, k, v, mesh=None, axis="sp", causal=False, scale=None):
    """Sequence-parallel attention.

    q, k, v: jax arrays or NDArrays of shape (B, H, T, D), sharded (or to be
    sharded) along T over mesh axis ``axis``. Returns same-shaped output
    with the same sharding. With ``mesh=None`` it is
    :func:`blockwise_attention` on one device (same math).
    """
    from ..ndarray import NDArray

    wrap = isinstance(q, NDArray)
    if wrap:
        q, k, v = q._data, k._data, v._data
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])

    if mesh is None:
        out = blockwise_attention(q, k, v, causal, scale)
        return NDArray(out) if wrap else out

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


def ring_attention_traced(q, k, v, mesh, axis="sp", causal=False,
                          scale=None, batch_axis=None):
    """Jit-safe ring attention for use INSIDE a traced program (the
    symbol-level ``_contrib_RingAttention`` op): placement is expressed as
    sharding constraints (not eager ``device_put``) and the ``shard_map``
    nests inside the caller's jit. On a combined mesh (e.g. dp×sp), pass
    ``batch_axis`` so the batch dim keeps its data-parallel sharding
    instead of being gathered/replicated over the other axes."""
    from jax.sharding import NamedSharding

    from .mesh import as_graft

    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    mesh = getattr(as_graft(mesh), "mesh", None)
    if mesh is None or axis not in mesh.axis_names:
        return blockwise_attention(q, k, v, causal, scale)
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
