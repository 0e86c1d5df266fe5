"""The fused attention kernels under the block-diffusion mask (``diffusion=``
of ``ops/flash_attention.py``: the causal walk with its diagonal cut by
blocks of positions, queries of one copy of a row over keys and values of the
other), in Pallas's interpreter on the CPU: bfloat16, one small shape,
forward and backward, against the ``jax.numpy`` blocks of
``ring_attention.diffusion_attention`` (which ``tests/test_sdar.py`` holds to
a dense softmax under the explicit mask); their visit list against a
brute-force count of the tiles the mask leaves something in; and that the
kernels trace to what they were when the mode is off. The compile for a
described v5e is the chip's (the cell's traced run)."""

import sys

import numpy as np
import pytest

import mxnet_tpu  # noqa: F401
import mxnet_tpu.parallel.ring_attention  # noqa: F401 (the module, below)
from mxnet_tpu.ops import flash_attention as fa

ra = sys.modules["mxnet_tpu.parallel.ring_attention"]


def _operands(seed, rows, heads, kv, length, d):
    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    return [jnp.asarray(rs.randn(*s), jnp.bfloat16) for s in (
        (rows, heads, length, d), (rows, kv, length, d),
        (rows, kv, length, d), (rows, heads, length, d))]


def _f32(x):
    return np.asarray(x, np.float32)


@pytest.mark.parametrize("bq,bk,block", [(128, 256, 4), (256, 128, 16)])
def test_kernels_match_the_blocks_forward_and_backward(bq, bk, block):
    """Both walks (the clean copy on itself, the noised copy on the clean
    one, whose first block sees no clean key at all) and the join with the
    noised copy's own blocks; the clean copy's ``dk`` and ``dv`` gather from
    both copies' queries."""
    import jax
    import jax.numpy as jnp

    q, k, v, g = _operands(1, 2, 2, 1, 512, 128)
    scale = 128 ** -0.5
    plan = fa.Plan(bq, bk, 64 << 20)

    def run(kernels):
        f = lambda *a: ra.diffusion_attention(  # noqa: E731
            *a, scale, block, 128, kernels, kernels is not None)
        out = f(q, k, v)
        grads = jax.grad(lambda *a: jnp.sum(
            (f(*a) * g).astype(jnp.float32)), (0, 1, 2))(q, k, v)
        return out, grads

    out, grads = run(plan)
    want, wants = run(None)
    assert np.max(np.abs(_f32(out) - _f32(want))) < 0.02
    for name, a, b in zip("qkv", grads, wants):
        err = np.max(np.abs(_f32(a) - _f32(b)))
        assert err < 0.01 * max(np.max(np.abs(_f32(b))), 1.0), (name, err)
    # the strict walk alone: rows of the first block come back with a
    # log-sum-exp no real score reaches, so the join weighs them at 0
    _, lse = fa.attention(q[:1], k[1:], v[1:], plan, scale, True, 0, True,
                          diffusion=(block, True))
    assert np.all(np.asarray(lse)[:, :, :block] < -1e37)
    assert np.all(np.abs(np.asarray(lse)[:, :, block:]) < 1e3)


@pytest.mark.parametrize("length,bq,bk,block", [
    (8192, 256, 512, 4), (2048, 128, 512, 4), (1024, 512, 128, 128),
    (1024, 128, 128, 1)])
def test_the_visit_list_is_the_tiles_the_mask_leaves_something_in(
        length, bq, bk, block):
    """Brute force over (query tile, key tile): the clean copy's walk visits
    exactly the tiles in which some key's block is at or before some
    query's; the noised copy's walk over the clean keys the tiles with a
    key's block BEFORE a query's, which is the same list wherever a block
    is narrower than a key tile (the cell's 4 against 512)."""
    first, end = fa.visits(length, bq, bk, True)
    assert not first.any()
    # tile (i, j) holds a kept pair iff its first key's block is at or
    # before (strict: before) its last query's block
    last_q = ((np.arange(0, length, bq) + bq - 1) // block)[:, None]
    first_k = (np.arange(0, length, bk) // block)[None, :]
    visited = np.arange(length // bk)[None, :] < end[:, None]
    assert np.array_equal(first_k <= last_q, visited)
    extra = visited & ~(first_k < last_q)
    # the noised copy's walk: the same list; a visited tile is empty only
    # where a whole key tile lies inside the query tile's last block
    assert np.array_equal(extra, visited & (first_k == last_q))
    assert extra.any() == (block >= bk)
    scored = 2 * fa.scored_pairs(length, bq, bk, True) + length * block
    kept_pairs = ra.diffusion_kept_pairs(length, block)
    assert kept_pairs <= scored
    if length == 8192:      # the cell's layer: under the issue's 1.35
        assert scored / kept_pairs < 1.1


def test_off_the_kernels_trace_to_what_they_were():
    """``diffusion=None`` adds nothing to a kernel's trace: the mask is a
    Python branch, and the static argument is left out of the store's key."""
    import jax
    import jax.numpy as jnp

    q, k, v, _ = _operands(2, 1, 2, 1, 256, 128)
    first, end = map(jnp.asarray, fa.visits(256, 128, 128, True))
    static = dict(scale=0.1, causal=True, window=0, bq=128, bk=128,
                  vmem_limit=64 << 20, interpret=True)
    off = jax.make_jaxpr(lambda *a: fa._fwd(*a, first, end, **static))(
        q, k, v)
    same = jax.make_jaxpr(lambda *a: fa._fwd(
        *a, first, end, diffusion=None, **static))(q, k, v)
    on = jax.make_jaxpr(lambda *a: fa._fwd(
        *a, first, end, diffusion=(4, False), **static))(q, k, v)
    assert str(off) == str(same) != str(on)
    assert "diffusion" not in fa._static(fa.Plan(128, 128, 1), 0.1, True, 0,
                                         False)
    assert fa._static(fa.Plan(128, 128, 1), 0.1, True, 0, False,
                      (4, True))["diffusion"] == (4, True)
