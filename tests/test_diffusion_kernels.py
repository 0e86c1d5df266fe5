"""The fused attention kernels under the block-diffusion mask (``diffusion=``
of ``ops/flash_attention.py``: the causal walk with its diagonal cut by
blocks of positions, queries of one copy of a row over keys and values of the
other), in Pallas's interpreter on the CPU: bfloat16, one small shape,
forward and backward, against the ``jax.numpy`` blocks of
``ring_attention.diffusion_attention`` (which ``tests/test_sdar.py`` holds to
a dense softmax under the explicit mask); their visit list against a
brute-force count of the tiles the mask leaves something in; that the
kernel path traces none of the ``jax.numpy`` squares; and that the kernels
trace to what they were when the mode is off. The compile for a
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


def _both_paths(q, k, v, g, scale, block, plan):
    """((out, (dq, dk, dv)) through the kernels in the interpreter, the
    same through the ``jax.numpy`` blocks)."""
    import jax
    import jax.numpy as jnp

    def run(kernels):
        f = lambda *a: ra.diffusion_attention(  # noqa: E731
            *a, scale, block, 128, kernels, kernels is not None)
        out = f(q, k, v)
        grads = jax.grad(lambda *a: jnp.sum(
            (f(*a) * g).astype(jnp.float32)), (0, 1, 2))(q, k, v)
        return out, grads

    return run(plan), run(None)


# the two tilings; a query block of block * 64 positions; and rows of ONE
# query block, none of whose noised queries' first block sees a clean key
@pytest.mark.parametrize("length,bq,bk,block", [
    (512, 128, 256, 4), (512, 256, 128, 16), (512, 256, 128, 4),
    (256, 256, 128, 128)])
def test_kernels_match_the_blocks_forward_and_backward(length, bq, bk, block):
    """Both walks (the clean copy on itself, the noised copy on the clean
    one and on its own blocks, one more tile of the same kernels) against
    the ``jax.numpy`` blocks: the output and ``dq``, ``dk``, ``dv`` of BOTH
    copies, each copy held to its own limit (the noised copy's ``dk`` and
    ``dv`` come from the kernel's own tile; the clean copy's gather from
    both copies' queries)."""
    q, k, v, g = _operands(1, 2, 2, 1, length, 128)
    scale = 128 ** -0.5
    plan = fa.Plan(bq, bk, 64 << 20)
    (out, grads), (want, wants) = _both_paths(q, k, v, g, scale, block, plan)
    for copy in (slice(0, 1), slice(1, 2)):     # noised, clean
        assert np.max(np.abs(_f32(out[copy]) - _f32(want[copy]))) < 0.02
        for name, a, b in zip("qkv", grads, wants):
            a, b = _f32(a[copy]), _f32(b[copy])
            err = np.max(np.abs(a - b))
            assert np.max(np.abs(b)) > 0.1, (name, copy)   # something to hold
            assert err < 0.01 * max(np.max(np.abs(b)), 1.0), (name, copy, err)
    # the strict walk alone: rows of the first block come back with a
    # log-sum-exp no real score reaches; with their own copy's keys beside
    # them every row has a real one, the joint one of the two parts
    alone = dict(diffusion=(block, True))
    _, lse = fa.attention(q[:1], k[1:], v[1:], plan, scale, True, 0, True,
                          **alone)
    assert np.all(np.asarray(lse)[:, :, :block] < -1e37)
    assert np.all(np.abs(np.asarray(lse)[:, :, block:]) < 1e3)
    _, joint = fa.attention(q[:1], k[1:], v[1:], plan, scale, True, 0, True,
                            own=(k[:1], v[:1]), **alone)
    assert np.all(np.abs(np.asarray(joint)) < 1e3)
    import jax

    s, _, _ = ra._own_scores(q[:1], k[:1], scale, block)
    mine = np.asarray(jax.nn.logsumexp(s, axis=-1)).reshape(joint.shape)
    assert np.allclose(np.asarray(joint)[:, :, :block], mine[:, :, :block],
                       atol=2e-2)
    assert np.allclose(np.asarray(joint)[:, :, block:], np.logaddexp(
        np.asarray(lse), mine)[:, :, block:], atol=2e-2)


def test_own_keys_ride_the_strict_walk_alone():
    q, k, v, _ = _operands(3, 1, 2, 1, 256, 128)
    plan = fa.Plan(128, 128, 64 << 20)
    for diffusion in (None, (4, False)):
        with pytest.raises(ValueError, match="strict"):
            fa.attention(q, k, v, plan, 0.1, True, 0, True, own=(k, v),
                         diffusion=diffusion)


def _equations(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (a ``jit``, a ``custom_vjp``,
    a ``pallas_call``'s body) included."""
    import jax

    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("on_kernels", [True, False])
def test_the_kernel_path_traces_no_little_squares(on_kernels):
    """Forward and backward under the kernels hold no ``attention.own_block``
    scope and no ``logaddexp``: the own block is in the strict walk's
    ``pallas_call``s, which take two operands and (backward) two results
    more than the clean walk's. The ``jax.numpy`` blocks hold both."""
    import jax
    import jax.numpy as jnp

    q, k, v, g = _operands(4, 2, 2, 1, 256, 128)
    plan = fa.Plan(128, 128, 64 << 20) if on_kernels else None
    f = lambda *a: jnp.sum((ra.diffusion_attention(  # noqa: E731
        *a, 0.1, 4, 128, plan, on_kernels) * g).astype(jnp.float32))
    traced = jax.make_jaxpr(jax.value_and_grad(f, (0, 1, 2)))(q, k, v)
    eqns = list(_equations(traced.jaxpr))
    squares = any("attention.own_block" in str(e.source_info.name_stack)
                  for e in eqns)
    assert squares == (not on_kernels)
    assert ("logaddexp" in str(traced)) == (not on_kernels)
    # clean walk, strict walk: first, end, q, k, v (+ own k, v) forward;
    # + d_out, lse, delta backward, whose results are dq, dk, dv (+ the own
    # dk, dv)
    calls = sorted((e.params["name"], len(e.invars), len(e.outvars))
                   for e in eqns if e.primitive.name == "pallas_call")
    assert calls == ([
        ("attention_bwd", 8, 3), ("attention_bwd", 10, 5),
        ("attention_fwd", 5, 2), ("attention_fwd", 7, 2)]
        if on_kernels else [])


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
    # and the noised copy's own blocks: a tile of bq keys a query block
    scored = 2 * fa.scored_pairs(length, bq, bk, True) + length * bq
    kept_pairs = ra.diffusion_kept_pairs(length, block)
    assert kept_pairs <= scored
    if length == 8192:      # the cell's layer: 1.093
        assert scored / kept_pairs < 1.1


def _off_the_mode(case):
    """The jaxpr of ``jax.grad`` through the kernels (``pallas_call`` bodies
    included) with ``diffusion`` None, as text."""
    import jax
    import jax.numpy as jnp

    plan = fa.Plan(128, 128, 64 << 20)
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)  # noqa: E731
    total = lambda out: jnp.sum(out.astype(jnp.float32))  # noqa: E731
    if case == "selection":
        operands = (shape(1, 2, 256, 128), shape(1, 1, 256, 128),
                    shape(1, 1, 256, 128), shape(1, 2, 256, 64),
                    shape(1, 1, 256, 64), shape(1, 2, 256))
        f = lambda *a: total(ra.selected_kernels(  # noqa: E731
            *a, 0.1, 64, 0.5, plan, True))
    else:
        window, d, dv = {"causal": (0, 128, 128), "window": (128, 128, 128),
                         "latent": (0, 192, 128)}[case]
        operands = (shape(1, 2, 256, d), shape(1, 1, 256, d),
                    shape(1, 1, 256, dv))
        f = lambda *a: total(ra.blockwise_attention(  # noqa: E731
            *a, True, 0.1, 128, window, plan, True))
    return str(jax.make_jaxpr(jax.grad(f, tuple(range(len(operands)))))(
        *operands))


# sha256 of ``_off_the_mode(case)`` at the commit before the own tile (PR 60's
# tree, jax 0.9.0): dense causal, a window, the latent widths 192 / 128, and
# ``kept`` under a selection (four kernels)
_BEFORE_THE_OWN_TILE = {
    "causal":
        "e04b83631e0e3fab23f41632da670e3b9f713254644266f22c7c6a14eb7f040c",
    "window":
        "58fd428cf83631f5044c192c4b7b497a9898d507fb5c5325299e984fd33b2b17",
    "latent":
        "ddf0bd0ec041639eac3fd8b7bdc28cfde642dc511fe0171a0070d4f047096be3",
    "selection":
        "c84dabb12367956d96c3631008b5ac9bbb4097d628fd612cc306a011418cf929",
}


@pytest.mark.parametrize("case", sorted(_BEFORE_THE_OWN_TILE))
def test_off_the_mode_the_gradient_traces_to_what_it_was(case):
    """With ``diffusion`` None the optional operands are absent and the
    kernels' jaxprs, forward and backward, are the parent's to the digest:
    what the other eight kernel cells (the Keye-VL-2.0 cell's ``kept``
    among them) run did not move."""
    import hashlib

    import jax

    if jax.__version__ != "0.9.0":
        pytest.skip("the digests are of jax 0.9.0's printed jaxprs")
    assert hashlib.sha256(_off_the_mode(case).encode()).hexdigest() \
        == _BEFORE_THE_OWN_TILE[case]


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
    strict = jax.make_jaxpr(lambda *a: fa._fwd(
        *a, first, end, diffusion=(4, True), **static))(q, k, v)
    own = jax.make_jaxpr(lambda *a: fa._fwd(
        *a, first, end, None, (a[1], a[2]), diffusion=(4, True), **static))(
            q, k, v)
    assert str(strict) != str(own)
    assert fa._optional(None, None) == () and fa._optional(k, None) == (k,)
    assert fa._optional(None, [k, v]) == (None, (k, v))
    assert "diffusion" not in fa._static(fa.Plan(128, 128, 1), 0.1, True, 0,
                                         False)
    assert fa._static(fa.Plan(128, 128, 1), 0.1, True, 0, False,
                      (4, True))["diffusion"] == (4, True)
