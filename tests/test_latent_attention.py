"""``RingAttention`` with values narrower than keys (a latent-attention
head: queries and keys of 192 = 128 + 64 rotated, values of 128) on each of
its paths: the ``jax.numpy`` blocks, the Pallas kernels in the interpreter,
the op, the sequence-parallel ring; the rule that says where the kernels
engage, which gives the accepted cells' layers the tiles they had; and
``RotaryEmbedding(interleaved=True)``. (The kernels compiled for a described
v5e are in ``test_grouped_matmul.py``; the model that uses them in
``test_kanana2.py``.)
"""

import importlib

import numpy as np
import pytest
from model_cases import bind_op, rel

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError

ra = importlib.import_module("mxnet_tpu.parallel.ring_attention")


def _dense_attention(q, k, v, scale, causal=True):
    """The whole masked score matrix over a repeated copy of k and v."""
    import jax
    import jax.numpy as jnp

    group, t = q.shape[1] // k.shape[1], q.shape[2]
    k, v = (jnp.repeat(x.astype(jnp.float32), group, 1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k,
                   precision="highest") * scale
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v,
                      precision="highest")


def _operands(heads, kv_heads, t, dk, dv, dtype, seed=3):
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(key, (1, h, t, d)).astype(dtype)
                 for key, h, d in zip(keys, (heads, kv_heads, kv_heads,
                                             heads), (dk, dk, dv, dv)))


def _values_and_gradients(f, q, k, v, g):
    import jax

    out, vjp = jax.vjp(f, q, k, v)
    return [np.asarray(a, np.float32)
            for a in (out,) + vjp(g.astype(out.dtype))]


PATHS = {
    # path: (heads, key/value heads, T, dtype, kernels' tiles, limit)
    "blocks_float32": (4, 4, 64, "float32", None, 1e-5),
    "blocks_grouped_float32": (4, 2, 64, "float32", None, 1e-5),
    "blocks_bfloat16": (4, 4, 64, "bfloat16", None, 2e-2),
    "kernels_interpreted": (4, 4, 256, "bfloat16", (128, 128), 2e-2),
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_attention_at_192_over_128_matches_a_dense_softmax(path):
    """q and k of 192 = 128 + 64, v of 128: the output is 128 wide and it
    and the three gradients are a dense softmax's, in ``jax.numpy`` blocks
    and through the Pallas kernels (in the interpreter)."""
    from mxnet_tpu.ops import flash_attention as fa

    heads, kv, t, dtype, tiles, limit = PATHS[path]
    q, k, v, g = _operands(heads, kv, t, 192, 128, dtype)
    scale = 192 ** -0.5
    plan = fa.Plan(*tiles, 32 << 20) if tiles else None

    def taken(q, k, v):
        return ra.blockwise_attention(q, k, v, True, scale, 32, 0, plan,
                                      True)

    got = _values_and_gradients(taken, q, k, v, g)
    want = _values_and_gradients(
        lambda q, k, v: _dense_attention(q, k, v, scale), q, k, v, g)
    assert got[0].shape == (1, heads, t, 128)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        assert rel(a, b) < limit
    assert got[1].shape == q.shape and got[3].shape == v.shape


@pytest.mark.slow
@pytest.mark.parametrize("tiles", [(256, 128), (128, 256)])
@pytest.mark.parametrize("heads", [(4, 4), (4, 2)])
def test_kernels_at_192_over_128_at_other_tiles_and_groups(tiles, heads):
    from mxnet_tpu.ops import flash_attention as fa

    q, k, v, g = _operands(*heads, 512, 192, 128, "bfloat16")
    scale = 192 ** -0.5
    plan = fa.Plan(*tiles, 32 << 20)
    got = _values_and_gradients(
        lambda q, k, v: ra.blockwise_attention(q, k, v, True, scale, 128, 0,
                                               plan, True), q, k, v, g)
    want = _values_and_gradients(
        lambda q, k, v: _dense_attention(q, k, v, scale), q, k, v, g)
    for a, b in zip(got, want):
        assert rel(a, b) < 2e-2


def test_the_op_takes_the_values_width_and_counts_a_latent_layer():
    """``RingAttention`` as a symbol: output shape from the value, the
    default scale from the key's 24 (not the value's 16), both directions
    against the dense softmax; queries and keys of different widths are
    refused by name."""
    import jax.numpy as jnp

    rs = np.random.RandomState(4)
    q, k = (rs.randn(2, 4, 32, 24).astype(np.float32) for _ in range(2))
    v, g = (rs.randn(2, 4, 32, 16).astype(np.float32) for _ in range(2))
    names = ["q", "k", "v"]
    sym = mx.sym.RingAttention(*map(mx.sym.Variable, names), causal=True)
    assert sym.infer_shape(q=q.shape, k=k.shape, v=v.shape)[1] == [
        (2, 4, 32, 16)]
    exe = bind_op(sym, names, [q, k, v])
    want = _values_and_gradients(
        lambda q, k, v: _dense_attention(q, k, v, 24 ** -0.5),
        *map(jnp.asarray, (q, k, v, g)))
    assert rel(exe.forward(is_train=True)[0].asnumpy(), want[0]) < 1e-5
    exe.backward(out_grads=[mx.nd.array(g)])
    for n, b in zip(names, want[1:]):
        assert rel(exe.grad_dict[n].asnumpy(), b) < 1e-5
    with pytest.raises(MXNetError, match="queries of 24 over keys of 16"):
        ra.blockwise_attention(jnp.asarray(q), jnp.asarray(v),
                               jnp.asarray(v), True, 0.2)


@pytest.mark.parametrize("causal", [False, True])
def test_the_ring_computes_the_same_function_at_unequal_widths(causal):
    """The sequence-parallel ring on the 8-device mesh with keys of 24 over
    values of 16: the output is 16 wide and the dense softmax's."""
    import jax.numpy as jnp

    rs = np.random.RandomState(5)
    q, k = (jnp.asarray(rs.randn(2, 3, 64, 24).astype(np.float32))
            for _ in range(2))
    v = jnp.asarray(rs.randn(2, 3, 64, 16).astype(np.float32))
    mesh = mx.parallel.make_mesh({"sp": 8})
    got = ra.ring_attention(q, k, v, mesh=mesh, causal=causal)
    assert got.shape == (2, 3, 64, 16)
    want = _dense_attention(q, k, v, 24 ** -0.5, causal)
    assert rel(got, want) < 1e-5
    assert rel(ra._full_attention(q, k, v, causal, 24 ** -0.5), want) < 1e-5


V5E_VMEM = 128 << 20


@pytest.mark.parametrize("case,args,tiles", [
    ("kanana2_at_T_8192", (32, 32, 8192, 192, True, 0, 128), (512, 512)),
    ("kanana2_at_T_4096", (32, 32, 4096, 192, True, 0, 128), (512, 512)),
    # the tiles the accepted cells' layers run at, as they were
    ("trinity_window", (32, 4, 4096, 128, True, 2048, 128), (256, 256)),
    ("trinity_full", (32, 4, 4096, 128, True, 0, None), (256, 512)),
    ("olmoe", (16, 16, 4096, 128, True, 0, 128), (512, 512)),
    ("qwen3_next_at_T_8192", (16, 2, 8192, 256, True, 0, 256), (128, 512)),
    ("qwen3_next_at_T_4096", (16, 2, 4096, 256, True, 0, None), (256, 512)),
    # widths the kernels do not take
    ("keys_of_160", (32, 32, 4096, 160, True, 0, 128), None),
    ("values_of_64", (32, 32, 4096, 192, True, 0, 64), None),
    ("keys_of_32_over_values_of_128", (32, 32, 4096, 32, True, 0, 128), None),
    # since PR 65 a half tile of lanes alone is a key width (a differential
    # pair's queries and keys under its two value heads side by side)
    ("keys_of_64_over_values_of_128", (32, 32, 4096, 64, True, 0, 128),
     (512, 512)),
])
def test_rule_gives_a_plan_at_192_over_128_and_the_old_tiles(case, args,
                                                             tiles):
    from mxnet_tpu.ops import flash_attention as fa

    plan = fa.plan("tpu", V5E_VMEM, "bfloat16", *args)
    if tiles is None:
        assert plan is None
        return
    assert (plan.bq, plan.bk) == tiles
    assert plan.vmem_limit <= V5E_VMEM * 3 // 4
    heads, kv, t, d, causal, window, dv = args
    if dv in (None, d):
        # one width: the rule is the one-width rule, to the byte
        assert plan == fa.plan("tpu", V5E_VMEM, "bfloat16", heads, kv, t, d,
                               causal, window)
    # and the ring_attention rule hands the value's width on
    assert ra.kernel_plan("bfloat16", (1, heads, t, d), kv, causal, window,
                          "cpu", dv) is None


# --- the rotary pairing ---------------------------------------------------------

def test_interleaved_rotary_is_the_pairwise_formula():
    """``RotaryEmbedding(interleaved=True)``: dims (2i, 2i + 1) of position
    t turn by t * base^(-2i/D), in place; rotate-half pairs (i, i + D/2);
    the two differ, and agree after sorting the evens before the odds (the
    family's own form)."""
    rs = np.random.RandomState(6)
    x = rs.randn(2, 3, 16, 8).astype(np.float32)
    t, d = x.shape[-2:]
    angle = np.arange(t)[:, None] * 1e6 ** (-np.arange(0, d, 2) / d)[None, :]
    want = np.empty_like(x, dtype=np.float64)
    want[..., 0::2] = x[..., 0::2] * np.cos(angle) - x[..., 1::2] * np.sin(
        angle)
    want[..., 1::2] = x[..., 1::2] * np.cos(angle) + x[..., 0::2] * np.sin(
        angle)

    def turned(data, **kw):
        sym = mx.sym.RotaryEmbedding(mx.sym.Variable("x"), base=1e6, **kw)
        return bind_op(sym, ["x"], [data]).forward()[0].asnumpy()

    got = turned(x, interleaved=True)
    assert rel(got, want) < 1e-6
    assert rel(turned(x), want) > 0.1
    sort = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
    assert rel(turned(x[..., sort]), want[..., sort]) < 1e-6
    # a partial head: only the first 4 of the 8 turn, in pairs
    part = turned(x, interleaved=True, rotary_dim=4)
    assert rel(part[..., :4], turned(x[..., :4].copy(), interleaved=True)) \
        < 1e-6
    assert np.array_equal(part[..., 4:], x[..., 4:])
