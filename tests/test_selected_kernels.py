"""``RingAttention`` under a selection in the fused kernels
(``ops/flash_attention.py``: ``attention_select``, ``attention_fwd`` /
``attention_bwd`` over kept tiles, ``attention_index_bwd``) in Pallas's
interpreter on the CPU, at small shapes: against ``selected_attention``'s
``jax.numpy`` blocks and against ``lax.top_k``'s sets and a whole score
matrix; rows whose scores at the threshold are equal; rows with fewer than
``top_k`` earlier keys; ``top_k >= T``; the rule; the operator's counters with
a v5e described. (The kernels compiled for a described v5e are in
``test_grouped_matmul.py``, beside the other compile tests.)
"""

import functools
import importlib

import numpy as np
import pytest

from mxnet_tpu.ops import flash_attention as fa
from mxnet_tpu.ops import pallas_support as ps

ra = importlib.import_module("mxnet_tpu.parallel.ring_attention")

V5E_VMEM = 128 << 20
H, KV, D, DI, TOP_K = 8, 2, 128, 64, 64
TENSORS = ["output", "lse", "tau", "dq", "dk", "dv", "d_iq", "d_ik", "d_iw"]
# name: (T, index heads, positions a query block, keys a block, top_k, the
# indexer's coefficient[, rows a batch])
CASES = {
    "t256": (256, 4, 128, 128, TOP_K, 1.0),
    "t512_wide_key_blocks": (512, 2, 128, 256, TOP_K, 0.5),
    "t256_narrow_query_blocks": (256, 4, 64, 128, TOP_K, 1.0),
    "no_index_loss": (256, 4, 128, 128, TOP_K, 0.0),
    "nothing_to_select": (256, 4, 128, 128, 256, 1.0),
    "two_rows_a_batch": (256, 2, 128, 128, TOP_K, 1.0, 2),
}


def _operands(t, j, seed=11, b=1):
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    shapes = ((b, H, t, D), (b, KV, t, D), (b, KV, t, D), (b, j, t, DI),
              (b, 1, t, DI), (b, j, t), (b, H, t, D))
    ops = [jax.random.normal(k, s) for k, s in zip(keys, shapes)]
    ops[5] = ops[5] * (j * DI) ** -0.5
    return tuple(x.astype(jnp.bfloat16) for x in ops)


def _whole_matrix(q, k, v, iq, ik, iw, scale, top_k, coef):
    """(output, coef x summed KL) over the whole (T, T) matrices in
    float32, a row keeping what reaches ``lax.top_k``'s k-th value (its
    set wherever the row's scores differ; a four-head indexer scores an
    exact 0 on a pair in 16): the equations, not the operator."""
    import jax
    import jax.numpy as jnp

    q, k, v, iq, ik, iw = (x.astype(jnp.float32)
                           for x in (q, k, v, iq, ik, iw))
    t = q.shape[2]
    group = q.shape[1] // k.shape[1]
    index = jnp.einsum("bjqd,bkd->bjqk", iq, ik[:, 0], precision="highest")
    index = jnp.sum(jax.nn.relu(index) * iw[..., None], 1)
    seen = jnp.tril(jnp.ones((t, t), bool))
    best, _ = jax.lax.top_k(jnp.where(seen, index, -jnp.inf), min(top_k, t))
    kept = seen & (index >= best[..., -1:])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, group, 1),
                   precision="highest") * scale
    p = jax.nn.softmax(jnp.where(kept[:, None], s, -jnp.inf), -1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, jnp.repeat(v, group, 1),
                     precision="highest")
    target = jax.lax.stop_gradient(jnp.mean(p, 1))
    given = jax.nn.log_softmax(jnp.where(kept, index, -jnp.inf), -1)
    kl = jnp.sum(jnp.where(kept, target * (
        jnp.log(jnp.maximum(target, 1e-30)) - jnp.where(kept, given, 0.0)),
        0.0))
    return out, coef * kl


@functools.lru_cache(maxsize=None)
def _three_ways(case):
    """{tensor: (the kernels', the jax.numpy blocks', the equations')} as
    float32 arrays."""
    import jax
    import jax.numpy as jnp

    t, j, bq, bk, top_k, coef = CASES[case][:6]
    *ops, g = _operands(t, j, b=(CASES[case] + (1,))[6])
    scale = D ** -0.5
    plan = fa.Plan(bq, bk, 32 << 20)

    def kernels(*a):
        return ra._selected_kernels_fwd(*a, scale, top_k, coef, plan, True)

    def blocks(*a):
        return ra._selected_fwd(*a, scale, 32, top_k, coef, 128)

    def grads(forward, backward):
        out, res = forward(*ops)
        return (out, res[7], res[8]) + tuple(backward(res, g))

    got = [
        jax.jit(lambda: grads(kernels, functools.partial(
            ra._selected_kernels_bwd, scale, top_k, coef, plan, True)))(),
        jax.jit(lambda: grads(blocks, functools.partial(
            ra._selected_bwd, scale, 32, top_k, coef, 128)))()]

    def equations(*a):
        out, kl = _whole_matrix(*a, scale, top_k, coef)
        return jnp.sum(out * g.astype(jnp.float32)) + kl, out

    (_, out), want = jax.jit(jax.value_and_grad(
        equations, argnums=range(6), has_aux=True))(*ops)
    got.append((out, None, None) + tuple(want))
    return {name: tuple(None if x[n] is None else np.asarray(
        x[n], np.float32) for x in got) for n, name in enumerate(TENSORS)}


def _rel(a, b):
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-30))


@pytest.mark.parametrize("tensor", TENSORS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_match_the_blocks_and_the_equations(case, tensor):
    """Output, the rows' log-sum-exp and thresholds and all six gradients:
    the kernels against ``selected_attention``'s ``jax.numpy`` blocks (the
    same bfloat16 operands, so the same sets: 1e-2 is the casts of ``p``,
    ``ds`` and the pulled-back index gradient to bfloat16 in another order)
    and against ``lax.top_k``'s sets over whole float32 matrices. Without
    the coefficient the index operands get nothing."""
    kernels, blocks, equations = _three_ways(case)[tensor]
    top_k, coef = CASES[case][4:6]
    if tensor.startswith("d_i") and not coef:
        assert not kernels.any() and not blocks.any()
        return
    if tensor == "tau":
        # a row with no more than top_k earlier keys keeps them all
        t = kernels.shape[-1]
        assert np.isneginf(kernels[:, :top_k]).all()
        assert np.isfinite(kernels[:, top_k:]).all()
        # (the blocks give the row of exactly top_k keys its least score)
        assert np.allclose(kernels[:, top_k:], blocks[:, top_k:], rtol=1e-6)
        return
    assert _rel(kernels, blocks) < (1e-6 if tensor == "lse" else 1e-2)
    if equations is not None:
        assert _rel(kernels, equations) < 3e-2


def test_nothing_to_select_is_the_dense_kernels_bit_for_bit():
    """``top_k >= T``: the output and the gradients of q, k, v are the dense
    causal kernels' to the bit (no kept tiles are written or read), and the
    index operands still learn from ``P``."""
    import jax

    t, j, bq, bk, top_k, coef = CASES["nothing_to_select"]
    *ops, g = _operands(t, j)
    plan = fa.Plan(bq, bk, 32 << 20)
    scale = D ** -0.5
    out, vjp = jax.vjp(lambda *a: ra.selected_kernels(
        *a, scale, top_k, coef, plan, True), *ops)
    dense, dense_vjp = jax.vjp(lambda *a: ra.blockwise_attention(
        *a, True, scale, 128, 0, plan, True), *ops[:3])
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(dense, np.float32))
    got = vjp(g)
    for a, b in zip(got[:3], dense_vjp(g)):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
    for a in got[3:]:
        assert np.abs(np.asarray(a, np.float32)).max() > 0
    tau, _, kept = fa.select(*ops[3:6], plan, top_k, True)
    assert kept is None and np.isneginf(np.asarray(tau)).all()


def test_equal_scores_at_the_threshold_are_all_kept():
    """Index keys that repeat give equal scores: a row whose ``top_k``-th
    and next scores are equal keeps every key that reaches the threshold
    (more than ``top_k``), as ``_selection`` does; the kept tiles of
    forward (queries by keys) and of backward (keys by queries) are the
    blocks' mask, and a row with distinct scores keeps ``lax.top_k``'s
    set."""
    import jax
    import jax.numpy as jnp

    t, j, top_k = 256, 2, 16
    _, _, _, iq, ik, iw, _ = _operands(t, j, seed=3)
    ik = jnp.repeat(ik[:, :, ::8], 8, axis=2)       # eight keys alike
    plan = fa.Plan(128, 128, 32 << 20)
    tau, index_lse, kept = fa.select(iq, ik, iw, plan, top_k, True)
    index = ra.index_scores(iq, ik, iw)
    seen = ra._causal(0, t, t)
    want = ra._selection(index, ra._threshold(index, seen, top_k), seen)
    assert np.array_equal(np.asarray(kept) > 0, np.asarray(want))
    counts = np.asarray(want).sum(-1)[0]
    assert (counts[:top_k] == np.arange(1, top_k + 1)).all()
    assert (counts >= np.minimum(np.arange(t) + 1, top_k)).all()
    assert (counts > top_k).sum() > t // 8          # tied rows keep more
    assert np.allclose(np.asarray(index_lse), np.asarray(
        jax.nn.logsumexp(jnp.where(want, index, -jnp.inf), axis=-1)),
        rtol=1e-6, atol=1e-6)
    q, k = _operands(t, j)[:2]
    lse = jnp.zeros((1, H, t), jnp.float32)
    _, transposed = fa.index_grads(q, k, iq, ik, iw, lse, tau, index_lse,
                                   plan, D ** -0.5, top_k, 0.0, True)
    assert np.array_equal(np.asarray(transposed),
                          np.asarray(kept).swapaxes(1, 2))
    # distinct scores (no pair scores an exact 0): lax.top_k's set
    iq, ik, iw = (abs(x) for x in _operands(t, 4)[3:6])
    _, _, kept = fa.select(iq, ik, iw, plan, top_k, True)
    index = jnp.where(seen, ra.index_scores(iq, ik, iw), -jnp.inf)
    best, chosen = jax.lax.top_k(index, top_k + 1)
    assert not np.asarray((best[..., -1] == best[..., -2])
                          & jnp.isfinite(best[..., -1])).any()
    want = np.zeros(index.shape, bool)
    np.put_along_axis(want, np.asarray(chosen[..., :top_k]), True, axis=-1)
    assert np.array_equal(np.asarray(kept) > 0, want & np.asarray(seen))


@pytest.mark.parametrize("x", [
    0.0, 1.0, -1.0, 1.5e-30, -1.5e-30, np.inf, -np.inf, 1e30, -1e30])
def test_ordered_keys_order_as_the_floats_do(x):
    """``_key`` is an int32 whose signed order is the float's, its own
    inverse on the bits; every float is above the key of a pair no query
    sees and none is below minus infinity's. (A sum that starts from +0.0
    is never -0.0, which the keys alone would put under it.)"""
    import jax.numpy as jnp

    others = jnp.asarray([-np.inf, -2.0, -1e-30, 0.0, 1e-30, 2.0, np.inf],
                         jnp.float32)
    value = jnp.float32(x)
    key = fa._key(value)
    assert np.array_equal(np.asarray(fa._key(others) < key),
                          np.asarray(others < value))
    assert np.asarray(fa._value(key)).tobytes() == np.float32(x).tobytes()
    assert fa._LOWEST < int(key) and fa._KEY_OF_MINUS_INF <= int(key)
    assert int(fa._key(jnp.float32(-np.inf))) == fa._KEY_OF_MINUS_INF


INDEX = ("bfloat16", 16, 64)
RULE = {
    # the Keye cell's layer: 128 positions x 256 keys, where the kept tiles
    # fit beside a head's keys, values and their gradients
    "keye_cell": (("tpu", V5E_VMEM, "bfloat16", 32, 4, 16384, 128, True, 0,
                   128, 2048, INDEX), (128, 256)),
    # nothing to select: the dense kernels' own tiles
    "top_k_over_T": (("tpu", V5E_VMEM, "bfloat16", 32, 4, 16384, 128, True,
                      0, 128, 16384, INDEX), (128, 512)),
    "shorter_rows": (("tpu", V5E_VMEM, "bfloat16", 32, 4, 4096, 128, True, 0,
                      128, 2048, INDEX), (256, 512)),
    "float32_trunk": (("tpu", V5E_VMEM, "float32", 32, 4, 16384, 128, True,
                       0, 128, 2048, ("float32", 16, 64)), None),
    "float32_indexer": (("tpu", V5E_VMEM, "bfloat16", 32, 4, 16384, 128,
                         True, 0, 128, 2048, ("float32", 16, 64)), None),
    "index_width_32": (("tpu", V5E_VMEM, "bfloat16", 32, 4, 16384, 128, True,
                        0, 128, 2048, ("bfloat16", 16, 32)), None),
    "no_indexer_named": (("tpu", V5E_VMEM, "bfloat16", 32, 4, 16384, 128,
                          True, 0, 128, 2048, None), None),
    "the_cpu": (("cpu", None, "bfloat16", 32, 4, 16384, 128, True, 0, 128,
                 2048, INDEX), None),
    "a_window": (("tpu", V5E_VMEM, "bfloat16", 32, 4, 16384, 128, True, 4096,
                  128, 2048, INDEX), None),
    "a_small_vmem": (("tpu", 16 << 20, "bfloat16", 32, 4, 16384, 128, True,
                      0, 128, 2048, INDEX), None),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_rule_says_where_the_selecting_kernels_engage(case):
    args, tiles = RULE[case]
    plan = fa.plan(*args)
    assert (plan and (plan.bq, plan.bk)) == tiles
    if plan:
        assert plan.vmem_limit <= V5E_VMEM * 3 // 4
        # what the forward and backward hold is under half the VMEM, what
        # the index kernels hold within the limit
        assert fa._select_bytes(args[4], args[3] // args[4], args[5],
                                args[6], args[11], *tiles) \
            + (16 << 20) <= plan.vmem_limit


def test_kernel_plan_asks_the_rule_with_the_indexer(monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: V5E_VMEM)
    iq = jax.ShapeDtypeStruct((1, 16, 16384, 64), jnp.bfloat16)
    ask = functools.partial(ra.kernel_plan, jnp.bfloat16,
                            (1, 32, 16384, 128), 4, True, 0)
    assert ask("tpu", 128, 2048, iq) == fa.plan(*RULE["keye_cell"][0])
    assert ask("tpu", 128, 2048) is None
    assert ask("cpu", 128, 2048, iq) is None
    assert ask("tpu", 128, 2048, jax.ShapeDtypeStruct(
        (1, 16, 16384, 32), jnp.bfloat16)) is None
    assert ra.kernel_plan(jnp.float32, (1, 32, 16384, 128), 4, True, 0,
                          "tpu", 128, 2048, iq) is None


@pytest.mark.parametrize("platform,dtype,kernel_layers", [
    ("tpu", "bfloat16", 1), ("tpu", "float32", 0), ("cpu", "bfloat16", 0)])
def test_counter_rule_under_a_selection(monkeypatch, platform, dtype,
                                        kernel_layers):
    """One launch of the cell's node: ``executor.attention_kernel_layers``
    follows the rule, the scored pairs follow the kernels' visit list at
    the plan's tiles, the selected and index pairs stay closed forms."""
    import jax

    from mxnet_tpu.ops import registry

    monkeypatch.setattr(ps, "attached_vmem_bytes",
                        lambda: V5E_VMEM if platform == "tpu" else None)
    t, top_k = 16384, 2048
    ins = [jax.ShapeDtypeStruct(s, dtype) for s in (
        (1, 32, t, 128), (1, 4, t, 128), (1, 4, t, 128), (1, 16, t, 64),
        (1, 1, t, 64), (1, 16, t))]
    got = registry.get("RingAttention").launch_counts(
        ins, None, dict(causal=True, window=0, select_top_k=top_k,
                        index_loss_coef=1.0), platform)
    assert got["executor.attention_kernel_layers"] == kernel_layers
    assert got["executor.attention_selected_layers"] == 1
    assert got["executor.attention_selected_pairs"] == 32 * (
        top_k * (top_k + 1) // 2 + (t - top_k) * top_k)
    assert got["executor.attention_index_pairs"] == 16 * t * (t + 1) // 2
    assert got["executor.attention_scored_pairs"] == 32 * (
        fa.scored_pairs(t, 128, 256, True) if kernel_layers
        else ra.selected_scored_pairs(t, 32, top_k))
    if kernel_layers:
        # what the cell's metric reads a step: four layers
        assert 4 * got["executor.attention_scored_pairs"] == 17_448_304_640


@pytest.mark.parametrize("coef", [0.0, 1.0])
def test_the_operator_takes_the_kernels_where_the_rule_says(monkeypatch,
                                                            coef):
    """``RingAttention(select_top_k=...)`` through an executor whose rule is
    steered to the kernels (interpreted): output and gradients are
    ``selected_kernels``' own, and the index operands' gradients are zeros
    without the coefficient."""
    import jax.numpy as jnp

    import mxnet_tpu as mx

    t, j = 256, 4
    *ops, g = _operands(t, j)
    plan = fa.Plan(128, 128, 32 << 20)
    direct = ra.selected_kernels
    asked = []
    monkeypatch.setattr(ra, "kernel_plan",
                        lambda *a, **kw: asked.append(a) or plan)
    monkeypatch.setattr(ra, "selected_kernels",
                        lambda *a: direct(*a, True))
    names = ["q", "k", "v", "iq", "ik", "iw"]
    sym = mx.sym.RingAttention(*map(mx.sym.Variable, names), causal=True,
                               select_top_k=TOP_K, index_loss_coef=coef)
    exe = sym.simple_bind(mx.cpu(), type_dict={n: "bfloat16" for n in names},
                          **{n: x.shape for n, x in zip(names, ops)})
    for n, x in zip(names, ops):
        exe.arg_dict[n][:] = mx.nd.array(np.asarray(x, np.float32)).astype(
            "bfloat16")
    out = exe.forward(is_train=True)[0].asnumpy().astype(np.float32)
    exe.backward([mx.nd.array(np.asarray(g, np.float32)).astype("bfloat16")])
    want = _three_ways("t256" if coef else "no_index_loss")
    assert asked and asked[0][7] == TOP_K
    assert _rel(out, want["output"][0]) < 1e-6
    for n, tensor in zip(names, TENSORS[3:]):
        got = exe.grad_dict[n].asnumpy().astype(np.float32)
        if n.startswith("i") and not coef:
            assert not got.any()
        else:
            assert _rel(got, want[tensor][0]) < 1e-6
