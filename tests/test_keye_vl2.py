"""Keye-VL-2.0's text decoder at a tiny size on the CPU (hidden 64, 8 query
heads over 2 key/value heads of 16, an indexer of 8 heads of 8 that keeps 8
keys a query, 4 of 16 experts held from id 4, top-2, T 32 and 48, vocabulary
64, 2 layers (1 in the tests of the tolerances, whose cost is compiles),
float32) against the plain reference
``benchmark/reference/keye-vl-2.0-30b-a3b.py``, and the selection inside
``RingAttention`` against ``lax.top_k`` and a gather.

Tolerances, and why: program and reference both compute in float32 and
differ by the order of their sums (a mask of a threshold against a gather of
``lax.top_k``'s indices, experts' rows sorted), so a tensor agrees to
``F32_TENSOR_TOLERANCE`` and the first step's loss and gradient norm to
``F32_TOLERANCES``. The program keeps every score that reaches a row's
threshold, so it holds ``lax.top_k``'s set wherever the row's k-th and
(k+1)-th scores differ and one key more or several where they are EQUAL. An
indexer of J heads scores an exact 0 on a pair in 2^J: at 2 heads tied rows
are common, at the 8 of this preset (ISSUE 51 wrote 2) and on the seeded
rows used here there is none, and the test of the chosen sets counts the
tied rows on both kinds of scores. ``TOLERANCES`` are what the bfloat16 trunk is held to
on the chip; each mechanism of the sparse attention left out moves the
gradient norm by more than they allow.
"""

import functools
import os
import sys

import model_cases as mc
import numpy as np
import pytest
from model_cases import bind_op, misses, rel

import mxnet_tpu as mx
import mxnet_tpu.parallel.ring_attention  # noqa: F401 (the module, below)
from mxnet_tpu.base import MXNetError

ra = sys.modules["mxnet_tpu.parallel.ring_attention"]
NAME = "keye-vl-2.0-30b-a3b"
TINY = dict(vocab_size=64, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=8, num_key_value_heads=2, head_dim=16,
            num_experts=4, num_experts_published=16, expert_offset=4,
            moe_intermediate_size=32, num_experts_per_tok=2,
            norm_topk_prob=True, router_aux_loss_coef=0.001,
            sa_config=dict(indexer_num_heads=8, indexer_head_dim=8, topk=8),
            index_loss_coef=1.0, index_norm_eps=1e-6, rms_norm_eps=1e-6,
            rope_theta=1e7)
ONE = dict(TINY, num_hidden_layers=1)
B, T = 2, 32
INDEX_LEAVES = ("index_q_weight", "index_k_weight", "index_k_norm_gamma",
                "index_k_norm_beta", "index_w_weight")


@pytest.fixture(scope="module")
def ref():
    return mc.load("reference", NAME)


def tiny_cfg(**over):
    cfg = dict(TINY, **over)
    if "topk" in over:
        cfg["sa_config"] = dict(TINY["sa_config"], topk=cfg.pop("topk"))
    return cfg


def tiny_sym_gen(dtype="float32", **over):
    return mc.load("configs", NAME).sym_gen(
        dict(tiny_cfg(**over), compute_dtype=dtype), mx)[0]


seeded_params = mc.seeded_params
seeded_tokens = functools.partial(mc.seeded_tokens, batch=B, seq_len=T,
                                  vocab=TINY["vocab_size"])


# --- LayerNorm ---------------------------------------------------------------

@pytest.mark.parametrize("axis", [-1, 1])
def test_layer_norm_matches_numpy_and_its_gradient(axis):
    rs = np.random.RandomState(3)
    x = rs.randn(3, 5, 7).astype(np.float32)
    width = x.shape[axis]
    gamma = (1 + 0.1 * rs.randn(width)).astype(np.float32)
    beta = (0.1 * rs.randn(width)).astype(np.float32)
    sym = mx.sym.LayerNorm(*map(mx.sym.Variable, "xgb"), axis=axis, eps=1e-6)
    assert sym.infer_shape(x=x.shape)[0] == [x.shape, (width,), (width,)]
    exe = bind_op(sym, "xgb", [x, gamma, beta])
    out = exe.forward(is_train=True)[0].asnumpy()
    along = [1, 1, 1]
    along[axis] = width
    mean = x.mean(axis, keepdims=True)
    want = (x - mean) / np.sqrt(x.var(axis, keepdims=True) + 1e-6) \
        * gamma.reshape(along) + beta.reshape(along)
    assert rel(out, want) < 1e-5
    mx.test_utils.check_numeric_gradient(sym, [x, gamma, beta],
                                         numeric_eps=1e-2, rtol=2e-2, atol=1e-3)


# --- the selection -----------------------------------------------------------

def _operands(seed, t, batch=2, heads=4, kv=2, d=16, j=8, di=8):
    rs = np.random.RandomState(seed)

    def draw(*shape):
        return rs.randn(*shape).astype(np.float32)

    return (draw(batch, heads, t, d), draw(batch, kv, t, d),
            draw(batch, kv, t, d), draw(batch, j, t, di),
            draw(batch, 1, t, di), draw(batch, j, t))


def _gathered(q, k, v, iq, ik, iw, scale, top_k, coef):
    """(output, coef x summed KL) from ``lax.top_k``'s indices and a gather
    of the chosen keys and values: the equations, not the operator."""
    import jax
    import jax.numpy as jnp

    t = q.shape[2]
    group = q.shape[1] // k.shape[1]
    index = jnp.einsum("bjqd,bkd->bjqk", iq, ik[:, 0], precision="highest")
    index = jnp.sum(jax.nn.relu(index) * iw[..., None], 1)
    seen = jnp.tril(jnp.ones((t, t), bool))
    scores, chosen = jax.lax.top_k(jnp.where(seen, index, -jnp.inf),
                                   min(top_k, t))
    valid = jnp.isfinite(scores)

    def gather(x):
        return jax.vmap(lambda rows, at: rows[:, at])(
            jnp.repeat(x, group, 1), chosen)

    s = jnp.einsum("bhqd,bhqkd->bhqk", q, gather(k),
                   precision="highest") * scale
    p = jax.nn.softmax(jnp.where(valid[:, None], s, -jnp.inf), -1)
    out = jnp.einsum("bhqk,bhqkd->bhqd", p, gather(v), precision="highest")
    target = jax.lax.stop_gradient(jnp.mean(p, 1))
    given = jax.nn.log_softmax(jnp.where(valid, scores, -jnp.inf), -1)
    kl = jnp.sum(jnp.where(valid, target * (
        jnp.log(jnp.maximum(target, 1e-30)) - jnp.where(valid, given, 0.0)),
        0.0))
    return out, coef * kl


@pytest.mark.parametrize("t,block,span,coef", [(32, 8, 16, 1.0),
                                               (48, 16, 16, 0.0)])
def test_selected_attention_matches_top_k_and_a_gather(t, block, span, coef):
    """Output and all six gradients of ``selected_attention`` over several
    blocks and spans against ``lax.top_k``'s indices, a gather and autodiff
    of the KL term; with the coefficient at 0 the index operands get
    nothing."""
    import jax
    import jax.numpy as jnp

    ops = tuple(map(jnp.asarray, _operands(4, t)))
    g = jnp.asarray(np.random.RandomState(5).randn(*ops[0].shape),
                    jnp.float32)

    def program(*a):
        out = ra.selected_attention(*a, 0.25, block, 8, coef, span)
        return jnp.sum(out * g), out

    def equations(*a):
        out, kl = _gathered(*a, 0.25, 8, coef)
        return jnp.sum(out * g) + kl, out

    (_, out), got = jax.jit(jax.value_and_grad(
        program, range(6), has_aux=True))(*ops)
    (_, gathered), want = jax.jit(jax.value_and_grad(
        equations, range(6), has_aux=True))(*ops)
    assert rel(out, gathered) < 1e-5
    for n, a, b in zip(("q", "k", "v", "iq", "ik", "iw"), got, want):
        if coef or n in "qkv":
            assert rel(a, b) < 1e-5, n
        else:
            assert not np.asarray(a).any() and not np.asarray(b).any(), n


@pytest.mark.parametrize("k", [1, 8, 64, 200])
def test_the_kth_largest_by_bisection_is_lax_top_ks(k):
    """Rows with -inf (fewer than k causal keys), +inf, both zeros, equal
    values throughout: the same float as ``lax.top_k``'s k-th."""
    import jax
    import jax.numpy as jnp

    x = np.random.RandomState(12).randn(3, 7, 200).astype(np.float32)
    x[0, 0, :50] = -np.inf
    x[2, 3, :190] = -np.inf
    x[1, 2, :] = 0.25
    x[0, 1, 5] = np.inf
    x[0, 2, :100] = -0.0
    x[0, 2, 100:150] = 0.0
    want = jax.lax.top_k(jnp.asarray(x), k)[0][..., -1]
    assert np.array_equal(np.asarray(ra.kth_largest(jnp.asarray(x), k)),
                          np.asarray(want))


def _tied_rows(index, top_k):
    """Rows whose ``top_k``-th and next largest causal scores are equal."""
    import jax
    import jax.numpy as jnp

    t = index.shape[-1]
    best = jax.lax.top_k(jnp.where(jnp.tril(jnp.ones((t, t), bool)), index,
                                   -jnp.inf), top_k + 1)[0]
    return int(jnp.sum((best[..., top_k - 1] == best[..., top_k])
                       & jnp.isfinite(best[..., top_k])))


@pytest.mark.parametrize("scores", ["seeded", "indexer_of_two_heads"])
def test_the_selected_sets_are_lax_top_ks(scores):
    """The mask of a block (every earlier key that reaches the row's
    threshold) holds exactly ``lax.top_k``'s indices on seeded continuous
    scores, where no row is tied (counted: 0). On a two-head indexer's,
    where a quarter of the pairs score an exact 0 and many rows are tied, it
    holds them in every row, is them in every row that is not tied, and
    what a tied row keeps beyond them scores exactly its threshold."""
    import jax
    import jax.numpy as jnp

    t, top_k = 48, 8
    if scores == "seeded":
        index = jnp.asarray(np.random.RandomState(6).randn(2, t, t),
                            jnp.float32)
        assert _tied_rows(index, top_k) == 0
    else:
        _, _, _, iq, ik, iw = map(jnp.asarray, _operands(7, t, j=2))
        index = ra.index_scores(iq, ik, iw)
        assert _tied_rows(index, top_k) > 4
    seen = jnp.tril(jnp.ones((t, t), bool))
    tau = ra._threshold(index, seen[None], top_k)
    kept = np.asarray(ra._selection(index, tau, seen[None]))
    best, chosen = jax.lax.top_k(jnp.where(seen, index, -jnp.inf),
                                 top_k + 1)
    want = np.zeros(kept.shape, bool)
    np.put_along_axis(want, np.asarray(chosen[..., :top_k]), True, axis=-1)
    want &= np.asarray(seen)
    tied = np.asarray((best[..., top_k - 1] == best[..., top_k])
                      & jnp.isfinite(best[..., top_k]))
    assert int(tied.sum()) == _tied_rows(index, top_k)
    assert (kept[~tied] == want[~tied]).all()
    assert (kept >= want).all()
    more = kept & ~want
    assert more[tied].any(-1).all() and not more[~tied].any()
    assert (np.asarray(index)[more]
            == np.broadcast_to(np.asarray(tau)[..., None], more.shape)[more]
            ).all()
    assert (want.sum(-1) == np.minimum(np.arange(t) + 1, top_k)).all()


def _attention_sym(top_k, coef=0.0):
    names = ["q", "k", "v"] + ["iq", "ik", "iw"] * (top_k > 0)
    return mx.sym.RingAttention(*map(mx.sym.Variable, names), causal=True,
                                select_top_k=top_k,
                                index_loss_coef=coef), names


def test_nothing_to_select_is_causal_attention_bit_for_bit():
    """``select_top_k >= T``: outputs and the gradients of q, k, v are
    ``RingAttention(causal=True)``'s to the bit, and the cross-entropy
    (any cotangent of the output) gives the index operands nothing."""
    ops = _operands(8, T)
    head = np.random.RandomState(9).randn(*ops[0].shape).astype(np.float32)
    got = {}
    for top_k in (0, T, 4 * T):
        sym, names = _attention_sym(top_k)
        exe = bind_op(sym, names, ops[:len(names)])
        out = exe.forward(is_train=True)[0].asnumpy()
        exe.backward(out_grads=[mx.nd.array(head)])
        got[top_k] = [out] + [exe.grad_dict[n].asnumpy() for n in names]
    for top_k in (T, 4 * T):
        for a, b in zip(got[0], got[top_k][:4]):
            assert np.array_equal(a, b)
        for a in got[top_k][4:]:
            assert not a.any()
    # and below T the selection does change the answer
    sym, names = _attention_sym(8)
    out = bind_op(sym, names, ops).forward()[0].asnumpy()
    assert rel(out, got[0][0]) > 1e-2


def test_the_indexers_gradient_is_the_closed_form():
    """``d L_I / d I[t, s] = (softmax_S(I)[s] - P[t, s])`` a row on ``S_t``
    (x the coefficient; the sum over rows, ``SoftmaxOutput``'s scale)
    pushed through ``I``: with a ZERO cotangent of the output, what the
    index operands receive is that and nothing else, and q, k, v receive
    nothing."""
    import jax
    import jax.numpy as jnp

    coef, top_k = 0.7, 8
    ops = _operands(10, T)
    sym, names = _attention_sym(top_k, coef)
    exe = bind_op(sym, names, ops)
    exe.forward(is_train=True)
    exe.backward(out_grads=[mx.nd.zeros(ops[0].shape)])
    q, k, v, iq, ik, iw = map(jnp.asarray, ops)
    index, pull = jax.vjp(ra.index_scores, iq, ik, iw)
    seen = jnp.tril(jnp.ones((T, T), bool))[None]
    kept = ra._selection(index, ra._threshold(index, seen, top_k), seen)
    group = q.shape[1] // k.shape[1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, group, 1),
                   precision="highest") / 4.0
    p = jax.nn.softmax(jnp.where(kept[:, None], s, -jnp.inf), -1)
    given = jax.nn.softmax(jnp.where(kept, index, -jnp.inf), -1)
    want = pull(coef * (given - jnp.mean(p, 1)))
    for n, b in zip(names[3:], want):
        assert rel(exe.grad_dict[n].asnumpy(), b) < 1e-5, n
    for n in names[:3]:
        assert not exe.grad_dict[n].asnumpy().any(), n


def test_the_ring_refuses_a_selection_by_name_and_the_rule_takes_one():
    import jax.numpy as jnp

    from mxnet_tpu import parallel
    from mxnet_tpu.ops import flash_attention

    mesh = parallel.make_mesh({"sp": 2})
    q, k, v, iq, ik, iw = map(jnp.asarray, _operands(11, 16, kv=4))
    select = (iq, ik, iw, 8, 1.0)
    with pytest.raises(MXNetError, match="select_top_k=8 is not supported "
                       "on the sequence-parallel ring"):
        ra.ring_attention(q, k, v, mesh=mesh, causal=True, select=select)
    with pytest.raises(MXNetError, match="select_top_k needs causal=True"):
        ra.ring_attention(q, k, v, mesh=None, causal=False, select=select)
    with pytest.raises(MXNetError, match="index_key"):
        ra.ring_attention(q, k, v, mesh=None, causal=True,
                          select=(iq, ik[:, :, :8], iw, 8, 1.0))
    # the rule: the kernels take a selection whose indexer they are told
    # (tests/test_selected_kernels.py has its cases); the process holds no
    # TPU here, so ``kernel_plan`` answers None whatever it is asked
    args = ("tpu", 128 << 20, jnp.bfloat16, 32, 4, 16384, 128)
    assert tuple(flash_attention.plan(*args))[:2] == (128, 512)
    assert tuple(flash_attention.plan(
        *args, select_top_k=2048, index=(jnp.bfloat16, 16, 64)))[:2] == \
        (128, 256)
    assert flash_attention.plan(*args, select_top_k=2048) is None
    assert ra.kernel_plan(jnp.bfloat16, (1, 32, 16384, 128), 4, True, 0,
                          "tpu", 128, select_top_k=2048,
                          index_query=iq) is None


@pytest.mark.parametrize("batch,t,top_k", [(2, 32, 8), (1, 4096, 2048)])
def test_launch_counts_are_the_closed_forms(batch, t, top_k):
    """What one launch of a node counts under a selection: the pairs the
    softmax keeps, the pairs the indexer scores, the tiles' pairs; nothing
    of the three without one."""
    import jax

    from mxnet_tpu.ops import registry

    heads, j = 4, 2
    ins = [jax.ShapeDtypeStruct(s, np.float32) for s in (
        (batch, heads, t, 16), (batch, 2, t, 16), (batch, 2, t, 16),
        (batch, j, t, 8), (batch, 1, t, 8), (batch, j, t))]
    op = registry.get("RingAttention")
    params = dict(causal=True, window=0, select_top_k=top_k,
                  index_loss_coef=1.0)
    got = op.launch_counts(ins, None, params, "cpu")
    kept = sum(min(i + 1, top_k) for i in range(t))
    assert got["executor.attention_selected_layers"] == 1
    assert got["executor.attention_selected_pairs"] == batch * heads * kept
    assert got["executor.attention_index_pairs"] == \
        batch * j * t * (t + 1) // 2
    block = ra.select_block_q(batch, heads, t)
    assert got["executor.attention_scored_pairs"] == batch * heads * sum(
        (b - a) * b for a, b, _ in ra.select_plan(t, block))
    assert got["executor.attention_kernel_layers"] == 0     # the CPU
    dense = op.launch_counts(ins[:3], None, dict(params, select_top_k=0),
                             "cpu")
    assert not [n for n in dense if "selected" in n or "index" in n]
    # the cell's layer, from the sizes alone: blocks of 32 queries (a tile of
    # 64 MiB) in 8 spans of 2048
    assert ra.select_block_q(1, 32, 16384) == 32
    assert ra.selected_scored_pairs(16384, 32, 2048) == sum(
        2048 * 2048 * i for i in range(1, 9))


# --- the held range ------------------------------------------------------------

def _moe_inputs(experts=16, seed=5, rows=48):
    rs = np.random.RandomState(seed)
    tok = rs.randn(rows, 64).astype(np.float32)
    router = (rs.randn(experts, 64) * 0.3).astype(np.float32)
    ws = [(rs.randn(*s) * 0.3).astype(np.float32)
          for s in ((experts, 64, 32), (experts, 64, 32), (experts, 32, 64))]
    return tok, router, ws


def _moe_sym(first=0, held=0):
    names = ["d", "r", "g", "u", "o"]
    return mx.sym.MoE(*map(mx.sym.Variable, names), num_experts=16,
                      num_hidden=32, top_k=2, route_norm=True,
                      num_local_experts=held, expert_offset=first), names


def test_the_sixteen_shares_add_up_to_the_uncut_layer(ref):
    """The share test: what the 4 shares of 4 experts (the cell's 16 of 8)
    add, each routing over all 16 and renormalising over its 2, is the
    uncut reference's mixture; one share alone is not."""
    import jax
    import jax.numpy as jnp

    tok, router, ws = _moe_inputs()
    total, shares = 0.0, []
    for first in range(0, 16, 4):
        sym, names = _moe_sym(first, 4)
        exe = bind_op(sym, names, [tok, router] + [
            w[first:first + 4] for w in ws])
        shares.append(exe.forward()[0].asnumpy())
        total = total + shares[-1]
    with jax.default_matmul_precision("highest"):
        w = {"moe_router_weight": router, "moe_gate_weight": ws[0],
             "moe_up_weight": ws[1], "moe_down_weight": ws[2]}
        w = {n: jnp.asarray(a) for n, a in w.items()}
        uncut, _ = ref.moe(tiny_cfg(expert_offset=0), jnp.asarray(tok), w)
        held = {n: a if n == "moe_router_weight" else a[4:8]
                for n, a in w.items()}
        share, _ = ref.moe(tiny_cfg(), jnp.asarray(tok), held)
    assert rel(total, uncut) < 1e-5
    assert rel(shares[1], share) < 1e-5
    assert rel(shares[1], uncut) > 1e-1


# --- the whole model ---------------------------------------------------------

@pytest.mark.parametrize("t,blocks", [(32, None), (48, (16, 32))])
def test_model_logits_and_every_gradient_match_the_reference(
        ref, monkeypatch, t, blocks):
    """At T 32 in one block, at T 48 steered through three blocks in two
    spans: probabilities and every leaf's gradient; the reference's chain a
    layer at a time is autodiff of its whole loss."""
    import jax
    import jax.numpy as jnp

    if blocks:
        monkeypatch.setattr(ra, "select_block_q", lambda *a: blocks[0])
        monkeypatch.setattr(ra, "SELECT_SPAN", blocks[1])
    sym = tiny_sym_gen()(t)[0]
    ids, label = seeded_tokens(seq_len=t)
    params = seeded_params(sym, data=ids.shape, softmax_label=label.shape)
    prob, grads = mc.program_first_step(sym, params, ids, label)
    leaves = {n: jnp.asarray(a) for n, a in params.items()}
    _, want = ref.value_and_grads(jax, TINY, leaves, jnp.asarray(ids),
                                  jnp.asarray(label))
    assert set(want) == set(grads)
    if not blocks:      # the probabilities, and the chain against autodiff
        scores = ref.logits(jax, TINY, leaves, jnp.asarray(ids))
        assert rel(prob, jax.nn.softmax(scores, -1)) \
            < ref.F32_TENSOR_TOLERANCE
        with jax.default_matmul_precision("highest"):
            whole = jax.jit(jax.grad(lambda p: ref.losses(
                jax, TINY, p, jnp.asarray(ids), jnp.asarray(label))[0]))(
                    leaves)
        for n in sorted(grads):
            assert rel(want[n], whole[n]) < 5e-5, n
    for n in sorted(grads):
        assert np.asarray(want[n]).any(), n
        assert rel(grads[n], want[n]) < ref.F32_TENSOR_TOLERANCE, n


def test_the_language_model_does_not_see_the_indexers_loss(first_step):
    """With the KL coefficient at 0 and at 1 every leaf outside the indexer
    gets the same gradient, bit for bit, and the probabilities are the
    same; the indexer's five leaves a layer get nothing at 0."""
    prob1, with_loss = first_step.prob, first_step.grads
    params = first_step.params
    prob0, without = mc.program_first_step(
        tiny_sym_gen(num_hidden_layers=1, index_loss_coef=0.0)(T)[0], params,
        first_step.ids, first_step.label)
    assert np.array_equal(prob0, prob1)
    for n in params:
        if n.split("_", 1)[1] in INDEX_LEAVES:
            assert with_loss[n].any() and not without[n].any(), n
        else:
            assert np.array_equal(with_loss[n], without[n]), n


@pytest.fixture(scope="module")
def first_step(ref):
    """The one-layer program's first step on four seeded rows and the plain
    reference's: one bind and one plain reference for the tests of the
    tolerances."""
    sym = tiny_sym_gen(num_hidden_layers=1)(T)[0]
    ids, label = seeded_tokens(batch=4)
    # the seed of the weights: a target from one head of 8 moves this size's
    # ``grad_norm`` by 1.6% to 3.2% over seeds 0-3 (0.49 at published widths)
    params = seeded_params(sym, seed=2, data=ids.shape,
                           softmax_label=label.shape)
    case = mc.first_step_case(ref, ONE, sym, params, ids, label)
    # against the plain reference the program is inside the float32 limits
    assert not misses(case.got, case.want, ref.F32_TOLERANCES)
    return case


def _no_selection(ref, mp):
    mp.setattr(ref, "index_top_k", lambda cfg: 1 << 30)


def _no_relu(ref, mp):
    mp.setattr(ref, "index_activation", lambda s: s)


def _weights_unscaled(ref, mp):
    mp.setattr(ref, "index_weight_scale", lambda cfg: 1.0)


def _no_index_loss(ref, mp):
    mp.setattr(ref, "index_loss_coef", lambda cfg: 0.0)


def _target_from_one_head(ref, mp):
    import jax

    mp.setattr(ref, "target", lambda p: jax.lax.stop_gradient(p[:, 0]))


def _index_input_not_detached(ref, mp):
    mp.setattr(ref, "index_input", lambda u: u)


@pytest.mark.parametrize("mutation", [
    _no_selection, _no_relu, _weights_unscaled, _no_index_loss,
    _target_from_one_head, _index_input_not_detached])
def test_tolerances_fail_a_wrong_layer(ref, monkeypatch, first_step,
                                       mutation):
    """Against a reference that leaves a piece of the sparse attention (or
    of the block) out, the program misses even the bfloat16 trunk's
    TOLERANCES; against the plain one it is inside the float32 ones (the
    fixture holds that, once)."""
    mutation(ref, monkeypatch)
    assert misses(first_step.got, ref.first_step(*first_step.args),
                  ref.TOLERANCES)


def test_float32_tolerances_fail_a_bfloat16_trunk(ref, first_step):
    """The bfloat16 trunk is outside the float32 tolerances. (That it is
    inside TOLERANCES is a statement about published widths, checked on
    the chip by the benchmark's driver.)"""
    got = mc.first_step_of_program(
        tiny_sym_gen("bfloat16", num_hidden_layers=1)(T)[0],
        first_step.params, first_step.ids, first_step.label)
    assert misses(got, first_step.want, ref.F32_TOLERANCES) == [
        "loss", "grad_norm"]


def test_tolerances_fail_the_reference_in_float8(ref, monkeypatch,
                                                 first_step):
    """The precision below the bfloat16 the configuration states: this
    reference with float8_e4m3fn weights and projection inputs comes out as
    not correct (at this size by the loss, 2.7e-3, and by ``grad_norm``,
    1.9e-2; at published widths by ``grad_norm`` alone, 0.047: the
    reference's docstring)."""
    import jax.numpy as jnp

    def f8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    jax, cfg, leaves, ids, label = first_step.args
    want = first_step.want
    plain = ref.project
    monkeypatch.setattr(ref, "project", lambda x, w: plain(f8(x), w))
    low = {n: a if n.endswith(("_gamma", "_beta")) else f8(a)
           for n, a in leaves.items()}
    got = ref.first_step(jax, cfg, low, ids, label)
    assert misses(got, want, ref.TOLERANCES)


def test_three_adam_steps_through_fit_follow_the_reference(ref):
    """BucketingModule.fit with optimizer='adam' on three batches: the
    cross-entropy before each step is the reference's, and every leaf, the
    indexer's too, moves."""
    import jax
    import jax.numpy as jnp

    gen = tiny_sym_gen(num_hidden_layers=1)
    batches = [seeded_tokens(seed=s) for s in (11, 12, 13)]
    params = seeded_params(gen(T)[0], data=(B, T), softmax_label=(B, T))
    adam = dict(learning_rate=0.001, beta1=0.9, beta2=0.95, epsilon=1e-8)

    class Batches(mx.io.DataIter):
        def __init__(self):
            super().__init__()
            self.batch_size, self.default_bucket_key = B, T
            self.provide_data = [mx.io.DataDesc("data", (B, T))]
            self.provide_label = [mx.io.DataDesc("softmax_label", (B, T))]
            self.at = 0

        def reset(self):
            self.at = 0

        def next(self):
            if self.at == len(batches):
                raise StopIteration
            ids, label = batches[self.at]
            self.at += 1
            return mx.io.DataBatch(
                data=[mx.nd.array(ids)], label=[mx.nd.array(label)],
                bucket_key=T, provide_data=self.provide_data,
                provide_label=self.provide_label)

    seen = []

    def read_loss(param):
        prob = param.locals["self"].get_outputs()[0].asnumpy()
        lab = param.locals["data_batch"].label[0].asnumpy().reshape(-1)
        picked = prob[np.arange(lab.size), lab.astype(int)]
        seen.append(float(-np.mean(np.log(picked))))

    mod = mx.mod.BucketingModule(sym_gen=gen, default_bucket_key=T,
                                 context=mx.cpu())
    mod.fit(Batches(), num_epoch=1, eval_metric=mx.metric.Perplexity(0),
            optimizer="adam", optimizer_params=adam,
            arg_params={n: mx.nd.array(a) for n, a in params.items()},
            aux_params={}, batch_end_callback=read_loss)
    want = ref.adam_steps(
        jax, ONE, {n: jnp.asarray(a) for n, a in params.items()},
        [(jnp.asarray(i), jnp.asarray(l)) for i, l in batches],
        lr=adam["learning_rate"], beta1=0.9, beta2=0.95, eps=1e-8,
        grad_scale=float(T))
    assert seen == pytest.approx(want, rel=1e-4)
    now = mod.get_params()[0]
    for n in params:
        assert not np.array_equal(now[n].asnumpy(), params[n]), n


def test_counters_under_recomputation(monkeypatch):
    """Through ``Module``'s fused step under ``MXNET_BACKWARD_DO_MIRROR=1``
    (the cell's switch): a launched train program counts its selected
    layers and their pairs, and every attention node keeps its output,
    log-sum-exp and thresholds beside ``MoE``'s residuals."""
    from mxnet_tpu import telemetry as tm

    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    ids, label = seeded_tokens()
    mod = mx.mod.Module(tiny_sym_gen()(T)[0], context=mx.cpu())
    mod.bind(data_shapes=[("data", ids.shape)],
             label_shapes=[("softmax_label", label.shape)])
    mod.init_params(mx.init.Normal(0.1))
    mod.init_optimizer(optimizer="adam")
    before = tm.snapshot()
    mod.forward_backward(mx.io.DataBatch(data=[mx.nd.array(ids)],
                                         label=[mx.nd.array(label)]))
    mod.update()
    after = tm.snapshot()

    def delta(name):
        return after["executor"].get(name, 0) - before.get(
            "executor", {}).get(name, 0)

    kept = sum(min(i + 1, 8) for i in range(T))
    assert delta("attention_layers") == delta("attention_selected_layers") == 2
    assert delta("attention_selected_pairs") == 2 * B * 8 * kept
    assert delta("attention_index_pairs") == 2 * B * TINY["sa_config"][
        "indexer_num_heads"] * T * (T + 1) // 2
    assert delta("attention_scored_pairs") == 2 * B * 8 * T * T
    assert delta("attention_kernel_layers") == 0       # the CPU's blocks
    assert delta("moe_local_experts") == 2 * 4
    assert delta("kept_residual_nodes") == 4     # two attention, two MoE


def test_estimate_flops_and_the_parameter_count_at_published_widths():
    """The configuration's count is ``infer_shape``'s, and
    ``models.recipe.estimate_flops`` counts the pairs the queries KEEP and
    the indexer's, not the causal triangle: it is the builder's count of a
    layer's attention to the last multiply-add."""
    import json

    from mxnet_tpu.models import recipe

    with open(os.path.join(mc.ROOT, "benchmark", "configs",
                           NAME + ".json")) as f:
        cfg = json.load(f)
    builder = mc.load("configs", NAME)
    t = 16384
    sym = builder.sym_gen(cfg, mx)[0](t)[0]
    assert len(sym.list_arguments()) - 2 == 4 * 17 + 3
    arg_shapes, _, _ = sym.infer_shape(data=(1, t), softmax_label=(1, t))
    count = sum(int(np.prod(s)) for n, s in zip(sym.list_arguments(),
                                                arg_shapes)
                if n not in ("data", "softmax_label"))
    assert count == cfg["parameters"] == 314396160
    macs = recipe.estimate_flops(sym, data=(1, t), softmax_label=(1, t)) / t
    # the estimator counts every assignment of the router's, the builder
    # the share that lands on the experts held here
    routed = 4 * (8 - 8 * 8 / 128) * 3 * 2048 * 768
    assert macs - routed == pytest.approx(
        builder.forward_macs_per_token(cfg), rel=1e-9)
    dense = builder.sym_gen(dict(cfg, sa_config=dict(
        cfg["sa_config"], topk=0)), mx)[0](t)[0]
    assert recipe.estimate_flops(dense, data=(1, t),
                                 softmax_label=(1, t)) / t > 1.3 * macs
