"""Trinity-Mini's layer (AFMoE) at a tiny size on the CPU (hidden 32, 4 query
heads over 2 key/value heads of 8, window 6, 4 of 16 experts held from id 4,
top-4, T 16, vocabulary 64, one dense and three expert layers, float32)
against the plain reference ``benchmark/reference/trinity-mini.py``.

Tolerances, and why: program and reference both compute in float32 and
differ by the order of their sums (blocks of queries and keys, experts' rows
sorted, a scatter-add combine), so a tensor agrees to
``F32_TENSOR_TOLERANCE`` (3e-4 of its largest element; measured here 5e-6)
and the first step's loss and gradient norm to ``F32_TOLERANCES`` (measured
~1e-7). A bfloat16 trunk misses those by orders of magnitude.
``TOLERANCES`` are what the bfloat16 trunk is held to on the chip; leaving
out the output gate, a sandwich norm, the selection bias, the route scale,
the renormalisation, the window or the embedding scale moves the gradient
norm by more than they allow.
"""

import functools
import json
import os

import model_cases as mc
import numpy as np
import pytest
from model_cases import bind_op, misses, rel

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError

NAME = "trinity-mini"
SLIDING, FULL = "sliding_attention", "full_attention"
TINY = dict(vocab_size=64, hidden_size=32, num_dense_layers=1,
            layer_types=[SLIDING, SLIDING, FULL, SLIDING],
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            sliding_window=6, intermediate_size=48, num_experts=4,
            num_experts_published=16, expert_offset=4,
            moe_intermediate_size=16, num_experts_per_tok=4,
            num_shared_experts=1, route_norm=True, route_scale=2.826,
            rms_norm_eps=1e-5, rope_theta=10000.0, mup_enabled=True)
B, T = 2, 16


@pytest.fixture(scope="module")
def ref():
    return mc.load("reference", NAME)


def tiny_sym_gen(dtype="float32", **over):
    cfg = dict(TINY, compute_dtype=dtype, **over)
    return mc.load("configs", NAME).sym_gen(cfg, mx)[0]


def scale_rule(name):
    """The common rule, and a selection bias normal(0, 0.2): one that
    changes which experts are chosen."""
    if name.endswith("_expert_bias"):
        return 0.2, 0.0
    return mc.gains_and_weights(name)


seeded_params = functools.partial(mc.seeded_params, rule=scale_rule)
seeded_tokens = functools.partial(mc.seeded_tokens, batch=B, seq_len=T,
                                  vocab=TINY["vocab_size"])


# --- attention: a window over grouped key/value heads ------------------------

def _dense_attention(q, k, v, window, scale):
    """The whole masked score matrix over a repeated copy of k and v."""
    import jax
    import jax.numpy as jnp

    group, t = q.shape[1] // k.shape[1], q.shape[2]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = (j <= i) & ((i - j < window) if window else True)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * scale
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


@pytest.mark.parametrize("window,kv_heads", [(0, 1), (5, 1), (16, 1),
                                             (24, 8), (24, 2)])
def test_ring_attention_with_window_and_grouped_heads(window, kv_heads):
    """``RingAttention(window=...)`` on 8 query heads over ``kv_heads``
    key/value heads (8:1, 4:1 and 1:1), T 64 in blocks of 8 queries:
    values and all three gradients against dense masked attention."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu.parallel.ring_attention  # noqa: F401 (the module)
    import sys

    ra = sys.modules["mxnet_tpu.parallel.ring_attention"]

    rs = np.random.RandomState(2)
    q, g = (rs.randn(2, 8, 64, 16).astype(np.float32) for _ in range(2))
    k, v = (rs.randn(2, kv_heads, 64, 16).astype(np.float32)
            for _ in range(2))

    def blocked(q, k, v):
        return ra.blockwise_attention(q, k, v, True, 0.25, 8, window)

    want = _dense_attention(*map(jnp.asarray, (q, k, v)), window, 0.25)
    assert rel(blocked(q, k, v), want) < 1e-5
    got = jax.grad(lambda *a: jnp.sum(blocked(*a) * g), (0, 1, 2))(q, k, v)
    dense = jax.grad(lambda *a: jnp.sum(_dense_attention(
        *a, window, 0.25) * g), (0, 1, 2))(*map(jnp.asarray, (q, k, v)))
    for a, b in zip(got, dense):
        assert a.shape == b.shape and rel(a, b) < 1e-5
    # the op, at its own block size (one block here), both directions
    names = ["q", "k", "v"]
    sym = mx.sym.RingAttention(*map(mx.sym.Variable, names), causal=True,
                               window=window, scale=0.25)
    exe = bind_op(sym, names, [q, k, v])
    assert rel(exe.forward(is_train=True)[0].asnumpy(), want) < 1e-5
    exe.backward(out_grads=[mx.nd.array(g)])
    for n, b in zip(names, dense):
        assert rel(exe.grad_dict[n].asnumpy(), b) < 1e-5


def test_out_of_band_key_blocks_are_not_computed():
    """The block plan of a window: a block of queries reads from the block
    that holds the first key of its band, so the score tiles the traced
    program holds, forward and backward, are the plan's and no wider."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu.parallel.ring_attention  # noqa: F401 (the module)
    import sys

    ra = sys.modules["mxnet_tpu.parallel.ring_attention"]

    assert ra.block_plan(64, 8, True, 16)[:5] == [
        (0, 8, 0, 8), (8, 16, 0, 16), (16, 24, 0, 24), (24, 32, 8, 32),
        (32, 40, 16, 40)]
    # at the cell's sizes: 14.7 M pairs in the band, 18.4 M in its blocks,
    # 35.7 M in the blocks of the full triangle
    assert ra.scored_pairs(8192, True, 2048) == 512 * (
        512 + 1024 + 1536 + 2048 + 12 * 2560) == 18350080
    assert ra.scored_pairs(8192, True, 0) == 35651584
    # a block is the largest whose float32 score tile is within 128 MiB:
    # OLMoE's 16 heads keep 512 (exactly 128 MiB), 32 heads take 256
    assert ra.block_q_of(1, 16, 4096) == 512
    assert ra.block_q_of(1, 32, 4096, 2048) == ra.block_q_of(1, 32, 4096) == 256
    assert ra.block_q_of(1, 32, 8192) == 128 and ra.block_q_of(2, 4, 16, 6) == 512
    assert 4 * ra.scored_pairs(4096, True, 2048, 256) + ra.scored_pairs(
        4096, True, 0, 256) == 1191182336 // 32

    def score_tiles(window):
        q = jnp.zeros((1, 4, 64, 12))
        k = v = jnp.zeros((1, 2, 64, 12))
        f = jax.grad(lambda q, k, v: jnp.sum(ra.blockwise_attention(
            q, k, v, True, 0.25, 8, window)), (0, 1, 2))
        tiles = []
        for eqn in jax.make_jaxpr(f)(q, k, v).jaxpr.eqns:
            sub = eqn.params.get("call_jaxpr") or eqn.params.get("jaxpr")
            eqns = sub.jaxpr.eqns if hasattr(sub, "jaxpr") else (
                sub.eqns if sub is not None else [eqn])
            for e in eqns:
                if e.primitive.name == "dot_general":
                    shape = e.outvars[0].aval.shape
                    if shape[-1] != 12:       # a score tile, not (.., D)
                        tiles.append(shape)
        return tiles

    for window, widest in ((16, 24), (0, 64)):
        tiles = score_tiles(window)
        assert tiles and max(t[-1] for t in tiles) == widest
        # scores forward, and twice backward (recomputed, and d_out . v^T);
        # the two query heads of a key/value head folded into the rows
        forward = sum(t[-2] * t[-1] for t in tiles) // 3
        assert forward == 2 * ra.scored_pairs(64, True, window, 8)


def test_the_ring_path_refuses_window_and_grouped_heads_by_name():
    from mxnet_tpu import parallel
    from mxnet_tpu.parallel.ring_attention import ring_attention

    mesh = parallel.make_mesh({"sp": 2})
    q = mx.nd.zeros((1, 4, 16, 8))
    kv = mx.nd.zeros((1, 2, 16, 8))
    with pytest.raises(MXNetError, match="window=4 is not supported"):
        ring_attention(q, q, q, mesh=mesh, causal=True, window=4)
    with pytest.raises(MXNetError, match="4 query heads over 2 key/value"):
        ring_attention(q, kv, kv, mesh=mesh, causal=True)
    with pytest.raises(MXNetError, match="window needs causal"):
        ring_attention(q, q, q, mesh=None, causal=False, window=4)


# --- the router and the held range --------------------------------------------

def _moe_inputs(experts=16, held=16, seed=5, rows=48):
    rs = np.random.RandomState(seed)
    tok = rs.randn(rows, 32).astype(np.float32)
    router = (rs.randn(experts, 32) * 0.3).astype(np.float32)
    bias = (rs.randn(experts) * 0.2).astype(np.float32)
    ws = [(rs.randn(*s) * 0.3).astype(np.float32)
          for s in ((held, 32, 16), (held, 32, 16), (held, 16, 32))]
    return tok, router, ws, bias


def _moe_sym(first=0, held=0, **over):
    kw = dict(num_experts=16, num_hidden=16, top_k=4, score_func="sigmoid",
              route_norm=True, route_scale=2.826, expert_bias=True,
              num_local_experts=held, expert_offset=first)
    kw.update(over)
    names = ["d", "r", "g", "u", "o"] + ["b"] * kw["expert_bias"]
    return mx.sym.MoE(*map(mx.sym.Variable, names), **kw), names


def _ref_moe(ref, first, t, router, bias, gate, up, down, **over):
    cfg = dict(TINY, expert_offset=first, **over)
    return ref.moe(cfg, t, {
        "moe_router_weight": router, "moe_expert_bias": bias,
        "moe_gate_weight": gate, "moe_up_weight": up,
        "moe_down_weight": down})


@pytest.mark.parametrize("case", ["sigmoid_bias_norm_scale", "no_norm",
                                  "no_bias", "held_4_from_8"])
def test_moe_router_and_held_range_match_the_reference(ref, case):
    """``MoE`` with a sigmoid score, the selection bias, renormalised and
    scaled weights, all experts held or experts 8-11 of 16: forward and
    every gradient against the reference; the bias gets none."""
    import jax
    import jax.numpy as jnp

    first, held = (8, 4) if case == "held_4_from_8" else (0, 16)
    over = {"route_norm": case != "no_norm"}
    tok, router, ws, bias = _moe_inputs()
    ws = [w[first:first + held] for w in ws]
    use_bias = case != "no_bias"
    sym, names = _moe_sym(first, held if held < 16 else 0,
                          expert_bias=use_bias, **over)
    inputs = [tok, router] + ws + [bias] * use_bias
    exe = bind_op(sym, names, inputs)
    out = exe.forward(is_train=True)[0].asnumpy()
    head = np.random.RandomState(9).randn(*out.shape).astype(np.float32)
    exe.backward(out_grads=[mx.nd.array(head)])
    b = jnp.asarray(bias) if use_bias else jnp.zeros(16)

    def scalar(t, r, g, u, o):
        y = _ref_moe(ref, first, t, r, b, g, u, o, **over)
        return jnp.sum(y * head), y

    with jax.default_matmul_precision("highest"):
        grads, want = jax.grad(scalar, argnums=(0, 1, 2, 3, 4), has_aux=True)(
            *map(jnp.asarray, inputs[:5]))
    assert rel(out, want) < 1e-5
    for n, g in zip(names, grads):
        assert rel(exe.grad_dict[n].asnumpy(), g) < 1e-4, (case, n)
    if use_bias:
        assert not exe.grad_dict["b"].asnumpy().any()
        # and the bias does steer: without it other experts are chosen
        plain = _ref_moe(ref, first, *map(jnp.asarray, inputs[:2]),
                         jnp.zeros(16), *map(jnp.asarray, inputs[2:5]),
                         **over)
        assert rel(plain, want) > 1e-2


def test_the_sixteen_shares_add_up_to_the_uncut_layer(ref):
    """The share test: the routed parts that the 16 shares of 1 expert...
    here 4 shares of 4 experts give, plus the shared expert counted once,
    are the uncut reference's feed-forward layer."""
    import jax
    import jax.numpy as jnp

    tok, router, ws, bias = _moe_inputs()
    rs = np.random.RandomState(8)
    shared = {f"shared_{n}_weight": jnp.asarray(
        (rs.randn(*s) * 0.3).astype(np.float32))
        for n, s in (("gate", (16, 32)), ("up", (16, 32)),
                     ("down", (32, 16)))}
    total = 0.0
    for first in range(0, 16, 4):
        sym, names = _moe_sym(first, 4)
        exe = bind_op(sym, names, [tok, router] + [
            w[first:first + 4] for w in ws] + [bias])
        total = total + exe.forward()[0].asnumpy()
    with jax.default_matmul_precision("highest"):
        w = dict(shared, moe_router_weight=router, moe_expert_bias=bias,
                 moe_gate_weight=ws[0], moe_up_weight=ws[1],
                 moe_down_weight=ws[2])
        w = {n: jnp.asarray(a) for n, a in w.items()}
        uncut = ref.mlp(dict(TINY, expert_offset=0), jnp.asarray(tok), w,
                        dense=False)
        once = ref.swiglu(jnp.asarray(tok), w["shared_gate_weight"],
                          w["shared_up_weight"], w["shared_down_weight"])
    assert rel(total + np.asarray(once), uncut) < 1e-5
    assert rel(total, uncut) > 1e-2       # the shared expert is not small


def test_held_experts_are_drop_free_when_routing_collapses_onto_them(ref):
    """A router that sends every token to the four experts held here: all
    N x k assignments are live, four times the rows of one dispatch round,
    and the other rounds run too (forward and gradients); with a balanced
    router the same program takes one round."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.defs_transformer import held_round_rows

    assert held_round_rows(48 * 4, 4, 16) == 96      # 2 x the balanced 48
    assert held_round_rows(65536, 8, 128) == 8192    # the cell's layer
    assert held_round_rows(32768, 64, 64) == 32768   # every expert held
    tok, router, ws, bias = _moe_inputs()
    tok[:, 0] = 1.0
    router[:, 0] = -6.0
    router[8:12, 0] = 6.0                             # all to experts 8-11
    ws = [w[8:12] for w in ws]
    sym, names = _moe_sym(8, 4)
    inputs = [tok, router] + ws + [bias]
    exe = bind_op(sym, names, inputs)
    out = exe.forward(is_train=True)[0].asnumpy()
    head = np.random.RandomState(9).randn(*out.shape).astype(np.float32)
    exe.backward(out_grads=[mx.nd.array(head)])

    def scalar(t, r, g, u, o):
        y = _ref_moe(ref, 8, t, r, jnp.asarray(bias), g, u, o)
        return jnp.sum(y * head), y

    with jax.default_matmul_precision("highest"):
        grads, want = jax.grad(scalar, argnums=(0, 1, 2, 3, 4), has_aux=True)(
            *map(jnp.asarray, inputs[:5]))
        scores = ref.router_scores(jnp.asarray(tok), jnp.asarray(router))
        chosen = ref.route(scores, jnp.asarray(bias), 4, True, 1.0) > 0
    assert (np.flatnonzero(np.asarray(chosen).sum(0)) == [8, 9, 10, 11]).all()
    assert rel(out, want) < 1e-5
    for n, g in zip(names, grads):
        assert rel(exe.grad_dict[n].asnumpy(), g) < 1e-4, n


def test_moe_refuses_what_it_does_not_define():
    tok, router, ws, bias = _moe_inputs()
    for over, match in (({"lb_coef": 0.01}, "softmax router"),
                        ({"score_func": "softmax"}, "needs score_func"),
                        ({"score_func": "tanh", "expert_bias": False},
                         "neither")):
        sym, names = _moe_sym(**over)
        with pytest.raises(MXNetError, match=match):
            bind_op(sym, names, [tok, router] + ws
                    + [bias] * (len(names) == 6)).forward()[0].asnumpy()
    with pytest.raises(MXNetError, match=r"experts \[14, 18\) of 16"):
        _moe_sym(14, 4)[0].infer_shape(d=(48, 32))


# --- the whole model ---------------------------------------------------------

def test_model_logits_and_every_gradient_match_the_reference(ref):
    import jax
    import jax.numpy as jnp

    sym = tiny_sym_gen()(T)[0]
    ids, label = seeded_tokens()
    params = seeded_params(sym, data=ids.shape, softmax_label=label.shape)
    prob, grads = mc.program_first_step(sym, params, ids, label)
    leaves = {n: jnp.asarray(a) for n, a in params.items()}
    scores = ref.logits(jax, TINY, leaves, jnp.asarray(ids))
    assert rel(prob, jax.nn.softmax(scores, -1)) < ref.F32_TENSOR_TOLERANCE
    _, want = ref.value_and_grads(jax, TINY, leaves, jnp.asarray(ids),
                                  jnp.asarray(label))
    assert set(want) == set(grads)
    # the reference's layer-at-a-time chain is autodiff of its whole loss
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(jax.grad(lambda p: ref.losses(
            jax, TINY, p, jnp.asarray(ids), jnp.asarray(label))[0]))(leaves)
    for n in sorted(grads):
        assert rel(want[n], whole[n]) < 1e-5 or not np.asarray(
            whole[n]).any(), n
    for n in sorted(grads):
        if n.endswith("_expert_bias"):
            assert not grads[n].any() and not np.asarray(want[n]).any()
        else:
            assert rel(grads[n], want[n]) < ref.F32_TENSOR_TOLERANCE, n


def _no_output_gate(ref, mp):
    mp.setattr(ref, "gate", lambda a, g: a)


def _no_post_norms(ref, mp):
    mp.setattr(ref, "post_norm", lambda x, gain, eps: x)


def _no_selection_bias(ref, mp):
    plain = ref.route
    mp.setattr(ref, "route", lambda scores, bias, k, norm, scale: plain(
        scores, 0.0 * bias, k, norm, scale))


def _no_route_scale(ref, mp):
    plain = ref.route
    mp.setattr(ref, "route", lambda scores, bias, k, norm, scale: plain(
        scores, bias, k, norm, 1.0))


def _no_renormalisation(ref, mp):
    plain = ref.route
    mp.setattr(ref, "route", lambda scores, bias, k, norm, scale: plain(
        scores, bias, k, False, scale))


def _no_window(ref, mp):
    plain = ref.attention_mask
    mp.setattr(ref, "attention_mask", lambda t, window: plain(t, 0))


def _rotary_on_every_layer(ref, mp):
    layer = ref.layer
    mp.setattr(ref, "layer", lambda cfg, h, w, kind, dense: layer(
        dict(cfg, sliding_window=0) if kind == FULL else cfg, h, w,
        SLIDING, dense))


def _no_embedding_scale(ref, mp):
    mp.setattr(ref, "embed_scale", lambda cfg: 1.0)


def _no_shared_expert(ref, mp):
    plain = ref.swiglu
    mp.setattr(ref, "swiglu", lambda u, g, up, down: plain(
        u, g, up, down) * (g.shape[0] != TINY["moe_intermediate_size"]))


def _kv_heads_not_grouped(ref, mp):
    import jax.numpy as jnp

    plain = ref.attention
    mp.setattr(ref, "attention", lambda q, k, v, window=0: plain(
        q, jnp.roll(k, 1, axis=1), v, window))


@pytest.fixture(scope="module")
def first_step(ref):
    """Four seeded rows through the float32 program and the plain
    reference, once for the tests of the tolerances."""
    sym = tiny_sym_gen()(T)[0]
    ids, label = seeded_tokens(batch=4)
    params = seeded_params(sym, data=ids.shape, softmax_label=label.shape)
    return mc.first_step_case(ref, TINY, sym, params, ids, label)


@pytest.mark.parametrize("mutation", [
    _no_output_gate, _no_post_norms, _no_selection_bias,
    _no_route_scale, _no_renormalisation, _no_window, _rotary_on_every_layer,
    _no_embedding_scale, _no_shared_expert, _kv_heads_not_grouped])
def test_tolerances_fail_a_wrong_layer(ref, monkeypatch, first_step,
                                       mutation):
    """Against a reference that leaves a piece out, the program misses even
    the bfloat16 trunk's TOLERANCES; against the plain one it is inside the
    float32 ones."""
    got = first_step.got
    assert not misses(got, first_step.want, ref.F32_TOLERANCES)
    mutation(ref, monkeypatch)
    assert misses(got, ref.first_step(*first_step.args), ref.TOLERANCES)


def test_float32_tolerances_fail_a_bfloat16_trunk(ref, first_step):
    """The bfloat16 trunk is outside the float32 tolerances. (That it is
    inside TOLERANCES is a statement about published widths, checked on
    the chip by the benchmark's driver.)"""
    got = mc.first_step_of_program(
        tiny_sym_gen("bfloat16")(T)[0], first_step.params, first_step.ids,
        first_step.label)
    assert misses(got, first_step.want, ref.F32_TOLERANCES) == [
        "loss", "grad_norm"]


def test_three_adam_steps_through_fit_follow_the_reference(ref):
    """BucketingModule.fit with optimizer='adam' on three batches: the
    cross-entropy before each step is the reference's, and the selection
    bias, which has no gradient, does not move."""
    import jax
    import jax.numpy as jnp

    gen = tiny_sym_gen()
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
        jax, TINY, {n: jnp.asarray(a) for n, a in params.items()},
        [(jnp.asarray(i), jnp.asarray(l)) for i, l in batches],
        lr=adam["learning_rate"], beta1=0.9, beta2=0.95, eps=1e-8,
        grad_scale=float(T))
    assert seen == pytest.approx(want, rel=1e-4)
    now = mod.get_params()[0]
    for n in params:
        moved = not np.array_equal(now[n].asnumpy(), params[n])
        assert moved != n.endswith("_expert_bias"), n


def test_checkpoint_round_trip_and_counters(tmp_path):
    """The model's parameters save and load like any Module's, and a
    launched train program counts its window layers, the pairs their
    block plans score and the experts held here."""
    from mxnet_tpu import telemetry as tm

    gen = tiny_sym_gen()
    ids, label = seeded_tokens()
    mod = mx.mod.Module(gen(T)[0], context=mx.cpu())
    mod.bind(data_shapes=[("data", (B, T))],
             label_shapes=[("softmax_label", (B, T))])
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

    assert delta("moe_layers") == 3 and delta("attention_layers") == 4
    assert delta("attention_window_layers") == 3
    assert delta("attention_scored_pairs") == 4 * B * 4 * T * T
    assert delta("moe_local_experts") == 3 * 4
    assert delta("moe_assignments") == 3 * B * T * 4
    assert delta("moe_kernel_matmuls") == 0          # the CPU
    prefix = str(tmp_path / "trinity")
    mod.save_checkpoint(prefix, 1)
    sym, args, aux = mx.model.load_checkpoint(prefix, 1)
    assert sym.list_arguments() == gen(T)[0].list_arguments()
    now = mod.get_params()[0]
    for n, a in args.items():
        assert np.array_equal(a.asnumpy(), now[n].asnumpy()), n


def test_estimate_flops_is_near_the_builders_count():
    """``models.recipe.estimate_flops`` on the published configuration
    against the builder's count of what this chip computes."""
    from mxnet_tpu.models import recipe

    with open(os.path.join(mc.ROOT, "benchmark", "configs",
                           NAME + ".json")) as f:
        cfg = json.load(f)
    builder = mc.load("configs", NAME)
    sym = builder.sym_gen(cfg, mx)[0](4096)[0]
    assert len(sym.list_arguments()) - 2 == 5 * 11 + 3 + 4 * 8 + 3
    arg_shapes, _, _ = sym.infer_shape(data=(1, 4096),
                                       softmax_label=(1, 4096))
    count = sum(int(np.prod(s)) for n, s in zip(sym.list_arguments(),
                                                arg_shapes)
                if n not in ("data", "softmax_label"))
    assert count == cfg["parameters"] == 504147712
    macs = recipe.estimate_flops(sym, data=(1, 4096),
                                 softmax_label=(1, 4096)) / 4096
    assert macs > 0
