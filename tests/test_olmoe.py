"""OLMoE at a tiny size on the CPU (hidden 32, 2 heads of 16, 8 experts of
width 16, top-2, T 16, vocabulary 64, 2 layers, float32) against the plain
reference ``benchmark/reference/olmoe-1b-7b.py``.

Tolerances, and why: program and reference both compute in float32 and
differ by the order of their sums (blocks of queries, experts' rows sorted),
so a tensor agrees to ``F32_TENSOR_TOLERANCE`` (3e-4 of its largest element;
measured here ~1e-6, on the chip 3e-5) and the first step's loss and
gradient norm to ``F32_TOLERANCES`` (1e-6 / 3e-6; measured ~2e-7). A
bfloat16 trunk misses those by orders of magnitude (mutation d). ``TOLERANCES`` are
what the bfloat16 trunk is held to on the chip; dropping an expert,
renormalising the top-k weights, or leaving out a q/k norm or the rotary
embedding moves the gradient norm by more than they allow (mutations a-c).
"""

import functools
import os

import model_cases as mc
import numpy as np
import pytest
from model_cases import misses, rel

import mxnet_tpu as mx
from mxnet_tpu import models

NAME = "olmoe-1b-7b"
TINY = dict(vocab_size=64, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=2, num_experts=8, intermediate_size=16,
            num_experts_per_tok=2, rms_norm_eps=1e-5, rope_theta=10000.0,
            router_aux_loss_coef=0.01, router_z_loss_coef=0.001)
B, T = 2, 16


@pytest.fixture(scope="module")
def ref():
    return mc.load("reference", NAME)


def tiny_sym_gen(dtype="float32", **over):
    cfg = dict(TINY, **over)
    return models.olmoe_sym_gen(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], num_experts=cfg["num_experts"],
        expert_width=cfg["intermediate_size"],
        top_k=cfg["num_experts_per_tok"], lb_coef=cfg["router_aux_loss_coef"],
        z_coef=cfg["router_z_loss_coef"], dtype=dtype)


seeded_params = mc.seeded_params
seeded_tokens = functools.partial(mc.seeded_tokens, batch=B, seq_len=T,
                                  vocab=TINY["vocab_size"])


# --- each op against the reference's function for it ------------------------

def _op_cases(ref):
    import jax.numpy as jnp

    rs = np.random.RandomState(5)
    x = rs.randn(2, 6, 32).astype(np.float32)
    gain = (1 + 0.1 * rs.randn(32)).astype(np.float32)
    qkv = [rs.randn(2, 2, 48, 16).astype(np.float32) for _ in range(3)]
    tok = rs.randn(24, 32).astype(np.float32)
    w = [rs.randn(*s).astype(np.float32) * 0.3
         for s in ((8, 32), (8, 32, 16), (8, 32, 16), (8, 16, 32))]

    def ref_moe(t, *ws):
        # the op attaches N x the penalty in backward: same total here
        out, pen = ref.moe(t, *ws, 2, 0.01, 0.001)
        return out, t.shape[0] * pen

    return {
        "RMSNorm": (
            lambda d, g: mx.sym.RMSNorm(d, g, eps=1e-5), [x, gain],
            lambda d, g: (ref.rms_norm(d, g, 1e-5), 0.0)),
        "RotaryEmbedding": (
            lambda d: mx.sym.RotaryEmbedding(d, base=10000.0), [qkv[0]],
            lambda d: (ref.rotary(d, 10000.0), 0.0)),
        "RingAttention": (
            lambda q, k, v: mx.sym.RingAttention(q, k, v, causal=True), qkv,
            lambda q, k, v: (ref.attention(q, k, v), 0.0)),
        "MoE": (
            lambda d, r, g, u, o: mx.sym.MoE(
                d, r, g, u, o, num_experts=8, num_hidden=16, top_k=2,
                lb_coef=0.01, z_coef=0.001), [tok] + w, ref_moe),
    }, jnp


@pytest.mark.parametrize("op", ["RMSNorm", "RotaryEmbedding",
                                "RingAttention", "MoE"])
def test_op_forward_and_gradient_match_the_reference(ref, op):
    import jax

    cases, jnp = _op_cases(ref)
    build, inputs, ref_fn = cases[op]
    names = [f"in{i}" for i in range(len(inputs))]
    sym = build(*[mx.sym.Variable(n) for n in names])
    exe = sym.bind(mx.cpu(), {n: mx.nd.array(a) for n, a in
                              zip(names, inputs)},
                   args_grad={n: mx.nd.zeros(a.shape) for n, a in
                              zip(names, inputs)})
    out = exe.forward(is_train=True)[0].asnumpy()
    head = np.random.RandomState(9).randn(*out.shape).astype(np.float32)
    exe.backward(out_grads=[mx.nd.array(head)])

    def scalar(*args):
        y, extra = ref_fn(*args)
        return jnp.sum(y * head) + extra, y

    with jax.default_matmul_precision("highest"):
        grads, want = jax.grad(scalar, argnums=tuple(range(len(inputs))),
                               has_aux=True)(*map(jnp.asarray, inputs))
    assert rel(out, want) < 1e-5
    for n, g in zip(names, grads):
        assert rel(exe.grad_dict[n].asnumpy(), g) < 1e-4, (op, n)


def test_blockwise_attention_blocks_and_matches_full_attention():
    """The one-device path of RingAttention in blocks of 16 queries (four
    blocks here), non-causal and causal, forward and gradients, against the
    whole score matrix."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel.ring_attention import (_full_attention,
                                                   blockwise_attention)

    rs = np.random.RandomState(3)
    q, k, v, g = (jnp.asarray(rs.randn(2, 2, 64, 16).astype(np.float32))
                  for _ in range(4))
    for causal in (False, True):
        def blocked(q, k, v):
            return jnp.sum(blockwise_attention(q, k, v, causal, 0.25, 16) * g)

        def full(q, k, v):
            return jnp.sum(_full_attention(q, k, v, causal, 0.25) * g)

        assert rel(blockwise_attention(q, k, v, causal, 0.25, 16),
                   _full_attention(q, k, v, causal, 0.25)) < 1e-5
        for a, b in zip(jax.grad(blocked, (0, 1, 2))(q, k, v),
                        jax.grad(full, (0, 1, 2))(q, k, v)):
            assert rel(a, b) < 1e-5


def test_ring_attention_without_a_mesh_equals_the_old_full_attention():
    """RingAttention(causal=True) with no mesh installed, the path the
    model runs, on a (2, 2, 64, 16) case: the shared body is pinned for
    both its users."""
    import jax.numpy as jnp

    from mxnet_tpu.parallel.ring_attention import (_full_attention,
                                                   ring_attention)

    rs = np.random.RandomState(4)
    q, k, v = (rs.randn(2, 2, 64, 16).astype(np.float32) for _ in range(3))
    want = _full_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           True, 0.25)
    got = ring_attention(mx.nd.array(q), mx.nd.array(k), mx.nd.array(v),
                         mesh=None, causal=True)
    assert rel(got.asnumpy(), want) < 1e-5
    sym = mx.sym.RingAttention(*[mx.sym.Variable(n) for n in "qkv"],
                               causal=True)
    exe = sym.bind(mx.cpu(), {"q": mx.nd.array(q), "k": mx.nd.array(k),
                              "v": mx.nd.array(v)})
    assert rel(exe.forward()[0].asnumpy(), want) < 1e-5


def test_moe_is_drop_free_when_every_token_takes_the_same_experts(ref):
    """A router that sends every token to experts 3 and 5: two groups hold
    all the rows, six are empty, and nothing is dropped."""
    import jax.numpy as jnp

    rs = np.random.RandomState(6)
    tok = rs.randn(40, 32).astype(np.float32)
    router = np.zeros((8, 32), np.float32)
    ws = [rs.randn(*s).astype(np.float32) * 0.3
          for s in ((8, 32, 16), (8, 32, 16), (8, 16, 32))]
    # logits = bias-like rows: tokens get a constant feature to route on
    tok[:, 0] = 1.0
    router[3, 0], router[5, 0] = 9.0, 8.0
    out = mx.nd.MoE(mx.nd.array(tok), mx.nd.array(router),
                    *map(mx.nd.array, ws), num_experts=8, num_hidden=16,
                    top_k=2).asnumpy()
    want, _ = ref.moe(jnp.asarray(tok), jnp.asarray(router),
                      *map(jnp.asarray, ws), 2, 0.0, 0.0)
    probs = np.asarray(ref.route(
        jnp.asarray(np.exp(tok @ router.T)
                    / np.exp(tok @ router.T).sum(-1, keepdims=True)), 2))
    assert (np.flatnonzero(probs.sum(0)) == [3, 5]).all()
    assert rel(out, want) < 1e-5


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
    for n in sorted(grads):
        assert rel(grads[n], want[n]) < ref.F32_TENSOR_TOLERANCE, n


def _drop_an_expert(ref, mp):
    import jax.numpy as jnp

    plain = ref.route

    def route(probs, k):
        kept = plain(probs, k)  # drop each token's most probable expert
        return jnp.where(probs >= jnp.max(probs, -1, keepdims=True), 0.0,
                         kept)
    mp.setattr(ref, "route", route)


def _renormalise(ref, mp):
    plain = ref.route

    def route(probs, k):
        kept = plain(probs, k)
        return kept / kept.sum(-1, keepdims=True)
    mp.setattr(ref, "route", route)


def _no_qk_norm(ref, mp):
    mp.setattr(ref, "normed_projection",
               lambda u, weight, gain, eps: u @ weight.T)


def _no_rotary(ref, mp):
    mp.setattr(ref, "rotary", lambda x, theta: x)


@pytest.fixture(scope="module")
def first_step(ref):
    """Four seeded rows through the float32 program and the plain
    reference, once for the tests of the tolerances."""
    sym = tiny_sym_gen()(T)[0]
    ids, label = seeded_tokens(batch=4)
    params = seeded_params(sym, data=ids.shape, softmax_label=label.shape)
    return mc.first_step_case(ref, TINY, sym, params, ids, label)


@pytest.mark.parametrize("mutation", [_drop_an_expert, _renormalise,
                                      _no_qk_norm, _no_rotary])
def test_tolerances_fail_a_wrong_layer(ref, monkeypatch, first_step,
                                       mutation):
    """(a)-(c): against a reference that leaves a piece out, the program
    misses even the bfloat16 trunk's TOLERANCES; against the plain one it
    is inside the float32 ones."""
    got = first_step.got
    assert not misses(got, first_step.want, ref.F32_TOLERANCES)
    mutation(ref, monkeypatch)
    assert misses(got, ref.first_step(*first_step.args), ref.TOLERANCES)


def test_float32_tolerances_fail_a_bfloat16_trunk(ref, first_step):
    """(d): the bfloat16 trunk is outside the float32 tolerances. (That it
    is inside TOLERANCES is a statement about published widths, checked on
    the chip by the benchmark's driver: at 32 features bfloat16 is off by
    more, 1.5e-3 in the loss.)"""
    got = mc.first_step_of_program(
        tiny_sym_gen("bfloat16")(T)[0], first_step.params, first_step.ids,
        first_step.label)
    assert misses(got, first_step.want, ref.F32_TOLERANCES) == [
        "loss", "grad_norm"]


def test_three_adam_steps_through_fit_follow_the_reference(ref):
    """BucketingModule.fit with optimizer='adam' on three batches: the
    cross-entropy before each step is the reference's."""
    import jax
    import jax.numpy as jnp

    gen = tiny_sym_gen()
    batches = [seeded_tokens(seed=s) for s in (11, 12, 13)]
    params = seeded_params(gen(T)[0], data=(B, T), softmax_label=(B, T))
    adam = dict(learning_rate=0.01, beta1=0.9, beta2=0.95, epsilon=1e-8)

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
    # fit rescales by 1 / batch rows; SoftmaxOutput summed over B*T rows
    want = ref.adam_steps(
        jax, TINY, {n: jnp.asarray(a) for n, a in params.items()},
        [(jnp.asarray(i), jnp.asarray(l)) for i, l in batches],
        lr=adam["learning_rate"], beta1=0.9, beta2=0.95, eps=1e-8,
        grad_scale=float(T))
    assert seen == pytest.approx(want, rel=1e-4)
    assert seen[2] < seen[0]


def test_checkpoint_round_trip_and_counters(tmp_path):
    """The model's parameters save and load like any Module's, and a
    launched train program counts its layers."""
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

    assert delta("moe_layers") == 2 and delta("attention_layers") == 2
    assert delta("moe_assignments") == 2 * B * T * 2
    prefix = str(tmp_path / "olmoe")
    mod.save_checkpoint(prefix, 1)
    sym, args, aux = mx.model.load_checkpoint(prefix, 1)
    assert sym.list_arguments() == gen(T)[0].list_arguments()
    now = mod.get_params()[0]
    for n, a in args.items():
        assert np.array_equal(a.asnumpy(), now[n].asnumpy()), n


def test_estimate_flops_counts_attention_and_routed_experts():
    """``models.recipe.estimate_flops`` on the published configuration
    agrees with the benchmark builder's ``train_flops_per_unit / 3``
    (forward, 2 FLOPs a multiply-add, a token) to 1%: causal scores at
    half, eight experts of the 64."""
    import json

    from mxnet_tpu.models import recipe

    with open(os.path.join(mc.ROOT, "benchmark", "configs",
                           "olmoe-1b-7b.json")) as f:
        cfg = json.load(f)
    builder = mc.load("configs", NAME)
    sym = builder.sym_gen(cfg, mx)[0](4096)[0]
    macs = recipe.estimate_flops(sym, data=(1, 4096),
                                 softmax_label=(1, 4096)) / 4096
    assert 2 * macs == pytest.approx(builder.train_flops_per_unit(cfg) / 3,
                                     rel=0.01)
    dense = 64 * 3 * 2048 * 1024     # every expert would be 8x the routed
    assert macs < builder.forward_macs_per_token(cfg) + dense / 2


# --- the float32 islands of a bfloat16 trunk ---------------------------------
# The driver's two scalars cannot see them (a reference wholly in bfloat16
# passes TOLERANCES, benchmark/reference/olmoe-1b-7b.py), so they are held
# here: an op fed a bfloat16 tensor returns the float32 result of that
# tensor, rounded to bfloat16 once.

def _in_bfloat16(build, inputs):
    """The op of ``build`` on ``inputs[0]`` cast to bfloat16 (the rest stay
    float32 masters), its output cast back."""
    names = [f"in{i}" for i in range(len(inputs))]
    var = [mx.sym.Variable(n) for n in names]
    sym = mx.sym.Cast(build(mx.sym.Cast(var[0], dtype="bfloat16"), *var[1:]),
                      dtype="float32")
    exe = sym.bind(mx.cpu(), {n: mx.nd.array(a) for n, a in
                              zip(names, inputs)})
    return exe.forward()[0].asnumpy()


def _island_cases(ref):
    """name -> (op, inputs, the float32 island, the same in bfloat16)."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    rs = np.random.RandomState(7)
    x = rs.randn(4, 16, 32).astype(np.float32)
    gain = (1 + 0.1 * rs.randn(32)).astype(np.float32)
    heads = rs.randn(2, 2, 48, 16).astype(np.float32)
    # MoE whose expert e answers every token with the unit vector e (up 1,
    # silu(gate 8) = 8 in bfloat16, down 1/8, on a constant feature): the
    # output IS the router, p_e at the experts chosen and 0 elsewhere
    tok = rs.randn(64, 32).astype(np.float32)
    tok[:, 0] = 1.0
    router = rs.randn(8, 32).astype(np.float32)
    gate, up = np.zeros((2, 8, 32, 16), np.float32)
    down = np.zeros((8, 16, 32), np.float32)
    gate[:, 0, 0], up[:, 0, 0] = 8.0, 1.0
    down[np.arange(8), 0, np.arange(8)] = 0.125

    def routed(scores):
        probs = jax.nn.softmax(scores.astype(jnp.float32), -1)
        return jnp.pad(ref.route(probs, 2), ((0, 0), (0, 24)))

    def rotary_bf16(d):
        t, dim = d.shape[-2:]
        inv = 1.0 / 10000.0 ** (jnp.arange(0, dim, 2, dtype=bf) / dim)
        emb = jnp.tile(jnp.arange(t, dtype=bf)[:, None] * inv[None, :], 2)
        half = jnp.concatenate([-d[..., dim // 2:], d[..., :dim // 2]], -1)
        return d * jnp.cos(emb) + half * jnp.sin(emb)

    return {
        "RMSNorm": (
            lambda d, g: mx.sym.RMSNorm(d, g, eps=1e-5), [x, gain],
            lambda d, g: ref.rms_norm(d.astype(jnp.float32), g, 1e-5),
            lambda d, g: ref.rms_norm(d, g.astype(bf), bf(1e-5))),
        "RotaryEmbedding": (
            lambda d: mx.sym.RotaryEmbedding(d, base=10000.0), [heads],
            lambda d: ref.rotary(d.astype(jnp.float32), 10000.0),
            rotary_bf16),
        "MoE": (
            lambda d, r, g, u, o: mx.sym.MoE(
                d, r, g, u, o, num_experts=8, num_hidden=16, top_k=2),
            [tok, router, gate, up, down],
            lambda d, r, *_: routed(jnp.dot(d.astype(jnp.float32), r.T)),
            lambda d, r, *_: routed(jnp.dot(d, r.astype(bf).T))),
    }


def _bfloat16_roundings_apart(got, want):
    """(share of elements that differ, largest difference in units of the
    last place of a bfloat16) of two bfloat16-valued arrays, over the
    elements that are not zero in both."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    held = (got != 0) | (want != 0)
    got, want = got[held], want[held]
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.maximum(
        np.abs(got), np.abs(want)), 1e-30))) - 7)
    return float(np.mean(got != want)), float(np.max(np.abs(got - want)
                                                     / ulp))


@pytest.mark.parametrize("op", ["RMSNorm", "RotaryEmbedding", "MoE"])
def test_float32_islands_of_a_bfloat16_trunk(ref, op):
    """On a bfloat16 input, RMSNorm's statistics, the rotary angles and
    rotation, and MoE's expert choice and probabilities are those of
    float32 arithmetic on that input: the output is the reference's
    float32 result rounded to bfloat16 once (under 2% of the elements a
    last place apart, from the order of float32 sums), where the same
    island computed in bfloat16 is several places off in most of them."""
    import jax
    import jax.numpy as jnp

    build, inputs, island, in_bfloat16 = _island_cases(ref)[op]
    got = _in_bfloat16(build, inputs)
    args = [jnp.asarray(inputs[0]).astype(jnp.bfloat16)] + [
        jnp.asarray(a) for a in inputs[1:]]
    with jax.default_matmul_precision("highest"):
        want = island(*args).astype(jnp.bfloat16)
        lost = in_bfloat16(*args).astype(jnp.bfloat16)
    if op == "MoE":    # two experts a token, the same two
        assert np.array_equal(got != 0, np.asarray(want) != 0)
        assert (np.sum(got != 0, -1) == 2).all()
    differ, places = _bfloat16_roundings_apart(got, want)
    assert differ < 0.02 and places <= 1.0, (differ, places)
    differ, places = _bfloat16_roundings_apart(lost, want)
    assert differ > 0.2 and places > 2.0, (differ, places)


# --- what a 626 M-parameter model forced on the executor ---------------------

def test_bind_makes_no_zeros_until_they_are_read():
    """simple_bind hands out arguments and gradients that allocate on first
    read, and copyto writes such a target without making its zeros."""
    sym = tiny_sym_gen()(T)[0]
    exe = sym.simple_bind(mx.cpu(), data=(B, T), softmax_label=(B, T))
    assert all(h._d is None for h in exe.arg_dict.values())
    assert all(h._d is None for h in exe.grad_dict.values())
    src = mx.nd.array(np.ones(exe.arg_dict["pred_weight"].shape, np.float32))
    src.copyto(exe.arg_dict["pred_weight"])
    assert exe.arg_dict["pred_weight"]._d is not None
    assert exe.arg_dict["pred_weight"].asnumpy().all()
    assert exe.arg_dict["embed_weight"]._d is None
    grad = exe.grad_dict["embed_weight"]
    assert grad.shape == (64, 32) and not grad.asnumpy().any()


def _trained(update_kwargs, steps=2):
    ids, label = seeded_tokens()
    batch = mx.io.DataBatch(data=[mx.nd.array(ids)],
                            label=[mx.nd.array(label)])
    sym = tiny_sym_gen()(T)[0]
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (B, T))],
             label_shapes=[("softmax_label", (B, T))])
    params = seeded_params(sym, data=(B, T), softmax_label=(B, T))
    mod.init_params(arg_params={n: mx.nd.array(a) for n, a in params.items()},
                    aux_params={})
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 0.01})
    for _ in range(steps):
        mod.forward_backward(batch)
        mod.update(**update_kwargs)
    return mod, batch


def _gradient_bytes(mod):
    exe = mod._exec_group._exec
    return sum(int(np.prod(exe.arg_dict[n].shape)) * 4
               for n in exe._wrt_names)


def test_update_publishes_gradients_as_its_caller_says():
    """``Module.update(publish_grads=False)`` returns no gradients from the
    fused step: the weights move exactly as when it publishes them,
    ``grad_dict`` raises with advice that works, and a backward that is
    read before ``update`` still serves them."""
    from mxnet_tpu.base import MXNetError

    (kept, _), (left_out, batch) = _trained({}), _trained(
        {"publish_grads": False})
    exe = left_out._exec_group._exec
    with pytest.raises(MXNetError, match="before update"):
        exe.grad_dict["pred_weight"].asnumpy()
    assert kept._exec_group._exec.grad_dict["pred_weight"].asnumpy().any()
    a, b = kept.get_params()[0], left_out.get_params()[0]
    for n in a:
        assert np.array_equal(a[n].asnumpy(), b[n].asnumpy()), n
    left_out.forward_backward(batch)
    assert exe.grad_dict["pred_weight"].asnumpy().any()
    left_out.update(publish_grads=True)
    assert exe.grad_dict["pred_weight"].asnumpy().any()


@pytest.mark.parametrize("eighths,published", [(7.9, False), (8.1, True)])
def test_update_alone_leaves_out_gradients_over_an_eighth_of_the_device(
        monkeypatch, eighths, published):
    """Without its caller's word, ``update()`` publishes gradients unless
    one set of them is over an eighth of the memory the device reports; an
    explicit True is honoured on a crowded device too, also through a
    one-step ``train_window``."""
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.context import Context

    mod, batch = _trained({}, steps=0)
    limit = int(_gradient_bytes(mod) * eighths)
    monkeypatch.setattr(Context, "memory_stats",
                        lambda self: {"bytes_limit": limit})
    exe = mod._exec_group._exec
    mod.forward_backward(batch)
    mod.update()
    if published:
        assert exe.grad_dict["pred_weight"].asnumpy().any()
    else:
        with pytest.raises(MXNetError, match="not published"):
            exe.grad_dict["pred_weight"].asnumpy()
    mod.forward_backward(batch)
    mod.update(publish_grads=True)
    assert exe.grad_dict["pred_weight"].asnumpy().any()
    boundary = mod.train_window(batch, 1, publish_grads=True)
    assert boundary.grads()["pred_weight"].asnumpy().any()
