"""ZAYA1-8B's layer (compressed convolutional attention over a top-1 mixture
routed by an MLP that carries state down the layers, residual scaling, a
tied head) at a tiny size on the CPU (hidden 64, 4 query over 2 key/value
heads of 16, 4 of 8 experts held from id 4, router width 16, T 16,
vocabulary 64, three layers, float32) against the plain reference
``benchmark/reference/zaya1-8b.py``. (``MoE(router="graph")``, the new forms
of ``CausalConv1D`` and ``Activation("gelu")`` alone are in
``test_zaya_ops.py``.)

Tolerances, and why: program and reference both compute in float32 and
differ by the order of their sums (blocks of queries and keys, experts' rows
sorted, a scatter-add combine), so a tensor agrees to
``F32_TENSOR_TOLERANCE`` and the first step's loss and gradient norm to
``F32_TOLERANCES``. A bfloat16 trunk misses those by orders of magnitude.
``TOLERANCES`` are what the bfloat16 trunk is held to on the chip; leaving
out a tap of either convolution, the q-k mean, the value shift, the key
temperature, the partial rotation, the router's carry, a residual scale or
the routing weight moves the loss or the gradient norm by more than they
allow.
"""

import functools
import json
import os

import model_cases as mc
import numpy as np
import pytest
from model_cases import misses, rel

import mxnet_tpu as mx

NAME = "zaya1-8b"
TINY = dict(vocab_size=64, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            cca_time0=2, cca_time1=2, partial_rotary_factor=0.5,
            num_experts=4, num_experts_published=8, expert_offset=4,
            moe_intermediate_size=16, num_experts_per_tok=1,
            router_hidden_size=16, rms_norm_eps=1e-5,
            rope_parameters={"hybrid": {"rope_theta": 5000000}},
            tie_word_embeddings=True)
B, T = 2, 16


@pytest.fixture(scope="module")
def ref():
    return mc.load("reference", NAME)


def tiny_sym_gen(dtype="float32", **over):
    cfg = dict(TINY, compute_dtype=dtype, **over)
    return mc.load("configs", NAME).sym_gen(cfg, mx)[0]


def scale_rule(name):
    """The common rule (biases and residual betas are weights here), with
    the carry's gamma normal(0.5, 0.1)."""
    if name.endswith("_carry_gamma"):
        return 0.1, 0.5
    return mc.gains_and_weights(name)


# seed 3: one whose router sends tokens to the held experts in every layer
# (a random MLP router at 16 features can send a layer's every token
# elsewhere, and the layer then trains nothing)
seeded_params = functools.partial(mc.seeded_params, rule=scale_rule, seed=3)
seeded_tokens = functools.partial(mc.seeded_tokens, batch=B, seq_len=T,
                                  vocab=TINY["vocab_size"])


# --- the share -------------------------------------------------------------------

@pytest.mark.parametrize("held", [4, 2])
def test_the_shares_add_up_to_the_uncut_layer(ref, held):
    """The share test: what the shares of ``held`` experts give (the cell's
    2 shares of 8) for the ONE set of logits every chip's router computes
    alike add up to the uncut reference's mixture; and one share is the
    reference's share."""
    import jax
    import jax.numpy as jnp

    rs = np.random.RandomState(5)
    tok = rs.randn(48, 64).astype(np.float32)
    logits = rs.randn(48, 8).astype(np.float32)
    ws = [(rs.randn(*s) * 0.3).astype(np.float32)
          for s in ((8, 64, 16), (8, 64, 16), (8, 16, 64))]
    w = dict(zip(("moe_gate_weight", "moe_up_weight", "moe_down_weight"),
                 map(jnp.asarray, ws)))
    names = ["d", "z", "g", "u", "o"]
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for first in range(0, 8, held):
            sym = mx.sym.MoE(
                *map(mx.sym.Variable, names), router="graph", num_experts=8,
                num_hidden=16, top_k=1, num_local_experts=held,
                expert_offset=first)
            exe = sym.bind(mx.cpu(), {n: mx.nd.array(a) for n, a in zip(
                names, [tok, logits] + [x[first:first + held] for x in ws])})
            part = exe.forward()[0].asnumpy()
            share = {n: a[first:first + held] for n, a in w.items()}
            assert rel(part, ref.mixture(
                dict(TINY, expert_offset=first), jnp.asarray(tok),
                jnp.asarray(logits), share)) < 1e-5
            assert np.abs(part).max() > 0.1
            total = total + part
        uncut = ref.mixture(dict(TINY, expert_offset=0), jnp.asarray(tok),
                            jnp.asarray(logits), w)
    assert rel(total, uncut) < 1e-5
    # top-1: every token's whole term comes from exactly one share
    assert rel(part, uncut) > 1e-2


# --- the whole model -------------------------------------------------------------

def test_model_logits_and_every_gradient_match_the_reference(ref):
    import jax
    import jax.numpy as jnp

    sym = tiny_sym_gen()(T)[0]
    ids, label = seeded_tokens()
    params = seeded_params(sym, data=ids.shape, softmax_label=label.shape)
    assert "pred_weight" not in params and "embed_weight" in params
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
        assert rel(want[n], whole[n]) < 1e-5, n
        assert np.asarray(want[n]).any(), n
        assert rel(grads[n], want[n]) < ref.F32_TENSOR_TOLERANCE, n


def _no_depthwise_tap(ref, mp):
    """conv0 without its tap on the token before."""
    mp.setattr(ref, "depthwise_conv", lambda c, w, b: c * w[:, -1] + b)


def _no_grouped_tap(ref, mp):
    """conv1 without its tap on the token before."""
    plain = ref.grouped_conv
    mp.setattr(ref, "grouped_conv", lambda c, w, b: plain(
        c, w.at[..., :-1].set(0.0), b))


def _no_qk_mean(ref, mp):
    plain = ref.qk_mean
    mp.setattr(ref, "qk_mean", lambda q0, k0: tuple(
        0.0 * m for m in plain(q0, k0)))


def _no_value_shift(ref, mp):
    import jax.numpy as jnp

    mp.setattr(ref, "values", lambda u, w1, w2: jnp.concatenate(
        [ref.project(u, w1), ref.project(u, w2)], -1))


def _no_key_temperature(ref, mp):
    mp.setattr(ref, "temperature", lambda k, tau: k)


def _whole_head_rotated(ref, mp):
    plain = ref.rotary
    mp.setattr(ref, "rotary", lambda x, theta, dims: plain(
        x, theta, x.shape[-1]))


def _no_carry(ref, mp):
    mp.setattr(ref, "carry", lambda r, state, gamma: r)


def _one_residual_scale_left_out(ref, mp):
    """The mixer's output scale ``s_o`` at 1."""
    plain = ref.residual
    mp.setattr(ref, "residual", lambda x, out, w, pre: plain(
        x, out, dict(w, attn_out_gamma=1.0), pre))


def _no_routing_weight(ref, mp):
    """The chosen expert's output at weight 1 and not ``p_e``."""
    plain = ref.route
    mp.setattr(ref, "route", lambda logits, k: (
        plain(logits, k) > 0).astype(logits.dtype))


@pytest.fixture(scope="module")
def first_step(ref):
    """Four seeded rows through the float32 program and the plain
    reference, once for the tests of the tolerances."""
    sym = tiny_sym_gen()(T)[0]
    ids, label = seeded_tokens(batch=4)
    params = seeded_params(sym, data=ids.shape, softmax_label=label.shape)
    return mc.first_step_case(ref, TINY, sym, params, ids, label)


@pytest.mark.parametrize("mutation", [
    _no_depthwise_tap, _no_grouped_tap, _no_qk_mean, _no_value_shift,
    _no_key_temperature, _whole_head_rotated, _no_carry,
    _one_residual_scale_left_out, _no_routing_weight])
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


def test_tolerances_fail_the_reference_in_float8(ref, monkeypatch,
                                                 first_step):
    """The precision below the bfloat16 the configuration states: this
    reference with float8_e4m3fn weights and projection inputs misses the
    limit the check rests on (PERF.md section 6, PR 44, has the reading at
    published widths)."""
    import jax.numpy as jnp

    def f8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    jax, _, leaves, *args = first_step.args
    want = first_step.want
    plain = ref.project
    monkeypatch.setattr(ref, "project",
                        lambda x, w, b=None: plain(f8(x), w, b))
    low = {n: a if n.endswith(("_gamma", "_beta", "_bias")) else f8(a)
           for n, a in leaves.items()}
    got = ref.first_step(jax, TINY, low, *args)
    assert "grad_norm" in misses(got, want, ref.TOLERANCES)


def test_three_adam_steps_through_fit_follow_the_reference(ref):
    """BucketingModule.fit with optimizer='adam' on three batches: the
    cross-entropy before each step is the reference's, and the tied table
    ends where ONE Adam update a step on the sum of its two gradients (the
    embedding's and the head's) puts it."""
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
    final = {}
    want = ref.adam_steps(
        jax, TINY, {n: jnp.asarray(a) for n, a in params.items()},
        [(jnp.asarray(i), jnp.asarray(l)) for i, l in batches],
        lr=adam["learning_rate"], beta1=0.9, beta2=0.95, eps=1e-8,
        grad_scale=float(T), final=final)
    assert seen == pytest.approx(want, rel=1e-4)
    now = mod.get_params()[0]
    assert set(now) == set(params)
    for n in params:
        assert not np.array_equal(now[n].asnumpy(), params[n]), n
    moved = np.abs(now["embed_weight"].asnumpy() - params["embed_weight"])
    # every row of the table moved (the head reads all of them), by three
    # steps of about the learning rate and not six
    assert moved.min() > 0 and moved.max() < 3.5 * adam["learning_rate"]
    assert np.abs(now["embed_weight"].asnumpy()
                  - np.asarray(final["embed_weight"])).max() < 2e-5


@pytest.mark.parametrize("mirror", ["0", "1"])
def test_counters_nodes_and_checkpoint_round_trip(tmp_path, monkeypatch,
                                                  mirror):
    """The model's parameters save and load like any Module's; its nodes
    carry the names a profile by operator reads the mixer and the router
    apart by; and a launched train program counts its graph-routed expert
    layers and its grouped convolutions, with per-operator recomputation on
    and off."""
    from mxnet_tpu import telemetry as tm

    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", mirror)
    gen = tiny_sym_gen()
    sym = gen(T)[0]
    nodes = set(sym.get_internals().list_outputs())
    for part in ("q", "k", "conv0", "conv1", "v1", "v2", "attn", "o",
                 "router_down", "router_norm", "router_fc1", "router_fc2",
                 "router_out", "moe"):
        assert f"l1_{part}_output" in nodes, part
    assert "l0_router_carry_gamma" not in sym.list_arguments()
    assert "l1_router_carry_gamma" in sym.list_arguments()
    assert "pred_weight" not in sym.list_arguments()
    ids, label = seeded_tokens()
    mod = mx.mod.Module(sym, context=mx.cpu())
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

    assert delta("moe_graph_routed_layers") == delta("moe_layers") == 3
    assert delta("conv_grouped_layers") == 3
    assert delta("moe_local_experts") == 3 * 4
    assert delta("moe_assignments") == 3 * B * T * 1
    assert delta("attention_layers") == 3
    assert delta("attention_latent_layers") == 0
    assert delta("attention_window_layers") == 0
    assert delta("attention_kernel_layers") == 0      # the CPU
    assert delta("attention_scored_pairs") == 3 * B * 4 * T * T
    assert delta("linear_attention_layers") == 0
    # attention and the expert layers keep what their backward reads
    assert delta("kept_residual_nodes") == (6 if mirror == "1" else 0)
    prefix = str(tmp_path / "zaya1")
    mod.save_checkpoint(prefix, 1)
    loaded, args, aux = mx.model.load_checkpoint(prefix, 1)
    assert loaded.list_arguments() == sym.list_arguments()
    now = mod.get_params()[0]
    for n, a in args.items():
        assert np.array_equal(a.asnumpy(), now[n].asnumpy()), n


def test_a_weight_routed_model_counts_no_graph_router():
    from mxnet_tpu import models, telemetry as tm

    gen = models.olmoe_sym_gen(vocab_size=64, hidden_size=32, num_layers=2,
                               num_heads=4, num_experts=8, expert_width=16,
                               top_k=2)
    mod = mx.mod.Module(gen(T)[0], context=mx.cpu())
    mod.bind(data_shapes=[("data", (B, T))],
             label_shapes=[("softmax_label", (B, T))])
    mod.init_params(mx.init.Normal(0.1))
    mod.init_optimizer(optimizer="adam")
    ids, label = seeded_tokens()
    before = tm.snapshot().get("executor", {})
    mod.forward_backward(mx.io.DataBatch(data=[mx.nd.array(ids)],
                                         label=[mx.nd.array(label)]))
    mod.update()
    after = tm.snapshot()["executor"]
    for name in ("moe_graph_routed_layers", "conv_grouped_layers"):
        assert after.get(name, 0) == before.get(name, 0), name
    assert after["moe_layers"] - before.get("moe_layers", 0) == 2


def test_estimate_flops_counts_the_convolutions_and_no_router_twice():
    """``models.recipe.estimate_flops`` on the published configuration
    against the builder's count of what this chip computes: both
    convolutions (2 taps a channel; 2 x 128 a channel inside a head), the
    router's four products as the graph's ``FullyConnected`` nodes and not
    again inside ``MoE``."""
    from mxnet_tpu.models import recipe

    with open(os.path.join(mc.ROOT, "benchmark", "configs", NAME + ".json")) as f:
        cfg = json.load(f)
    builder = mc.load("configs", NAME)
    t = 8192
    sym = builder.sym_gen(cfg, mx)[0](t)[0]
    arg_shapes, _, _ = sym.infer_shape(data=(1, t), softmax_label=(1, t))
    count = sum(int(np.prod(s)) for n, s in zip(sym.list_arguments(),
                                                arg_shapes)
                if n not in ("data", "softmax_label"))
    assert count == cfg["parameters"] == 494822152
    macs = recipe.estimate_flops(sym, data=(1, t), softmax_label=(1, t)) / t
    # estimate_flops sends every token to its top-1 expert (all of them
    # held); the builder counts the half that the 8 of 16 held here receive
    all_held = 4 * (1 - 8 / 16) * 3 * 2048 * 2048
    assert macs == pytest.approx(
        builder.forward_macs_per_token(cfg) + all_held, rel=1e-6)
    assert builder.conv_macs_per_token(cfg) == 1280 * 2 + 10 * 128 * 128 * 2
    assert builder.router_macs_per_token(cfg) == 2048 * 256 + 2 * 256 * 256 \
        + 256 * 16
