"""Kimi-Linear-48B-A3B's layers (Kimi Delta Attention: a delta rule whose
state fades by a gate a key channel; latent attention without positions; a
sigmoid-routed held-expert mixture with one shared expert) at a tiny size on
the CPU (hidden 64, 4 KDA heads of 16, 4 latent heads of 24 + 8 over values
of 16, latent 32, 4 of 16 experts held from id 4, top-3, T 16, vocabulary
64, published layers 1-5: KDA + dense, KDA, KDA, latent, KDA over sparse
blocks, float32) against the plain reference
``benchmark/reference/kimi-linear-48b-a3b.py``, whose recurrence runs a
token at a time. (The operator's two gate forms are in
``test_gated_delta_channel.py``.)

Tolerances, and why: program and reference both compute in float32 and
differ by the order of their sums (chunks and sub-chunks against tokens,
blocks of queries and keys, experts' rows sorted, a scatter-add combine), so
a tensor agrees to ``F32_TENSOR_TOLERANCE`` and the first step's loss and
gradient norm to ``F32_TOLERANCES``. A bfloat16 trunk misses those by orders
of magnitude. ``TOLERANCES`` are what the bfloat16 trunk is held to on the
chip; a gate averaged over a head's channels, the state dropped between
chunks, rotated 'rope' dims, or a left-out convolution, norm, gate or
renormalisation moves the loss or the gradient norm by more than they allow.
"""

import functools
import json
import os

import model_cases as mc
import numpy as np
import pytest
from model_cases import bind_op, misses, rel
from test_kanana2 import _moe_inputs

import mxnet_tpu as mx

NAME = "kimi-linear-48b-a3b"
TINY = dict(vocab_size=64, hidden_size=64, num_hidden_layers=5,
            linear_attn_config=dict(kda_layers=[1, 2, 3, 5],
                                    full_attn_layers=[4], num_heads=4,
                                    head_dim=16, short_conv_kernel_size=4),
            first_k_dense_replace=1, num_attention_heads=4,
            num_key_value_heads=4, qk_nope_head_dim=24, qk_rope_head_dim=8,
            v_head_dim=16, kv_lora_rank=32, intermediate_size=96,
            num_experts=4, num_experts_published=16, expert_offset=4,
            moe_intermediate_size=16, num_experts_per_token=3,
            num_shared_experts=1, moe_renormalize=True,
            routed_scaling_factor=2.446, rms_norm_eps=1e-5)
B, T = 2, 16


@pytest.fixture(scope="module")
def ref():
    return mc.load("reference", NAME)


def tiny_sym_gen(dtype="float32", **over):
    cfg = dict(TINY, compute_dtype=dtype, **over)
    return mc.load("configs", NAME).sym_gen(cfg, mx)[0]


def scale_rule(name):
    """The common rule, a selection bias normal(0, 0.2) (one that changes
    which experts are chosen), ``A_log`` uniform over [0, ln 16] and
    ``dt_bias`` uniform over [ln 0.001, ln 0.5] a channel: decays from
    nothing to half a token."""
    if name.endswith("_A_log"):
        return "uniform", 0.0, np.log(16.0)
    if name.endswith("_dt_bias"):
        return "uniform", np.log(0.001), np.log(0.5)
    if name.endswith("_expert_bias"):
        return 0.2, 0.0
    return mc.gains_and_weights(name)


seeded_params = functools.partial(mc.seeded_params, rule=scale_rule)
seeded_tokens = functools.partial(mc.seeded_tokens, batch=B, seq_len=T,
                                  vocab=TINY["vocab_size"])


# --- the layer pattern -----------------------------------------------------------

def _ops_by_layer(sym):
    nodes = json.loads(sym.tojson())["nodes"]
    return {n["name"]: n["op"] for n in nodes if n["op"] != "null"}


def test_the_layer_pattern_comes_from_the_two_lists():
    """Published layer i + 1 is what the two lists say: a ``GatedDeltaRule``
    with a convolution on the KDA layers, a ``RingAttention`` and no
    rotation on the full ones; dense first, sparse after."""
    ops = _ops_by_layer(tiny_sym_gen()(T)[0])
    for i in range(5):
        full = i == 3
        assert (f"l{i}_attn" in ops) == full
        assert (f"l{i}_delta" in ops) == (f"l{i}_conv" in ops) == (not full)
        assert (f"l{i}_moe" in ops) == (i >= 1)
        assert (f"l{i}_mlp_down" in ops) == (i == 0)
    assert "RotaryEmbedding" not in ops.values()
    other = dict(TINY["linear_attn_config"], kda_layers=[2, 4],
                 full_attn_layers=[1, 3, 5])
    ops = _ops_by_layer(tiny_sym_gen(linear_attn_config=other)(T)[0])
    assert [f"l{i}_attn" in ops for i in range(5)] == [
        True, False, True, False, True]


@pytest.mark.parametrize("kda,full", [([1, 2, 3], [4]),
                                      ([1, 2, 3, 4, 5], [4])])
def test_a_layer_in_both_lists_or_in_neither_is_refused(kda, full):
    lists = dict(TINY["linear_attn_config"], kda_layers=kda,
                 full_attn_layers=full)
    with pytest.raises(ValueError, match="kda_layers"):
        tiny_sym_gen(linear_attn_config=lists)


def test_the_published_defaults_are_the_published_pattern():
    from mxnet_tpu.models import kimi_linear

    full = (4, 8, 12, 16, 20, 24, 27)
    assert kimi_linear._KDA_LAYERS == tuple(
        i for i in range(1, 28) if i not in full)


# --- the latent block, with and without positions --------------------------------

def _deepseek_gen(**over):
    from mxnet_tpu import models

    return models.deepseek_v3_sym_gen(**dict(dict(
        vocab_size=64, hidden_size=64, num_layers=3, num_heads=4,
        qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16,
        kv_lora_rank=32, dense_width=96, num_experts=16, expert_width=16,
        top_k=3, num_local_experts=4, expert_offset=4), **over))


def test_deepseek_v3_symbol_is_unchanged_node_for_node():
    """``deepseek_v3.py``'s blocks became functions that ``kimi_linear.py``
    calls too; its own graph is what it was before the factoring (the
    digests are the parent tree's: kanana-2-30b-a3b's program does not
    change)."""
    import hashlib

    def digest(gen):
        with mx.name.NameManager():     # unnamed nodes count from 0
            return hashlib.sha256(
                gen(128)[0].tojson().encode()).hexdigest()[:16]

    from mxnet_tpu import models

    assert digest(_deepseek_gen(num_shared_experts=0)) == "06f1e20cdf692e29"
    assert digest(models.deepseek_v3_sym_gen(
        num_layers=3, dtype="bfloat16")) == "c718eec07ba566ba"
    assert digest(models.qwen3_next_sym_gen(
        num_layers=4, dtype="bfloat16")) == "32a9b6a97bd33c8c"


def test_the_latent_block_unrotated_is_the_rotated_one_less_its_rotations():
    """The two builders call one function: a model of one full layer over
    a dense SwiGLU has ``deepseek_v3``'s nodes without the two
    ``RotaryEmbedding`` nodes and without the split and the concatenation
    around the query's; the parameters are the same."""
    from collections import Counter

    lists = dict(TINY["linear_attn_config"], kda_layers=[],
                 full_attn_layers=[1])
    ours = tiny_sym_gen(num_hidden_layers=1, linear_attn_config=lists)(T)[0]
    theirs = _deepseek_gen(num_layers=1, rms_norm_eps=1e-5)(T)[0]
    assert ours.list_arguments() == theirs.list_arguments()
    rotated = Counter(_ops_by_layer(theirs).values())
    rotated.subtract({"RotaryEmbedding": 2, "Concat": 1, "slice_axis": 2})
    assert +rotated == Counter(_ops_by_layer(ours).values())


# --- the share -------------------------------------------------------------------

def _moe_sym(first, held):
    names = ["d", "r", "g", "u", "o", "b"]
    return mx.sym.MoE(
        *map(mx.sym.Variable, names), num_experts=16, num_hidden=16, top_k=3,
        score_func="sigmoid", route_norm=True, route_scale=2.446,
        expert_bias=True, num_local_experts=held, expert_offset=first), names


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """The share test: the routed parts that 4 shares of 4 experts give
    (the cell's 32 shares of 8), plus the one shared expert counted once,
    are the uncut reference's feed-forward layer; and one share is the
    reference's share."""
    import jax
    import jax.numpy as jnp

    tok, router, ws, bias = _moe_inputs()
    rs = np.random.RandomState(8)
    shared = {f"shared_{n}_weight": (rs.randn(*s) * 0.3).astype(np.float32)
              for n, s in (("gate", (16, 64)), ("up", (16, 64)),
                           ("down", (64, 16)))}
    w = dict(shared, moe_router_weight=router, moe_expert_bias=bias,
             moe_gate_weight=ws[0], moe_up_weight=ws[1],
             moe_down_weight=ws[2])
    w = {n: jnp.asarray(a) for n, a in w.items()}
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for first in range(0, 16, 4):
            sym, names = _moe_sym(first, 4)
            exe = bind_op(sym, names, [tok, router] + [
                x[first:first + 4] for x in ws] + [bias])
            part = exe.forward()[0].asnumpy()
            held = dict(w, **{f"moe_{n}_weight": w[f"moe_{n}_weight"][
                first:first + 4] for n in ("gate", "up", "down")})
            assert rel(part, ref.moe(dict(TINY, expert_offset=first),
                                     jnp.asarray(tok), held)) < 1e-5
            total = total + part
        uncut = ref.mlp(dict(TINY, expert_offset=0), jnp.asarray(tok), w,
                        dense=False)
        once = ref.swiglu(jnp.asarray(tok), w["shared_gate_weight"],
                          w["shared_up_weight"], w["shared_down_weight"])
    assert rel(total + np.asarray(once), uncut) < 1e-5
    assert rel(total, uncut) > 1e-2       # the shared expert is not small


# --- the whole model -------------------------------------------------------------

@pytest.mark.parametrize("seq_len", [T, 80])
def test_model_logits_and_every_gradient_match_the_reference(ref, seq_len):
    """At T 16 (one short chunk) and at T 80 (a whole chunk of 64 with its
    four sub-chunks, and a padded one): probabilities and every gradient
    leaf."""
    import jax
    import jax.numpy as jnp

    sym = tiny_sym_gen()(seq_len)[0]
    ids, label = seeded_tokens(seq_len=seq_len)
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
        assert rel(want[n], whole[n]) < 3e-5 or not np.asarray(
            whole[n]).any(), n
    for n in sorted(grads):
        if n.endswith("_expert_bias"):
            assert not grads[n].any() and not np.asarray(want[n]).any()
        else:
            assert rel(grads[n], want[n]) < ref.F32_TENSOR_TOLERANCE, n


def _gate_averaged_over_a_heads_channels(ref, mp):
    """A gate a head: the model rewritten onto the scalar rule."""
    plain = ref.log_decay

    def averaged(a, a_log, dt_bias):
        g = plain(a, a_log, dt_bias)
        return 0.0 * g + g.mean(-1, keepdims=True)

    mp.setattr(ref, "log_decay", averaged)


def _state_dropped_between_chunks(ref, mp):
    """The state starts at 0 again every 64 tokens."""
    import jax.numpy as jnp

    plain = ref.delta_rule

    def chunked(q, k, v, g, beta):
        return jnp.concatenate(
            [plain(*(x[:, :, a:a + 64] for x in (q, k, v, g, beta)))
             for a in range(0, q.shape[2], 64)], 2)

    mp.setattr(ref, "delta_rule", chunked)


def _no_decay(ref, mp):
    mp.setattr(ref, "log_decay", lambda a, a_log, dt_bias: 0.0 * a)


def _write_strength_one(ref, mp):
    mp.setattr(ref, "write_strength", lambda b: 0.0 * b + 1.0)


def _no_convolution(ref, mp):
    mp.setattr(ref, "causal_conv", lambda x, w: x)


def _no_unit_length(ref, mp):
    mp.setattr(ref, "unit_length", lambda x: x)


def _silu_output_gate(ref, mp):
    """Qwen3-Next's gated norm (``silu``) where this family has a
    ``sigmoid``."""
    import jax

    mp.setattr(ref, "gated_norm", lambda o, z, gain, eps: ref.rms_norm(
        o, gain, eps) * jax.nn.silu(z))


def _rotated_shared_key(ref, mp):
    """The 64 'rope' dims rotated after all (``mla_use_nope`` ignored): the
    kanana form, pairs (2i, 2i + 1) at theta 1e4."""
    import jax.numpy as jnp

    def rotary(x, theta=10000.0):
        t, d = x.shape[-2:]
        inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        even, odd = x[..., 0::2], x[..., 1::2]
        return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                         -1).reshape(x.shape)

    plain = ref.keys
    mp.setattr(ref, "keys", lambda k_nope, k_shared: plain(
        k_nope, rotary(k_shared)))


def _no_latent_norm(ref, mp):
    mp.setattr(ref, "latent_norm", lambda c, gain, eps: c)


def _scale_of_the_nope_dims(ref, mp):
    mp.setattr(ref, "score_scale",
               lambda cfg: cfg["qk_nope_head_dim"] ** -0.5)


def _no_renormalisation(ref, mp):
    plain = ref.route
    mp.setattr(ref, "route", lambda scores, bias, k, norm, scale: plain(
        scores, bias, k, False, scale))


def _no_route_scale(ref, mp):
    plain = ref.route
    mp.setattr(ref, "route", lambda scores, bias, k, norm, scale: plain(
        scores, bias, k, norm, 1.0))


def _no_shared_expert(ref, mp):
    plain = ref.swiglu
    width = TINY["moe_intermediate_size"] * TINY["num_shared_experts"]
    mp.setattr(ref, "swiglu", lambda u, g, up, down: plain(
        u, g, up, down) * (g.shape[0] != width))


@pytest.fixture(scope="module")
def first_steps(ref):
    """The program's first step and the plain reference's at T 128, two
    chunks of 64, so that a state dropped between them shows: computed once
    for all the mutations."""
    sym = tiny_sym_gen()(128)[0]
    ids, label = seeded_tokens(batch=2, seq_len=128)
    params = seeded_params(sym, data=ids.shape, softmax_label=label.shape)
    return mc.first_step_case(ref, TINY, sym, params, ids, label)


def test_float32_tolerances_hold_the_program(first_steps, ref):
    assert not misses(first_steps.got, first_steps.want, ref.F32_TOLERANCES)


@pytest.mark.parametrize("mutation", [
    _gate_averaged_over_a_heads_channels, _state_dropped_between_chunks,
    _no_decay, _write_strength_one, _no_convolution, _no_unit_length,
    _silu_output_gate, _rotated_shared_key, _no_latent_norm,
    _scale_of_the_nope_dims, _no_renormalisation, _no_route_scale,
    _no_shared_expert])
def test_tolerances_fail_a_wrong_layer(first_steps, ref, monkeypatch,
                                       mutation):
    """Against a reference that leaves a piece out, the program misses even
    the bfloat16 trunk's TOLERANCES (against the plain one it is inside the
    float32 ones: the test above)."""
    mutation(ref, monkeypatch)
    assert misses(first_steps.got, ref.first_step(*first_steps.args),
                  ref.TOLERANCES)


@pytest.fixture(scope="module")
def four_rows(ref):
    """(params, ids, label, the reference's arguments, the plain reference's
    reading) of four seeded rows of T 16, once for the two tests below."""
    ids, label = seeded_tokens(batch=4)
    params = seeded_params(tiny_sym_gen()(T)[0], data=ids.shape,
                           softmax_label=label.shape)
    args = mc.reference_args(TINY, params, ids, label)
    return params, ids, label, args, ref.first_step(*args)


def test_float32_tolerances_fail_a_bfloat16_trunk(ref, four_rows):
    """The bfloat16 trunk is outside the float32 tolerances. (That it is
    inside TOLERANCES is a statement about published widths, checked on
    the chip by the benchmark's driver.)"""
    params, ids, label, _, want = four_rows
    got = mc.first_step_of_program(tiny_sym_gen("bfloat16")(T)[0], params,
                                   ids, label)
    assert misses(got, want, ref.F32_TOLERANCES) == ["loss", "grad_norm"]


def test_tolerances_fail_the_reference_in_float8(ref, monkeypatch, four_rows):
    """The precision below the bfloat16 the configuration states: this
    reference with float8_e4m3fn weights and projection inputs misses the
    limit the check rests on."""
    import jax.numpy as jnp

    def f8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    (jax, _, leaves, *args), want = four_rows[3], four_rows[4]
    plain = ref.project
    monkeypatch.setattr(ref, "project", lambda x, w: plain(f8(x), w))
    low = {n: a if n.endswith(("_gamma", "_expert_bias", "_A_log",
                               "_dt_bias")) else f8(a)
           for n, a in leaves.items()}
    got = ref.first_step(jax, TINY, low, *args)
    assert "grad_norm" in misses(got, want, ref.TOLERANCES)


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


@pytest.mark.parametrize("mirror", ["0", "1"])
def test_counters_nodes_and_checkpoint_round_trip(tmp_path, monkeypatch,
                                                  mirror):
    """The model's parameters save and load like any Module's; its nodes
    carry the names a profile by operator reads the mixers apart by; and a
    launched train program counts its four channel-gated layers (none in a
    kernel: no kernel computes them), its one latent layer and its expert
    layers, with per-operator recomputation on and off."""
    from mxnet_tpu import telemetry as tm

    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", mirror)
    gen = tiny_sym_gen()
    nodes = set(gen(T)[0].get_internals().list_outputs())
    for part in ("qkv", "conv", "f_a", "f_b", "b", "delta", "out_norm",
                 "g_a", "g_b", "o", "moe", "shared_down"):
        assert f"l1_{part}_output" in nodes, part
    for part in ("q", "kv_a", "kv_a_norm", "kv_b", "attn", "o"):
        assert f"l3_{part}_output" in nodes, part
    ids, label = seeded_tokens()
    mod = mx.mod.Module(gen(T)[0], context=mx.cpu())
    mod.bind(data_shapes=[("data", (B, T))],
             label_shapes=[("softmax_label", (B, T))])
    mod.init_params(mx.init.Normal(0.1))
    mod.init_optimizer(optimizer="adam")
    # the builder's own defaults: decays spread over a head's channels
    args = mod.get_params()[0]
    dt_bias = args["l0_dt_bias"].asnumpy()
    assert dt_bias.shape == (4, 1, 16)
    assert np.allclose(np.exp(dt_bias[:, 0, 0]), 0.001)
    assert np.allclose(np.exp(dt_bias[:, 0, -1]), 0.1)
    assert np.allclose(np.exp(args["l0_A_log"].asnumpy()[:, 0, 0]),
                       [1.0, 16 ** (1 / 3), 16 ** (2 / 3), 16.0])
    before = tm.snapshot()
    mod.forward_backward(mx.io.DataBatch(data=[mx.nd.array(ids)],
                                         label=[mx.nd.array(label)]))
    mod.update()
    after = tm.snapshot()

    def delta(name):
        return after["executor"].get(name, 0) - before.get(
            "executor", {}).get(name, 0)

    assert delta("linear_attention_layers") == 4
    assert delta("linear_attention_channel_gated_layers") == 4
    assert delta("linear_attention_chunks") == 4 * B
    assert delta("linear_attention_kernel_layers") == 0
    assert delta("linear_attention_scan_kernel_layers") == 0
    assert delta("conv_kernel_layers") == 0            # the CPU
    assert delta("attention_layers") == delta("attention_latent_layers") == 1
    assert delta("attention_pair_lanes") == 24 + 8 + 16
    assert delta("moe_layers") == 4 and delta("moe_local_experts") == 4 * 4
    assert delta("moe_assignments") == 4 * B * T * 3
    prefix = str(tmp_path / "kimi")
    mod.save_checkpoint(prefix, 1)
    sym, args, aux = mx.model.load_checkpoint(prefix, 1)
    assert sym.list_arguments() == gen(T)[0].list_arguments()
    now = mod.get_params()[0]
    for n, a in args.items():
        assert np.array_equal(a.asnumpy(), now[n].asnumpy()), n


def test_a_gate_a_head_counts_no_channel_gated_layer():
    """The alarm's other side: Qwen3-Next's rule, a gate a head, moves
    ``linear_attention_layers`` and not the new counter."""
    from mxnet_tpu import models, telemetry as tm

    gen = models.qwen3_next_sym_gen(
        vocab_size=64, hidden_size=32, num_layers=2,
        full_attention_interval=2, num_heads=2, num_kv_heads=1, head_dim=16,
        linear_key_heads=2, linear_value_heads=4, linear_key_dim=8,
        linear_value_dim=8, num_experts=8, expert_width=16, top_k=2,
        shared_expert_width=16)
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

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    assert delta("linear_attention_layers") == 1
    assert delta("linear_attention_channel_gated_layers") == 0


def test_estimate_flops_and_the_parameter_count_of_the_published_cut():
    """``models.recipe.estimate_flops`` on the published configuration
    against the builder's count of what this chip computes, and the
    parameters of the cut against the configuration's table."""
    from mxnet_tpu.models import recipe

    with open(os.path.join(mc.ROOT, "benchmark", "configs", NAME + ".json")) as f:
        cfg = json.load(f)
    builder = mc.load("configs", NAME)
    t = 4096
    sym = builder.sym_gen(cfg, mx)[0](t)[0]
    arg_shapes, _, _ = sym.infer_shape(data=(1, t), softmax_label=(1, t))
    sizes = {n: int(np.prod(s)) for n, s in zip(sym.list_arguments(),
                                                arg_shapes)
             if n not in ("data", "softmax_label")}
    assert sum(sizes.values()) == cfg["parameters"] == 602434432
    assert sum(v for n, v in sizes.items() if n.startswith("l1_") and not
               n.startswith(("l1_moe", "l1_shared", "l1_input",
                             "l1_post"))) == 39514272     # a KDA mixer
    assert sum(v for n, v in sizes.items() if n.startswith("l3_") and not
               n.startswith(("l3_moe", "l3_shared", "l3_input",
                             "l3_post"))) == 29114880     # the latent mixer
    macs = recipe.estimate_flops(sym, data=(1, t), softmax_label=(1, t)) / t
    # estimate_flops sends every token to top_k experts (all of them held);
    # the builder counts the 8 of 256 held here
    all_held = 4 * (8 - 8 * 8 / 256) * 3 * 2304 * 1024
    assert macs == pytest.approx(
        builder.forward_macs_per_token(cfg) + all_held, rel=1e-6)
