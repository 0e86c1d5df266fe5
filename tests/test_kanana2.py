"""kanana-2-30b-a3b's layer (DeepSeek-V3: multi-head latent attention over a
sigmoid-routed held-expert mixture) at a tiny size on the CPU (hidden 64, 4
heads of 24 + 8 rotated over values of 16, latent 32, 4 of 16 experts held
from id 4, top-3, two shared experts, T 16, vocabulary 64, one dense and two
expert layers, float32) against the plain reference
``benchmark/reference/kanana-2-30b-a3b.py``. (``RingAttention`` with values
narrower than keys, and the interleaved rotary pairing, are in
``test_latent_attention.py``.)

Tolerances, and why: program and reference both compute in float32 and
differ by the order of their sums (blocks of queries and keys, experts' rows
sorted, a scatter-add combine), so a tensor agrees to
``F32_TENSOR_TOLERANCE`` and the first step's loss and gradient norm to
``F32_TOLERANCES``. A bfloat16 trunk misses those by orders of magnitude.
``TOLERANCES`` are what the bfloat16 trunk is held to on the chip; leaving
out the latent's norm, the interleaved pairing, the sharing of the rotated
key, the rotated dims in the softmax scale, the renormalisation or the route
scale moves the loss or the gradient norm by more than they allow.
"""

import functools
import json
import os

import model_cases as mc
import numpy as np
import pytest
from model_cases import bind_op, misses, rel

import mxnet_tpu as mx

NAME = "kanana-2-30b-a3b"
TINY = dict(vocab_size=64, hidden_size=64, num_hidden_layers=3,
            first_k_dense_replace=1, num_attention_heads=4,
            qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16,
            kv_lora_rank=32, intermediate_size=96, n_routed_experts=4,
            n_routed_experts_published=16, expert_offset=4,
            moe_intermediate_size=16, num_experts_per_tok=3,
            n_shared_experts=2, norm_topk_prob=True,
            routed_scaling_factor=2.448, rms_norm_eps=1e-6,
            rope_theta=1000000, rope_interleave=True)
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


# --- the share -------------------------------------------------------------------

def _moe_inputs(seed=5, rows=48):
    rs = np.random.RandomState(seed)
    tok = rs.randn(rows, 64).astype(np.float32)
    router = (rs.randn(16, 64) * 0.3).astype(np.float32)
    bias = (rs.randn(16) * 0.2).astype(np.float32)
    ws = [(rs.randn(*s) * 0.3).astype(np.float32)
          for s in ((16, 64, 16), (16, 64, 16), (16, 16, 64))]
    return tok, router, ws, bias


def _moe_sym(first, held):
    names = ["d", "r", "g", "u", "o", "b"]
    return mx.sym.MoE(
        *map(mx.sym.Variable, names), num_experts=16, num_hidden=16, top_k=3,
        score_func="sigmoid", route_norm=True, route_scale=2.448,
        expert_bias=True, num_local_experts=held, expert_offset=first), names


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """The share test: the routed parts that 4 shares of 4 experts give
    (the cell's 16 shares of 8), plus the two shared experts counted once,
    are the uncut reference's feed-forward layer; and one share is the
    reference's share."""
    import jax
    import jax.numpy as jnp

    tok, router, ws, bias = _moe_inputs()
    rs = np.random.RandomState(8)
    shared = {f"shared_{n}_weight": (rs.randn(*s) * 0.3).astype(np.float32)
              for n, s in (("gate", (32, 64)), ("up", (32, 64)),
                           ("down", (64, 32)))}
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
    assert rel(total, uncut) > 1e-2       # the shared experts are not small


# --- the whole model -------------------------------------------------------------

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


def _no_latent_norm(ref, mp):
    mp.setattr(ref, "latent_norm", lambda c, gain, eps: c)


def _rotate_half_pairing(ref, mp):
    import jax.numpy as jnp

    def rotate_half(x, theta):
        t, d = x.shape[-2:]
        inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        a, b = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    mp.setattr(ref, "rotary", rotate_half)


def _rotated_key_not_shared(ref, mp):
    """Only the first head sees the rotated key: a per-head key that the
    other heads' rows of ``kv_a`` would have to supply."""
    import jax.numpy as jnp

    plain = ref.keys

    def first_head_only(k_nope, k_rope):
        k = plain(k_nope, k_rope)
        nope = k_nope.shape[-1]
        return jnp.concatenate([k[..., :nope], k[..., nope:].at[:, 1:].set(
            0.0)], -1)

    mp.setattr(ref, "keys", first_head_only)


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


def _no_selection_bias(ref, mp):
    plain = ref.route
    mp.setattr(ref, "route", lambda scores, bias, k, norm, scale: plain(
        scores, 0.0 * bias, k, norm, scale))


def _no_shared_experts(ref, mp):
    plain = ref.swiglu
    width = TINY["moe_intermediate_size"] * TINY["n_shared_experts"]
    mp.setattr(ref, "swiglu", lambda u, g, up, down: plain(
        u, g, up, down) * (g.shape[0] != width))


def _no_positions(ref, mp):
    mp.setattr(ref, "rotary", lambda x, theta: x)


@pytest.fixture(scope="module")
def first_step(ref):
    """Four seeded rows through the float32 program and the plain
    reference, once for the tests of the tolerances."""
    sym = tiny_sym_gen()(T)[0]
    ids, label = seeded_tokens(batch=4)
    params = seeded_params(sym, data=ids.shape, softmax_label=label.shape)
    return mc.first_step_case(ref, TINY, sym, params, ids, label)


@pytest.mark.parametrize("mutation", [
    _no_latent_norm, _rotate_half_pairing, _rotated_key_not_shared,
    _scale_of_the_nope_dims, _no_renormalisation, _no_route_scale,
    _no_selection_bias, _no_shared_experts, _no_positions])
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
    limit the check rests on (at published widths, 1 x 8192 tokens, a
    builder's scratch run read 1.2e-4 on the loss, inside its limit, and
    0.78 on ``grad_norm``: PERF.md section 6, PR 41)."""
    import jax.numpy as jnp

    def f8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    jax, _, leaves, *args = first_step.args
    want = first_step.want
    plain = ref.project
    monkeypatch.setattr(ref, "project", lambda x, w: plain(f8(x), w))
    low = {n: a if n.endswith(("_gamma", "_expert_bias")) else f8(a)
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
    carry the names a profile by operator reads the mixer apart by; and a
    launched train program counts its latent layers and the lanes a pair
    is computed over (24 + 8 scored, 16 weighed), with per-operator
    recomputation on and off."""
    from mxnet_tpu import telemetry as tm

    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", mirror)
    gen = tiny_sym_gen()
    nodes = set(gen(T)[0].get_internals().list_outputs())
    for part in ("q", "kv_a", "kv_a_norm", "kv_b", "attn", "o", "moe",
                 "shared_gate", "shared_up", "shared_down"):
        assert f"l1_{part}_output" in nodes, part
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

    assert delta("attention_layers") == delta("attention_latent_layers") == 3
    assert delta("attention_pair_lanes") == 3 * (24 + 8 + 16)
    assert delta("attention_window_layers") == 0
    assert delta("attention_kernel_layers") == 0      # the CPU
    assert delta("attention_scored_pairs") == 3 * B * 4 * T * T
    assert delta("moe_layers") == 2 and delta("moe_local_experts") == 2 * 4
    assert delta("moe_assignments") == 2 * B * T * 3
    # attention and the expert layers keep what their backward reads
    assert delta("kept_residual_nodes") == (5 if mirror == "1" else 0)
    prefix = str(tmp_path / "kanana2")
    mod.save_checkpoint(prefix, 1)
    sym, args, aux = mx.model.load_checkpoint(prefix, 1)
    assert sym.list_arguments() == gen(T)[0].list_arguments()
    now = mod.get_params()[0]
    for n, a in args.items():
        assert np.array_equal(a.asnumpy(), now[n].asnumpy()), n


def test_a_model_of_equal_widths_counts_no_latent_layer():
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
    assert after.get("attention_latent_layers", 0) == before.get(
        "attention_latent_layers", 0)
    assert after["attention_pair_lanes"] - before.get(
        "attention_pair_lanes", 0) == 2 * (8 + 8)


def test_estimate_flops_counts_the_scores_at_both_widths():
    """``models.recipe.estimate_flops`` on the published configuration
    against the builder's count of what this chip computes: ``p.v`` at the
    values' 128."""
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
    assert count == cfg["parameters"] == 424961024
    macs = recipe.estimate_flops(sym, data=(1, t), softmax_label=(1, t)) / t
    # estimate_flops sends every token to top_k experts (all of them held);
    # the builder counts the 8 of 128 held here
    all_held = 4 * (6 - 6 * 8 / 128) * 3 * 2048 * 768
    assert macs == pytest.approx(
        builder.forward_macs_per_token(cfg) + all_held, rel=1e-6)
    scores = 5 * builder.score_macs_per_token(cfg)
    assert scores == 5 * 32 * 4096 * (192 + 128)
