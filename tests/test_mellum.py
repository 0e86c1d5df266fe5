"""Mellum 2 at a tiny size on the CPU (hidden 64, 8 query heads over 2
key/value heads of 16, two periods of three window layers and one full layer,
a band of 8 keys, T 32: twice the 16 positions the full layers' YaRN
frequencies start from, so that the ramp rises inside the head's 8 pairs; 4
of 16 experts held from id 4, top-2, vocabulary 64, float32) against the
plain reference ``benchmark/reference/mellum2-12b-a2.5b.py``.

Tolerances, and why: program and reference both compute in float32 and
differ by the order of their sums (a walk over key blocks against one masked
softmax, experts' rows sorted), so a tensor agrees to
``F32_TENSOR_TOLERANCE`` and the first step's loss and gradient norm to
``F32_TOLERANCES``. ``TOLERANCES`` are what the bfloat16 trunk is held to on
the chip. Each mutation leaves one piece out of the REFERENCE (the YaRN
frequencies, the amplitude, the band, one key of it, the schedules by kind,
the per-head norms, the renormalisation of the routing weights): the program
must then be past a leaf's limit and past the scalars'.

The shared block (``keye_vl2.qwen3_moe_block``) now takes its layer's
rotation from the caller: the Keye-VL-2.0 and SDAR programs at their tests'
tiny sizes lower to the text they lowered to before.
"""

import functools
import json
import os
import sys

import model_cases as mc
import numpy as np
import pytest
from model_cases import bind_op, misses, rel

import mxnet_tpu as mx
import mxnet_tpu.parallel.ring_attention  # noqa: F401 (the module, below)
from mxnet_tpu import models
from mxnet_tpu import telemetry as tm
from mxnet_tpu.models.keye_vl2 import qwen3_moe_block
from mxnet_tpu.models.mellum import rotary_keywords

sys.path.insert(0, os.path.join(mc.ROOT, "tools"))
ra = sys.modules["mxnet_tpu.parallel.ring_attention"]
NAME = "mellum2-12b-a2.5b"
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
ROPE = {
    "sliding_attention": {"rope_type": "default", "rope_theta": 100},
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 100, "factor": 4,
        "original_max_position_embeddings": 16, "beta_fast": 1,
        "beta_slow": 0.1, "attention_factor": 1.25},
}
TINY = dict(vocab_size=64, hidden_size=64, num_hidden_layers=8,
            layer_types=PERIOD * 2, num_attention_heads=8,
            num_key_value_heads=2, head_dim=16, sliding_window=8,
            num_experts=4, num_experts_published=16, expert_offset=4,
            moe_intermediate_size=32, num_experts_per_tok=2,
            norm_topk_prob=True, router_aux_loss_coef=0.001,
            rms_norm_eps=1e-6, rope_parameters=ROPE)
B, T = 2, 32


@pytest.fixture(scope="module")
def ref():
    return mc.load("reference", NAME)


def tiny_cfg(**over):
    cfg = dict(TINY, **over)
    cfg["num_hidden_layers"] = len(cfg["layer_types"])
    return cfg


def tiny_sym_gen(dtype="float32", **over):
    return mc.load("configs", NAME).sym_gen(
        dict(tiny_cfg(**over), compute_dtype=dtype), mx)[0]


seeded_params = mc.seeded_params
seeded_tokens = functools.partial(mc.seeded_tokens, batch=B, seq_len=T,
                                  vocab=TINY["vocab_size"])


def test_the_tiny_ramp_rises_inside_the_head(ref):
    """The preset is worth its mutations: over the head's 8 pairs the full
    layers' ramp is 0 on the fastest, 1 on the slowest and between on
    others, T is past the length the frequencies start from, and the band is
    shorter than T."""
    rope = ROPE["full_attention"]
    plain = ref.inv_freq(ROPE["sliding_attention"], 8)
    ratio = ref.inv_freq(rope, 8) / plain
    assert ratio[0] == 1.0 and ratio[-1] == 1 / rope["factor"]
    assert np.sum((ratio < 1.0) & (ratio > 1 / rope["factor"])) >= 3
    assert rope["original_max_position_embeddings"] < T
    assert TINY["sliding_window"] < T
    assert ref.amplitude(rope) == 1.25
    assert ref.amplitude(dict(rope, attention_factor=None)) \
        == 0.1 * np.log(4.0) + 1.0
    # and the builder hands the operator the same numbers
    assert rotary_keywords(rope) == dict(
        base=100.0, scaling="yarn", factor=4.0, original_max_position=16,
        beta_fast=1.0, beta_slow=0.1, attention_factor=1.25)
    assert rotary_keywords(ROPE["sliding_attention"]) == dict(base=100.0)


# --- the share -----------------------------------------------------------------

EXPERTS = 64    # the PUBLISHED count, 8 a share


def _layer_inputs(seed=5):
    """A (B, T, 64) stream and one layer's leaves, all ``EXPERTS`` experts."""
    rs = np.random.RandomState(seed)

    def draw(*shape, scale=0.3):
        return (rs.randn(*shape) * scale).astype(np.float32)

    def gain(n):
        return (1.0 + 0.1 * rs.randn(n)).astype(np.float32)

    x = draw(B, T, 64, scale=1.0)
    w = {"input_norm_gamma": gain(64), "q_weight": draw(128, 64),
         "q_norm_gamma": gain(16), "k_weight": draw(32, 64),
         "k_norm_gamma": gain(16), "v_weight": draw(32, 64),
         "o_weight": draw(64, 128), "post_norm_gamma": gain(64),
         "moe_router_weight": draw(EXPERTS, 64),
         "moe_gate_weight": draw(EXPERTS, 64, 32),
         "moe_up_weight": draw(EXPERTS, 64, 32),
         "moe_down_weight": draw(EXPERTS, 32, 64)}
    return x, w


def _layer_sym(kind, first, held):
    attention = lambda q, k, v, u: mx.sym.RingAttention(  # noqa: E731
        q, k, v, causal=True,
        window=TINY["sliding_window"] if kind == "sliding_attention" else 0,
        name="l0_attn")
    return qwen3_moe_block(
        mx.sym.Variable("x"), "l0_", attention, hidden_size=64, num_heads=8,
        num_kv_heads=2, head_dim=16, num_experts=EXPERTS, expert_width=32,
        top_k=8, route_norm=True, num_local_experts=held,
        expert_offset=first, rms_norm_eps=1e-6,
        rotary=rotary_keywords(ROPE[kind]), lb_coef=0.0)


def _layer_out(kind, x, w, first, held):
    sym = _layer_sym(kind, first, held)
    leaves = {"x": x}
    for name in sym.list_arguments():
        if name == "x":
            continue
        a = w[name[len("l0_"):]]
        held_leaf = name.startswith("l0_moe_") and "router" not in name
        leaves[name] = a[first:first + held] if held_leaf and held else a
    names = sym.list_arguments()
    return bind_op(sym, names, [leaves[n] for n in names]).forward()[
        0].asnumpy()


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_the_eight_shares_add_up_to_the_uncut_layer(ref, kind):
    """The share test at the published counts, 64 experts in 8 shares of 8,
    top-8: what the 8 shares' experts add, each share routing over all 64
    and renormalising over its 8, with attention and the residual (what
    every chip computes alike: a share whose experts write nothing) counted
    once, is the uncut reference's layer; one share alone is not."""
    import jax
    import jax.numpy as jnp

    x, w = _layer_inputs()
    silent = dict(w, moe_down_weight=np.zeros_like(w["moe_down_weight"]))
    alike = _layer_out(kind, x, silent, 0, 8)
    shares = [_layer_out(kind, x, w, first, 8)
              for first in range(0, EXPERTS, 8)]
    total = alike + sum(share - alike for share in shares)
    cfg = dict(TINY, num_experts_per_tok=8, expert_offset=0,
               router_aux_loss_coef=0.0)
    with jax.default_matmul_precision("highest"):
        leaves = {n: jnp.asarray(a) for n, a in w.items()}
        uncut, _ = ref.layer(cfg, kind, jnp.asarray(x), leaves)
        held = {n: a[8:16] if n in ("moe_gate_weight", "moe_up_weight",
                                    "moe_down_weight") else a
                for n, a in leaves.items()}
        second, _ = ref.layer(dict(cfg, expert_offset=8), kind,
                              jnp.asarray(x), held)
    assert rel(total, uncut) < 1e-5
    assert rel(shares[1], second) < 1e-5
    assert rel(shares[1] - alike, uncut - alike) > 1e-1
    # and all 64 held at once is the uncut layer too
    assert rel(_layer_out(kind, x, w, 0, 0), uncut) < 1e-5


# --- the whole model -----------------------------------------------------------

@pytest.fixture(scope="module")
def first_step(ref):
    """The two-period program's first step on seeded rows and the plain
    reference's: one bind and one plain reference for every test below."""
    sym = tiny_sym_gen()(T)[0]
    ids, label = seeded_tokens()
    params = seeded_params(sym, data=ids.shape, softmax_label=label.shape)
    case = mc.first_step_case(ref, TINY, sym, params, ids, label)
    assert not misses(case.got, case.want, ref.F32_TOLERANCES)
    return case


def test_model_logits_and_every_gradient_match_the_reference(ref,
                                                             first_step):
    """Probabilities and every leaf's gradient in float32; the reference's
    chain a layer at a time is autodiff of its whole loss."""
    import jax
    import jax.numpy as jnp

    _, cfg, leaves, ids, label = first_step.args
    _, want = ref.value_and_grads(jax, cfg, leaves, ids, label)
    assert set(want) == set(first_step.grads)
    assert len(want) == 8 * 12 + 3
    scores = ref.logits(jax, cfg, leaves, ids)
    assert rel(first_step.prob, jax.nn.softmax(scores, -1)) \
        < ref.F32_TENSOR_TOLERANCE
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(jax.grad(lambda p: ref.losses(
            jax, cfg, p, ids, label)[0]))(leaves)
    for n in sorted(want):
        assert np.asarray(want[n]).any(), n
        assert rel(want[n], whole[n]) < 5e-5, n
        assert rel(first_step.grads[n], want[n]) \
            < ref.F32_TENSOR_TOLERANCE, n


def test_the_bfloat16_trunk_follows_the_reference_and_misses_float32s(
        ref, first_step):
    """The bfloat16 trunk's output, loss and every gradient are the float32
    reference's to bfloat16's rounding through eight layers of 64 features
    (per cent, not the chip's limits: ``TOLERANCES`` are a statement about
    published widths, checked there by the benchmark's driver), and outside
    the float32 tolerances."""
    prob, grads = mc.program_first_step(
        tiny_sym_gen("bfloat16")(T)[0], first_step.params, first_step.ids,
        first_step.label)
    got = mc.reading(prob, grads, first_step.label)
    assert misses(got, first_step.want, ref.F32_TOLERANCES) == [
        "loss", "grad_norm"]
    assert not misses(got, first_step.want, {"loss": 2e-2, "grad_norm": 5e-2})
    assert rel(prob, first_step.prob) < 0.1
    apart = sum(np.sum(np.square(grads[n] - first_step.grads[n],
                                 dtype=np.float64)) for n in grads)
    assert np.sqrt(apart) < 0.25 * first_step.got["grad_norm"]


def _geometric_on_the_full_layers(ref, mp):
    plain = ref.inv_freq
    mp.setattr(ref, "inv_freq", lambda rope, half: plain(
        dict(rope, rope_type="default"), half))


def _no_amplitude(ref, mp):
    mp.setattr(ref, "amplitude", lambda rope: 1.0)


def _no_band(ref, mp):
    mp.setattr(ref, "band", lambda cfg, kind: 0)


def _a_band_one_key_wider(ref, mp):
    band = ref.band
    mp.setattr(ref, "band", lambda cfg, kind: band(cfg, kind)
               and band(cfg, kind) + 1)


def _schedules_swapped(ref, mp):
    other = {"sliding_attention": "full_attention",
             "full_attention": "sliding_attention"}
    mp.setattr(ref, "rope_of",
               lambda cfg, kind: cfg["rope_parameters"][other[kind]])


def _no_head_norms(ref, mp):
    mp.setattr(ref, "head_norm", lambda z, gain, eps: z)


def _no_renormalisation(ref, mp):
    route = ref.route
    mp.setattr(ref, "route", lambda probs, k, norm: route(probs, k, False))


MUTATIONS = [
    _geometric_on_the_full_layers, _no_amplitude, _no_band,
    _a_band_one_key_wider, _schedules_swapped, _no_head_norms,
    _no_renormalisation]


@pytest.mark.parametrize("mutation", MUTATIONS,
                         ids=[m.__name__[1:] for m in MUTATIONS])
def test_tolerances_fail_a_wrong_layer(ref, monkeypatch, first_step,
                                       mutation):
    """Against a reference that leaves one piece out, some leaf's gradient
    is past its limit and the first step misses even the bfloat16 trunk's
    TOLERANCES, a single key of the band included; against the plain one
    every leaf and both scalars are inside the float32 limits (the fixture
    and the test above hold that, once)."""
    mutation(ref, monkeypatch)
    jax, cfg, leaves, ids, label = first_step.args
    ce, want = ref.value_and_grads(jax, cfg, leaves, ids, label)
    off = [n for n in want if rel(first_step.grads[n], want[n])
           > ref.F32_TENSOR_TOLERANCE]
    assert off
    norm = float(np.sqrt(sum(np.sum(np.square(np.asarray(g, np.float64)))
                             for g in want.values())))
    assert misses(first_step.got, {"loss": float(ce), "grad_norm": norm},
                  ref.TOLERANCES)


def test_three_adam_steps_through_fit_follow_the_reference(ref):
    """BucketingModule.fit with optimizer='adam' on three batches of the
    one-period model: the cross-entropy before each step is the
    reference's, and every leaf moves."""
    import jax
    import jax.numpy as jnp

    cfg = tiny_cfg(layer_types=PERIOD)
    gen = tiny_sym_gen(layer_types=PERIOD)
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
        jax, cfg, {n: jnp.asarray(a) for n, a in params.items()},
        [(jnp.asarray(i), jnp.asarray(l)) for i, l in batches],
        lr=adam["learning_rate"], beta1=0.9, beta2=0.95, eps=1e-8,
        grad_scale=float(T))
    assert seen == pytest.approx(want, rel=1e-4)
    now = mod.get_params()[0]
    for n in params:
        assert not np.array_equal(now[n].asnumpy(), params[n]), n


# --- the counters ----------------------------------------------------------------

@pytest.mark.parametrize("mirror", ["", "1"], ids=["kept", "recomputed"])
def test_launch_counts_are_the_closed_forms(monkeypatch, mirror):
    """Through ``Module``'s fused step (and under
    ``MXNET_BACKWARD_DO_MIRROR=1``, the cell's switch): a launched train
    program counts 2 scaled rotary nodes a full layer (its queries and its
    keys), the exact pairs its window layers' bands keep and the pairs their
    blocks score, and nothing of a full layer among those two."""
    if mirror:
        monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", mirror)
    ids, label = seeded_tokens()
    mod = mx.mod.Module(tiny_sym_gen()(T)[0], context=mx.cpu())
    mod.bind(data_shapes=[("data", ids.shape)],
             label_shapes=[("softmax_label", label.shape)])
    mod.init_params(mx.init.Normal(0.1))
    mod.init_optimizer(optimizer="adam")
    before = tm.snapshot().get("executor", {})
    mod.forward_backward(mx.io.DataBatch(data=[mx.nd.array(ids)],
                                         label=[mx.nd.array(label)]))
    mod.update()
    after = tm.snapshot()["executor"]

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    heads, window = TINY["num_attention_heads"], TINY["sliding_window"]
    kept = sum(min(t + 1, window) for t in range(T))
    band = ra.scored_pairs(T, True, window,
                           ra.block_q_of(B, heads, T, window))
    full = ra.scored_pairs(T, True, 0, ra.block_q_of(B, heads, T, 0))
    assert delta("rotary_nodes") == 16 and delta("rotary_scaled_nodes") == 4
    assert delta("attention_layers") == 8
    assert delta("attention_window_layers") == 6
    assert delta("attention_band_kept_pairs") == 6 * B * heads * kept
    assert delta("attention_band_scored_pairs") == 6 * B * heads * band
    assert delta("attention_scored_pairs") == B * heads * (6 * band
                                                           + 2 * full)
    assert kept < band <= full      # one block holds this T: nothing skipped
    assert delta("attention_kernel_layers") == 0       # the CPU's blocks
    assert delta("moe_local_experts") == 8 * 4
    if mirror:
        assert delta("kept_residual_nodes") == 16  # 8 attention, 8 MoE


def test_the_band_counts_at_the_cells_tiles(monkeypatch):
    """What the cell's window and full layers count where the kernels
    engage (asked as for one v5e): the rule gives the band the narrowest key
    block, 256 x 128, and the full layer 128 x 512 (its 256 rows a tile do
    not fit VMEM beside 16 384 keys and values and their gradients); the
    band keeps 16 253 440 pairs a head and scores 20 316 160, 1.25 times as
    many."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import flash_attention as fa
    from mxnet_tpu.ops import pallas_support as ps
    from mxnet_tpu.ops import registry

    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: 128 << 20)
    op = registry.get("RingAttention")
    q = jax.ShapeDtypeStruct((1, 32, 16384, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 4, 16384, 128), jnp.bfloat16)
    counts = {}
    for window in (1024, 0):
        params = op.parse_params(dict(causal=True, window=window))
        counts[window] = op.launch_counts([q, kv, kv], [q], params, "tpu")
        plan = fa.plan("tpu", 128 << 20, "bfloat16", 32, 4, 16384, 128,
                       True, window)
        assert (plan.bq, plan.bk) == ((256, 128) if window else (128, 512))
    band, full = counts[1024], counts[0]
    assert band["executor.attention_kernel_layers"] == 1
    assert band["executor.attention_band_kept_pairs"] == 32 * 16253440
    assert band["executor.attention_band_scored_pairs"] == 32 * 20316160 \
        == band["executor.attention_scored_pairs"]
    assert full["executor.attention_scored_pairs"] == 32 * 138412032
    assert "executor.attention_band_kept_pairs" not in full
    assert "executor.attention_band_scored_pairs" not in full


# --- published widths ----------------------------------------------------------

def test_estimate_flops_and_the_parameter_count_at_published_widths():
    """The configuration's count is ``infer_shape``'s (340 350 208 as cut,
    12.15 B uncut from the builder's defaults), and
    ``models.recipe.estimate_flops`` counts a window layer's band and not
    half the square: it is the builder's count of the window layers'
    attention to the last multiply-add."""
    from mxnet_tpu.models import recipe

    with open(os.path.join(mc.ROOT, "benchmark", "configs",
                           NAME + ".json")) as f:
        cfg = json.load(f)
    builder = mc.load("configs", NAME)
    t = 16384

    def count(sym):
        arg_shapes, _, _ = sym.infer_shape(data=(1, t), softmax_label=(1, t))
        return sum(int(np.prod(s)) for n, s in zip(sym.list_arguments(),
                                                   arg_shapes)
                   if n not in ("data", "softmax_label"))

    sym = builder.sym_gen(cfg, mx)[0](t)[0]
    assert len(sym.list_arguments()) - 2 == 4 * 12 + 3
    assert count(sym) == cfg["parameters"] == 340350208
    layer = 21233920 + 64 * 2304 + 2 * 2304
    assert cfg["parameters"] == 4 * (layer + 8 * 3 * 2304 * 896) \
        + 2 * 12288 * 2304 + 2304
    uncut = count(models.mellum_sym_gen()(t)[0])
    assert uncut == 28 * (layer + 64 * 3 * 2304 * 896) \
        + 2 * 98304 * 2304 + 2304
    assert round(uncut / 1e9, 2) == 12.15
    macs = recipe.estimate_flops(sym, data=(1, t), softmax_label=(1, t)) / t
    # the estimator counts every assignment of the router's, the builder
    # the share that lands on the experts held here
    routed = 4 * (8 - 8 * 8 / 64) * 3 * 2304 * 896
    # and a full causal layer at half the square, the builder the triangle
    # with its diagonal: half a pair a token and head, twice (q.k and p.v)
    diagonal = 32 * 128
    assert macs - routed + diagonal == pytest.approx(
        builder.forward_macs_per_token(cfg), rel=1e-9)
    unbanded = builder.sym_gen(dict(cfg, sliding_window=None), mx)[0](t)[0]
    assert recipe.estimate_flops(unbanded, data=(1, t),
                                 softmax_label=(1, t)) / t > 1.4 * macs


# --- the shared block did not move ---------------------------------------------

# sha256[:12] of the fused train program of the Keye-VL-2.0 and SDAR test
# presets (``tests/test_keye_vl2.py`` / ``tests/test_sdar.py``: ``TINY``, 2 x
# 32 tokens, Adam), lowered for the CPU without debug info
# (``tools/lowered_hashes.py``), at the commit before ``qwen3_moe_block``
# took its layer's rotation and ``RotaryEmbedding`` a schedule (PR 61's tree,
# jax 0.9.0); keyed (model, trunk dtype, MXNET_BACKWARD_DO_MIRROR)
_BEFORE_THE_ROTATION_WAS_THE_CALLERS = {
    ("keye_vl2", "float32", ""): "30e88dcd78cd",
    ("keye_vl2", "float32", "1"): "86a3607315af",
    ("keye_vl2", "bfloat16", ""): "e52a5d90b3d7",
    ("keye_vl2", "bfloat16", "1"): "9fe032626145",
    ("sdar", "float32", ""): "029f94564d2e",
    ("sdar", "float32", "1"): "e4d60bfc3467",
    ("sdar", "bfloat16", ""): "3c2e91f622b1",
    ("sdar", "bfloat16", "1"): "53b4a0ebcc3f",
}


@pytest.mark.parametrize("model,dtype,mirror",
                         sorted(_BEFORE_THE_ROTATION_WAS_THE_CALLERS))
def test_the_keye_and_sdar_programs_lower_to_the_parents_text(
        monkeypatch, model, dtype, mirror):
    """ADAPTED, not split: the two cells that run the shared block pass it
    what they passed, and their train programs did not change by an
    instruction."""
    import jax
    import lowered_hashes

    if jax.__version__ != "0.9.0":
        pytest.skip("the digests are of jax 0.9.0's lowered text")
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", mirror or "0")
    name, tiny = {
        "keye_vl2": ("keye-vl-2.0-30b-a3b", "test_keye_vl2"),
        "sdar": ("sdar-30b-a3b", "test_sdar")}[model]
    preset = dict(__import__(tiny).TINY, compute_dtype=dtype)
    sym = mc.load("configs", name).sym_gen(preset, mx)[0](T)[0]
    ids, label = seeded_tokens()

    def drive():
        mod = mx.mod.Module(sym, context=mx.cpu())
        mod.bind(data_shapes=[("data", ids.shape)],
                 label_shapes=[("softmax_label", label.shape)])
        mod.init_params(mx.init.Normal(0.1))
        mod.init_optimizer(optimizer="adam")
        mod.forward_backward(mx.io.DataBatch(
            data=[mx.nd.array(ids)], label=[mx.nd.array(label)]))
        mod.update()

    seen = lowered_hashes.lowered_programs(drive, launches=1)
    assert [sha for counter, sha, _ in seen
            if counter == lowered_hashes.FUSED] == [
        _BEFORE_THE_ROTATION_WAS_THE_CALLERS[model, dtype, mirror]]


def test_the_readings_tool_rehearses_on_the_cpu(tmp_path):
    """``tools/mellum2_readings.py`` is where ``TOLERANCES`` come from: its
    two chip modes run end to end at ``--tiny``, through the driver's own
    check and the harness's own comparison, and a control that must fail
    does (the labels not shifted), beside one that must not (the sound
    program against its reference)."""
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def run(*argv):
        out = subprocess.run(
            [sys.executable, os.path.join(root, "tools",
                                          "mellum2_readings.py"), *argv,
             "--tiny", "--out", str(tmp_path)],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
            text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        with open(tmp_path / (argv[0] + ".json")) as f:
            return json.load(f)

    (sound,) = run("checks", "5")
    assert sound["correct"] and set(sound["compared"]) == {
        "loss_rel_err", "grad_norm_rel_err"}
    shifted, wider = run("controls", "5", "--only",
                         "labels_not_shifted,a_band_one_key_wider")
    assert shifted["control"] == "labels_not_shifted" \
        and not shifted["correct"]
    assert shifted["compared"]["loss_rel_err"][0] > 1e-3
    assert wider["compared"]["grad_norm_rel_err"][0] > 0.0
