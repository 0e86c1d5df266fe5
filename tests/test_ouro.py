"""Ouro's looped stack at a tiny size on the CPU (hidden 64, 4 heads of 16,
SwiGLU 96, 2 layers run R = 4 and R = 2 times, T 32, vocabulary 64, float32)
against the plain reference ``benchmark/reference/ouro-2.6b.py``, and the
loss layer ``ExitSoftmaxOutput`` alone against ``jax.grad`` of its closed
form.

Tolerances, and why: program and reference both compute in float32 and
differ by the order of their sums (blocks of queries, a weight's gradient
summed over its R uses), so a tensor agrees to ``F32_TENSOR_TOLERANCE``
(3e-4 of its largest element) and the first step's loss and gradient norm
to ``F32_TOLERANCES``. A bfloat16 trunk misses those by orders of magnitude.
``TOLERANCES`` are what the bfloat16 trunk is held to on the chip; a pass
left out, a stream fed on without its final norm, the entropy term dropped
or a last exit that reads its gate moves the gradient norm by more than they
allow, and so does the reference computed in float8.
"""

import functools
import json
import os

import model_cases as mc
import numpy as np
import pytest
from model_cases import misses, rel

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError

NAME = "ouro-2.6b"
TINY = dict(vocab_size=64, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4, head_dim=16,
            intermediate_size=96, total_ut_steps=4, exit_entropy_beta=0.1,
            rms_norm_eps=1e-6, rope_theta=1000000)
B, T = 4, 32


@pytest.fixture(scope="module")
def ref():
    return mc.load("reference", NAME)


def tiny(passes=4, **over):
    return dict(TINY, total_ut_steps=passes, **over)


def tiny_sym_gen(cfg, dtype="float32"):
    return mc.load("configs", NAME).sym_gen(
        dict(cfg, compute_dtype=dtype), mx)[0]


def scale_rule(name):
    """normal(0, 0.2) weights (at 64 features that is what makes every
    branch of the tiny model matter), gains normal(1, 0.1), and a gate of
    normal(0, 2) weights and a bias near -0.7: exits of unlike shares, and
    a gate through which enough of the gradient reaches the trunk that the
    entropy term shows in the gradient's norm (at normal(0, 0.5) dropping
    beta moves it by 1.3e-3, inside the bfloat16 limit)."""
    if name.startswith("early_exit_gate"):
        return (2.0, 0.0) if name.endswith("_weight") else (
            0.5, -0.7 if name.endswith("gate_bias") else 0.0)
    return mc.gains_and_weights(name, weight=0.2)


seeded_params = functools.partial(mc.seeded_params, rule=scale_rule)
seeded_tokens = functools.partial(mc.seeded_tokens, batch=B, seq_len=T,
                                  vocab=TINY["vocab_size"])


# --- the loss layer alone ----------------------------------------------------

def _closed_form(zs, ss, label, beta, ignore=0):
    """J of the issue, written out: products of (1 - lam), no logs of
    sigmoids."""
    import jax
    import jax.numpy as jnp

    lab = label.astype(jnp.int32)
    lams = [jax.nn.sigmoid(s[:, 0]) for s in ss]
    stay, shares = jnp.ones_like(lams[0]), []
    for lam in lams[:-1]:
        shares.append(lam * stay)
        stay = stay * (1.0 - lam)
    shares.append(stay)
    row = 0.0
    for p, z in zip(shares, zs):
        nll = -jnp.take_along_axis(jax.nn.log_softmax(z, -1), lab[:, None],
                                   1)[:, 0]
        row = row + p * nll + beta * p * jnp.log(p)
    return jnp.sum(jnp.where(lab != ignore, row, 0.0)), jnp.stack(shares, 1)


def _exit_op(passes, beta, rows=24, classes=16, seed=3, **over):
    rs = np.random.RandomState(seed)
    zs = [rs.randn(rows, classes).astype(np.float32) * 2.0
          for _ in range(passes)]
    ss = [rs.randn(rows, 1).astype(np.float32) for _ in range(passes)]
    label = rs.randint(0, classes, size=(rows,)).astype(np.float32)
    label[::5] = 0.0                      # pad rows
    names = [f"z{t}" for t in range(passes)] + [
        f"s{t}" for t in range(passes)] + ["label"]
    kw = dict(num_exits=passes, beta=beta, use_ignore=True, ignore_label=0)
    kw.update(over)
    sym = mx.sym.ExitSoftmaxOutput(*map(mx.sym.Variable, names), **kw)
    inputs = zs + ss + [label]
    exe = sym.bind(mx.cpu(), {n: mx.nd.array(a) for n, a in
                              zip(names, inputs)},
                   args_grad={n: mx.nd.zeros(a.shape) for n, a in
                              zip(names, inputs)})
    return exe, names, zs, ss, label


@pytest.mark.parametrize("passes", [2, 4])
@pytest.mark.parametrize("beta", [0.0, 0.1])
def test_exit_loss_gradients_are_those_of_the_closed_form(passes, beta):
    """``ExitSoftmaxOutput``: the output is the last exit's softmax, the
    head gradient is ignored, and the gradients of every exit's logits and
    every gate's score are ``jax.grad`` of J written out; pad rows train
    nothing, the shares of a row sum to 1 and the last gate gets none."""
    import jax
    import jax.numpy as jnp

    exe, names, zs, ss, label = _exit_op(passes, beta)
    out = exe.forward(is_train=True)[0].asnumpy()
    assert rel(out, jax.nn.softmax(jnp.asarray(zs[-1]), -1)) < 1e-6
    exe.backward(out_grads=[mx.nd.array(np.full(out.shape, 7.0, "f"))])
    (_, shares), (d_zs, d_ss) = jax.value_and_grad(
        lambda z, s: _closed_form(z, s, jnp.asarray(label), beta),
        argnums=(0, 1), has_aux=True)(
            [jnp.asarray(z) for z in zs], [jnp.asarray(s) for s in ss])
    np.testing.assert_allclose(np.asarray(shares).sum(1), 1.0, rtol=1e-6)
    for n, want in zip(names, d_zs + d_ss):
        got = exe.grad_dict[n].asnumpy()
        assert rel(got, want) < 1e-5, n
        assert not got[::5].any(), n      # the pad rows
    assert not exe.grad_dict[names[2 * passes - 1]].asnumpy().any()
    assert exe.grad_dict[names[passes]].asnumpy().any()
    assert not exe.grad_dict["label"].asnumpy().any()


def test_exit_loss_survives_a_saturated_gate():
    """Scores of +-60: a share of exactly 0 in float32, and no NaN (the
    shares are made in logs)."""
    exe, names, zs, ss, label = _exit_op(4, 0.1)
    for t, v in enumerate((60.0, -60.0, 60.0, 0.0)):
        exe.arg_dict[f"s{t}"][:] = v
    exe.forward(is_train=True)
    exe.backward()
    for n in names[:-1]:
        assert np.isfinite(exe.grad_dict[n].asnumpy()).all(), n


def test_exit_loss_refuses_what_it_does_not_define():
    v = mx.sym.Variable
    with pytest.raises(MXNetError, match="at least 2 exits"):
        mx.sym.ExitSoftmaxOutput(v("z"), v("s"), v("l"), num_exits=1) \
            .infer_shape(z=(8, 4))
    two = mx.sym.ExitSoftmaxOutput(v("z0"), v("z1"), v("s0"), v("s1"),
                                   v("l"), num_exits=2)
    assert two.infer_shape(z0=(8, 4))[0] == [(8, 4), (8, 4), (8, 1), (8, 1),
                                             (8,)]
    with pytest.raises(MXNetError, match="one class id a row"):
        two.infer_shape(z0=(8, 4), l=(8, 1))
    with pytest.raises(MXNetError, match=r"a gate is \(rows, 1\)"):
        two.infer_shape(z0=(8, 4), s0=(8,))
    with pytest.raises(MXNetError, match="of one shape"):
        two.infer_shape(z0=(8, 4), z1=(8, 5))
    # class ids do not fit a bfloat16 trunk: the label keeps float32
    args, outs, _ = two.infer_type(z0="bfloat16")
    assert [np.dtype(a).name for a in args] == ["bfloat16"] * 4 + ["float32"]
    assert np.dtype(outs[0]).name == "float32"


# --- the whole model ---------------------------------------------------------

class Step:
    """One forward/backward of the tiny model at ``passes``: every exit's
    logits, the last exit's probabilities and every parameter's gradient a
    row, from ONE executor over the model's head grouped with its
    internals."""

    def __init__(self, passes, dtype="float32"):
        self.cfg = tiny(passes)
        self.sym = tiny_sym_gen(self.cfg, dtype)(T)[0]
        self.ids, self.label = seeded_tokens()
        self.params = seeded_params(tiny_sym_gen(self.cfg)(T)[0],
                                    data=self.ids.shape,
                                    softmax_label=self.label.shape)
        inner = self.sym.get_internals()
        heads = [self.sym] + [inner[f"u{t}_pred_output"]
                              for t in range(1, passes + 1)]
        exe = mx.sym.Group(heads).simple_bind(
            mx.cpu(), data=self.ids.shape, softmax_label=self.label.shape)
        for n, a in self.params.items():
            exe.arg_dict[n][:] = a
        exe.arg_dict["data"][:] = self.ids
        exe.arg_dict["softmax_label"][:] = self.label
        outs = [o.asnumpy().astype(np.float32)
                for o in exe.forward(is_train=True)]
        exe.backward()
        self.prob, self.logits = outs[0], outs[1:]
        self.grads = {n: exe.grad_dict[n].asnumpy() / self.ids.size
                      for n in self.params}

    def first_step(self):
        """What the benchmark's driver reads: loss from the probabilities,
        gradient norm over rows."""
        return mc.reading(self.prob, self.grads, self.label)

    def leaves(self):
        return mc.reference_args(self.cfg, self.params, self.ids,
                                 self.label)[2:]

    @functools.cached_property
    def want(self):
        """The plain reference's reading of the same step, evaluated once."""
        return mc.load("reference", NAME).first_step(*mc.reference_args(
            self.cfg, self.params, self.ids, self.label))


@pytest.fixture(scope="module")
def steps():
    """{passes: Step}, each bound once a module."""
    made = {}

    def step(passes):
        if passes not in made:
            made[passes] = Step(passes)
        return made[passes]

    return step


@pytest.mark.parametrize("passes", [4, 2])
def test_every_exits_logits_and_every_gradient_match_the_reference(
        ref, steps, passes):
    import jax

    step = steps(passes)
    leaves, ids, label = step.leaves()
    want = ref.logits(jax, step.cfg, leaves, ids)
    assert len(want) == len(step.logits) == passes
    for t, (got, z) in enumerate(zip(step.logits, want), 1):
        assert rel(got, z) < ref.F32_TENSOR_TOLERANCE, t
    assert rel(step.prob, jax.nn.softmax(want[-1], -1)) \
        < ref.F32_TENSOR_TOLERANCE
    _, grads, shares = ref.value_and_grads(jax, step.cfg, leaves, ids, label)
    np.testing.assert_allclose(np.asarray(shares).sum(1), 1.0, rtol=1e-5)
    # exits of unlike shares, so that a share misplaced shows
    mean = np.asarray(shares).mean(0)
    assert mean.min() > 0.02 and mean.max() - mean.min() > 0.02
    assert set(grads) == set(step.grads)
    if passes == 4:
        # the reference's chain a piece at a time is autodiff of its whole
        # objective
        with jax.default_matmul_precision("highest"):
            whole = jax.jit(jax.grad(lambda p: ref.objective(
                step.cfg, p, ids, label)[0]))(leaves)
        for n in grads:
            assert rel(grads[n], whole[n]) < 1e-5, n
    shared = ("early_exit_gate_weight", "early_exit_gate_bias",
              "pred_weight", "final_norm_gamma", "l0_q_weight",
              "l1_mlp_down_weight", "l1_post_mlp_norm_gamma", "embed_weight")
    for n in shared + tuple(sorted(set(grads) - set(shared))):
        assert np.asarray(grads[n]).any(), n
        assert rel(step.grads[n], grads[n]) < ref.F32_TENSOR_TOLERANCE, n
    assert not misses(step.first_step(), step.want, ref.F32_TOLERANCES)


def _a_pass_left_out(ref, mp):
    mp.setattr(ref, "ut_steps", lambda cfg: cfg["total_ut_steps"] - 1)


def _final_norm_not_fed_on(ref, mp):
    mp.setattr(ref, "carried", lambda normed, raw: raw)


def _beta_dropped(ref, mp):
    mp.setattr(ref, "entropy_weight", lambda cfg: 0.0)


def _last_exit_reads_its_gate(ref, mp):
    mp.setattr(ref, "last_share", lambda lam_last, stay: lam_last * stay)


def _no_post_norms(ref, mp):
    mp.setattr(ref, "post_norm", lambda x, gain, eps: x)


def _float8(ref, mp):
    """The reference in the precision below the trunk's: float8_e4m3fn
    matmul inputs (the weights are cast by the test)."""
    import jax.numpy as jnp

    def f8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    plain = ref.project
    mp.setattr(ref, "project", lambda x, w: plain(f8(x), f8(w)))


@pytest.mark.parametrize("mutation,seen_by", [
    (_a_pass_left_out, "grad_norm"), (_final_norm_not_fed_on, "grad_norm"),
    (_beta_dropped, "the gate"), (_last_exit_reads_its_gate, "grad_norm"),
    (_no_post_norms, "grad_norm"), (_float8, "grad_norm")])
def test_tolerances_fail_a_wrong_layer_and_a_float8_reference(
        ref, steps, monkeypatch, mutation, seen_by):
    """Against a reference that leaves a piece out, or computes in
    float8_e4m3fn, the program misses even the bfloat16 trunk's TOLERANCES
    (the test above holds it inside the float32 ones against the plain
    reference). The entropy term reaches the parameters through the gate
    alone: dropping it moves the norm over every parameter by 2e-4 here and
    by less at published widths, so it is seen by the gate's own gradient,
    which is what ``tools/ouro_gate_check.py`` compares on the chip."""
    import jax
    import jax.numpy as jnp

    step = steps(4)
    mutation(ref, monkeypatch)
    ce, grads, _ = ref.value_and_grads(jax, step.cfg, *step.leaves())
    want = {"loss": float(ce), "grad_norm": float(jnp.sqrt(sum(
        jnp.sum(g ** 2) for g in grads.values())))}
    missed = misses(step.first_step(), want, ref.TOLERANCES)
    if seen_by == "grad_norm":
        assert "grad_norm" in missed, mutation.__name__
    else:
        assert not missed
        for n in ("early_exit_gate_weight", "early_exit_gate_bias"):
            assert rel(step.grads[n], grads[n]) \
                > ref.TOLERANCES["grad_norm"], n


def test_float32_tolerances_fail_a_bfloat16_trunk_and_counts_under_recompute(
        ref, steps, monkeypatch):
    """The bfloat16 trunk is outside the float32 tolerances. (That it is
    inside TOLERANCES is a statement about published widths, checked on the
    chip by the benchmark's driver.) Bound under
    ``MXNET_BACKWARD_DO_MIRROR=1``, the cell's switch: a launched train
    program counts its exits, their rows, the attention of every layer
    APPLICATION and the nodes that read a shared weight; every attention
    node and the loss keep the residuals they name."""
    from mxnet_tpu import telemetry as tm

    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    before = tm.snapshot().get("executor", {})
    low = Step(4, "bfloat16")
    after = tm.snapshot()["executor"]
    assert misses(low.first_step(), steps(4).want, ref.F32_TOLERANCES) \
        == ["loss", "grad_norm"]

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    layers = TINY["num_hidden_layers"]
    assert delta("exit_loss_heads") == 4
    assert delta("exit_loss_rows") == 4 * B * T
    assert delta("attention_layers") == 4 * layers
    assert delta("shared_weight_reads") == 4 * (11 * layers + 3)
    assert delta("stacked_wgrad") == 0
    assert delta("kept_residual_nodes") == 4 * layers + 1


def test_a_shared_weights_gradient_is_summed_in_float32():
    """A weight that four ``FullyConnected`` nodes of a bfloat16 trunk read
    (float32 master, cast where it is used): each use's gradient is its
    matmul's bfloat16 result, comes back through its cast's transpose as
    float32, and the four are added there. Uses whose gradients are c,
    c/512, c/512, c/512 with c in [4, 8), each exact in bfloat16 (and of
    unlike row counts, so that ``_shared_fc_plan`` stacks none of them): the
    float32 sum is the float32 reference's to the last bit, where a sum
    TAKEN in bfloat16 drops every small term (half an ulp of c is 1/64 >
    c/512) and is off by 3/512, over the bfloat16 tolerance of 2^-8."""
    w = mx.sym.Variable("w_weight")
    outs = [mx.sym.sum(mx.sym.FullyConnected(
        mx.sym.Cast(mx.sym.Variable(f"d{i}"), dtype="bfloat16"), w,
        num_hidden=8, no_bias=True, name=f"use{i}")) for i in range(4)]
    loss = mx.sym.MakeLoss(mx.sym.Cast(outs[0] + outs[1] + outs[2] + outs[3],
                                       dtype="float32"))
    rows = {f"d{i}": (4 + i, 16) for i in range(4)}
    exe = loss.simple_bind(mx.cpu(), **rows)
    assert exe.arg_dict["w_weight"].dtype == np.float32
    assert exe._shared_fc_plan()[2] == 0
    # 1 + k/64 is exact in bfloat16, and so is every column's sum,
    # c_j = 5.5 + j/16, and c_j / 512
    first = 1.0 + np.arange(64, dtype=np.float32).reshape(4, 16) / 64.0
    exe.arg_dict["d0"][:] = first
    for i in (1, 2, 3):
        small = np.zeros(rows[f"d{i}"], np.float32)
        small[i] = first.sum(0) / 512.0
        exe.arg_dict[f"d{i}"][:] = small
    exe.arg_dict["w_weight"][:] = 0.5
    exe.forward(is_train=True)
    exe.backward()
    got = exe.grad_dict["w_weight"].asnumpy()
    assert got.dtype == np.float32
    first_use = np.tile(first.sum(0), (8, 1))
    exact = first_use * np.float32(1.0 + 3.0 / 512.0)
    assert rel(first_use, exact) > 2.0 ** -8    # what bfloat16 would give
    assert rel(got, exact) < 1e-6


def test_three_adam_steps_through_fit_follow_the_reference(ref):
    """BucketingModule.fit with optimizer='adam' on three batches: the last
    exit's cross-entropy before each step is the reference's, and every
    parameter moves, the gate's too."""
    import jax
    import jax.numpy as jnp

    cfg = tiny(4)
    gen = tiny_sym_gen(cfg)
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


def test_checkpoint_round_trip_saves_each_shared_weight_once(tmp_path):
    gen = tiny_sym_gen(tiny(4))
    mod = mx.mod.Module(gen(T)[0], context=mx.cpu())
    mod.bind(data_shapes=[("data", (B, T))],
             label_shapes=[("softmax_label", (B, T))])
    mod.init_params(mx.init.Normal(0.1))
    prefix = str(tmp_path / "ouro")
    mod.save_checkpoint(prefix, 1)
    sym, args, aux = mx.model.load_checkpoint(prefix, 1)
    assert sym.list_arguments() == gen(T)[0].list_arguments()
    layers = TINY["num_hidden_layers"]
    assert len(args) == 11 * layers + 5 and not aux
    assert len(set(args)) == len(args)
    assert sum(n.startswith("u") for n in args) == 0   # no copy a pass
    now = mod.get_params()[0]
    for n, a in args.items():
        assert np.array_equal(a.asnumpy(), now[n].asnumpy()), n


@pytest.mark.parametrize("layers,count", [(4, 406884353), (6, 509661185)])
def test_estimate_flops_and_the_parameter_count_at_published_widths(
        layers, count):
    """By shapes alone: the count is ``infer_shape``'s (layers + embedding
    and head + gate + norm gains), and ``models.recipe.estimate_flops``
    counts a shared weight's nodes a node: R applications of every layer
    and R heads, which is the builder's count to the last multiply-add but
    for the gate's 2048 a row and exit and the causal diagonal."""
    from mxnet_tpu.models import recipe

    with open(os.path.join(mc.ROOT, "benchmark", "configs",
                           NAME + ".json")) as f:
        cfg = json.load(f)
    if layers == cfg["num_hidden_layers"]:
        assert cfg["parameters"] == count
    cfg = dict(cfg, num_hidden_layers=layers)
    builder = mc.load("configs", NAME)
    t = 4096
    sym = builder.sym_gen(cfg, mx)[0](t)[0]
    assert len(sym.list_arguments()) - 2 == 11 * layers + 5
    arg_shapes, _, _ = sym.infer_shape(data=(1, t), softmax_label=(1, t))
    sizes = {n: int(np.prod(s)) for n, s in zip(sym.list_arguments(),
                                                arg_shapes)
             if n not in ("data", "softmax_label")}
    assert sum(sizes.values()) == count
    assert sum(v for n, v in sizes.items() if n.endswith("_weight")
               and n[0] == "l") == layers * 51380224
    assert sizes["embed_weight"] + sizes["pred_weight"] == 201326592
    assert sizes["early_exit_gate_weight"] + sizes[
        "early_exit_gate_bias"] == 2049
    assert sum(v for n, v in sizes.items() if n.endswith("_gamma")) \
        == (4 * layers + 1) * 2048
    macs = recipe.estimate_flops(sym, data=(1, t), softmax_label=(1, t)) / t
    once = recipe.estimate_flops(
        builder.sym_gen(dict(cfg, total_ut_steps=2), mx)[0](t)[0],
        data=(1, t), softmax_label=(1, t)) / t
    assert macs == 2 * once               # R x (layers + head + gate)
    want = builder.forward_macs_per_token(cfg)
    # the estimator: the gate too, and T / 2 keys a query where the
    # builder counts (T + 1) / 2
    assert macs == want + 4 * 2048 - 4 * layers * 2048
    assert builder.train_flops_per_unit(cfg) == 6 * want
