"""``MoE``'s routing bookkeeping as dense selects (``_select_scores``, the
counts, the held round's own window of weights) against a test-local copy of
the forms it had: ``take_along_axis``, ``bincount`` and a gather of all
N * k weights. Same bits, forward and backward, and no gather or scatter of
N * k indices left in a lowered train step. And the held range's rounds
against a copy of the loop they were: one static round traces no loop, the
further rounds of the others sit under a branch that the first round's
wgrads cross in the dtype they were made in. And the held range's rounds
once more with their two row sums in the row sum kernel
(``ops/row_sum_kernels.py``, Pallas's interpreter) against the scatter-adds
they hold on the CPU. And a held round whose grouped matmuls run their
kernels (``ops/grouped_matmul.py``, the interpreter), which own the round's
dead rows: no row select is traced around a matmul, and the bits are those
of the same kernels masked on both sides, the form ``ragged_dot`` keeps."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import executor as ex
from mxnet_tpu.ops import defs_transformer as dt
from mxnet_tpu.ops import registry
from mxnet_tpu.ops import row_sum_kernels as rsk
from mxnet_tpu.ops.registry import keep

SWITCH = "MXNET_BACKWARD_DO_MIRROR"
E, H, F, N = 16, 32, 8, 40


# --- the forms before: scalars moved one index at a time -------------------------

def _router_before(logits, bias, params):
    k = params["top_k"]
    n, e = logits.shape
    if params["score_func"] == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        biased = scores if bias is None else scores + jax.lax.stop_gradient(
            bias.astype(jnp.float32))
        expert = keep(jax.lax.top_k(biased, k)[1].reshape(-1))
        counts = keep(jnp.bincount(expert, length=e).astype(jnp.int32))
    else:
        expert = keep(jax.lax.top_k(logits, k)[1].reshape(-1))
        counts = keep(jnp.bincount(expert, length=e).astype(jnp.int32))
        logits = dt._attach_router_losses(
            logits, counts.astype(jnp.float32) / n,
            params["lb_coef"], params["z_coef"])
        scores = jax.nn.softmax(logits, axis=-1)
    p = keep(jnp.take_along_axis(scores, expert.reshape(n, k), axis=1))
    if params["route_norm"]:
        p = p / (jnp.sum(p, axis=-1, keepdims=True) + 1e-20)
    if params["route_scale"] != 1.0:
        p = p * params["route_scale"]
    return expert, p, counts


def _held_round_before(first, rows, x, tok, weight, counts, w_gate, w_up,
                       w_down):
    ends = jnp.cumsum(counts)
    here = (jnp.clip(ends, first, first + rows)
            - jnp.clip(ends - counts, first, first + rows)).astype(jnp.int32)
    live = (jnp.arange(rows) < jnp.sum(here))[:, None]
    tok = jax.lax.dynamic_slice_in_dim(tok, first, rows)
    weight = jax.lax.dynamic_slice_in_dim(weight, first, rows)
    matmul = dt._expert_matmul(here, x.dtype, rows, (w_gate, w_up, w_down))[0]

    def live_matmul(r, w):
        return jnp.where(live, matmul(jnp.where(live, r, 0), w), 0)

    r = keep(x[tok])
    gate, up = keep((live_matmul(r, w_gate), live_matmul(r, w_up)))
    y = keep(live_matmul(keep(jax.nn.silu(gate) * up), w_down))
    return jnp.zeros(x.shape, jnp.float32).at[tok].add(
        y.astype(jnp.float32) * weight[:, None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rounds_before(rows, x, weight, w_gate, w_up, w_down, tok, counts):
    return _rounds_before_fwd(rows, x, weight, w_gate, w_up, w_down, tok,
                              counts)[0]


def _round_before_of(first, rows, tok, counts):
    return lambda x, weight, *w: _held_round_before(
        first, rows, x, tok, weight, counts, *w)


def _rounds_before_fwd(rows, x, weight, w_gate, w_up, w_down, tok, counts):
    wrt = (x, weight, w_gate, w_up, w_down)
    out, vjp = jax.vjp(_round_before_of(0, rows, tok, counts), *wrt)
    rounds = (jnp.sum(counts) + rows - 1) // rows
    out = jax.lax.fori_loop(
        1, rounds, lambda r, acc: acc + _round_before_of(
            r * rows, rows, tok, counts)(*wrt), out)
    return out, (vjp, wrt, tok, counts, rounds)


def _rounds_before_bwd(rows, res, g):
    vjp, wrt, tok, counts, rounds = res

    def more(r, cts):
        back = jax.vjp(_round_before_of(r * rows, rows, tok, counts),
                       *wrt)[1]
        return jax.tree.map(jnp.add, cts, back(g))

    return jax.lax.fori_loop(1, rounds, more, vjp(g)) + (None, None)


_rounds_before.defvjp(_rounds_before_fwd, _rounds_before_bwd)


def _held_rounds_before(rows, platform, x, flat, w_gate, w_up, w_down, order,
                        counts, runs=None):
    """Today's call of ``_held_rounds`` answered as ``_moe`` answered it
    before: every assignment's weight gathered into the sorted order, the
    padding zeros of ``tok`` and ``weight``. One static round is answered
    with no loop, as ``_held_rounds`` answers it: XLA:CPU adds the rows'
    two cotangents (the router's product, the round's scatter) in a fused
    product's accumulator on one side of a loop's edge and not on the
    other, an ulp apart, and the loop against the round alone is held
    without ``jax.jit`` below."""
    nk = flat.shape[0]
    pad = order.shape[0] - nk
    order = order[:nk]
    tok = jnp.pad(order // (nk // x.shape[0]), (0, pad))
    weight = keep(jnp.pad(flat[order], (0, pad)))
    if nk == rows:
        return _held_round_before(0, rows, x, tok, weight, counts, w_gate,
                                  w_up, w_down)
    return _rounds_before(rows, x, weight, w_gate, w_up, w_down, tok, counts)


def _as_before(steer):
    steer.setattr(dt, "_router", _router_before)
    steer.setattr(dt, "_held_rounds", _held_rounds_before)


# --- the same bits ---------------------------------------------------------------

SCORES = {
    "softmax": dict(score_func="softmax"),
    "softmax-norm-losses": dict(score_func="softmax", route_norm=True,
                                lb_coef=0.01, z_coef=0.001),
    "sigmoid": dict(score_func="sigmoid"),
    "sigmoid-bias-norm-scale": dict(score_func="sigmoid", route_norm=True,
                                    route_scale=2.5, expert_bias=True),
}
# name: (top_k, experts held, the first of them, routing collapsed onto them)
LAYOUTS = {
    "all-held": (3, E, 0, False),
    # 120 assignments, rounds of 64: two rounds' worth, 8 padded rows
    "held-range-padded-tail": (3, 4, 8, False),
    # 80 assignments, twice the balanced share of 8 of 16 experts: one round
    # of all of them, no padding, the tail all dead rows
    "held-range-whole-rounds": (2, 8, 0, False),
    # most tokens' three experts are held here: over 64 live rows, two rounds
    "collapsed-two-rounds": (3, 4, 8, True),
}


def _inputs(graph, held, first, bias, collapsed):
    rs = np.random.RandomState(4)
    x = rs.randn(N, H).astype(np.float32)
    router = (rs.randn(E, H) * 0.3).astype(np.float32)
    if collapsed:  # one feature every token has, the held experts read
        x[:, 0] = 3.0
        router[first:first + held, 0] += 2.0
    ws = [(rs.randn(*s) * 0.3).astype(np.float32)[first:first + held]
          for s in ((E, H, F), (E, H, F), (E, F, H))]
    extra = [(rs.randn(E) * 0.2).astype(np.float32)] if bias else []
    return [x, x @ router.T if graph else router] + ws + extra


def _run(steer, switch, graph, layout, score):
    top_k, held, first, collapsed = layout
    names = ["x", "r", "g", "u", "o"] + ["b"] * score.get("expert_bias",
                                                           False)
    steer.setenv(SWITCH, switch)
    sym = mx.sym.MoE(
        *map(mx.sym.Variable, names), name="moe", num_experts=E,
        num_hidden=F, top_k=top_k, num_local_experts=0 if held == E else held,
        expert_offset=first, router="graph" if graph else "weight", **score)
    inputs = _inputs(graph, held, first, len(names) > 5, collapsed)
    exe = sym.bind(mx.cpu(), dict(zip(names, map(mx.nd.array, inputs))),
                   args_grad={n: mx.nd.zeros(a.shape)
                              for n, a in zip(names, inputs)})
    out = exe.forward(is_train=True)[0].asnumpy()
    head = np.random.RandomState(6).randn(N, H).astype(np.float32)
    exe.backward(out_grads=[mx.nd.array(head)])
    if collapsed:  # more rows than one round's 64: the loop ran
        logits = inputs[1] if graph else inputs[0] @ inputs[1].T
        if len(names) > 5:
            logits = 1 / (1 + np.exp(-logits)) + inputs[5]
        chosen = np.argsort(-logits, axis=1)[:, :top_k]
        assert np.sum((chosen >= first) & (chosen < first + held)) > 64
        assert np.abs(exe.grad_dict["g"].asnumpy()[-1]).max() > 0
    return [out] + [exe.grad_dict[n].asnumpy() for n in names[:5]]


CASES = (
    [(s, l, False, "0") for s in SCORES for l in LAYOUTS]
    + [(s, l, graph, switch) for s in ("softmax-norm-losses",
                                       "sigmoid-bias-norm-scale")
       for l in LAYOUTS for graph, switch in ((True, "0"), (False, "1"))]
    + [("softmax-norm-losses", l, True, "1")
       for l in ("all-held", "collapsed-two-rounds")])


@pytest.mark.parametrize(
    "score,layout,graph,switch", CASES,
    ids=["-".join([s, l, "graph" if g else "weight", "mirror" + m])
         for s, l, g, m in CASES])
def test_dense_selects_give_the_bits_of_the_gathers(monkeypatch, score,
                                                    layout, graph, switch):
    """The output and the gradients of the rows, of the router's weight (or
    of the logits a graph computed) and of the three expert weights."""
    with monkeypatch.context() as steer:
        now = _run(steer, switch, graph, LAYOUTS[layout], SCORES[score])
    with monkeypatch.context() as steer:
        _as_before(steer)
        before = _run(steer, switch, graph, LAYOUTS[layout], SCORES[score])
    for name, a, b in zip(["out", "x", "r", "g", "u", "o"], now, before):
        assert np.abs(b).max() > 0, name
        np.testing.assert_array_equal(a, b, name)


@pytest.mark.parametrize("n,e,k", [(40, 16, 3), (64, 128, 8), (8, 16, 1)])
def test_select_scores_is_take_along_axis(n, e, k):
    rs = np.random.RandomState(n)
    scores = jax.nn.softmax(jnp.asarray(rs.randn(n, e), jnp.float32), -1)
    expert = jax.lax.top_k(scores, k)[1]
    g = jnp.asarray(rs.randn(n, k), jnp.float32)
    want, back = jax.vjp(
        lambda s: jnp.take_along_axis(s, expert, axis=1), scores)
    got, mine = jax.vjp(lambda s: dt._select_scores(s, expert, e), scores)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(mine(g)[0], back(g)[0])
    counts = jnp.sum(dt._chosen(expert, e), axis=(0, 1), dtype=jnp.int32)
    np.testing.assert_array_equal(
        counts, jnp.bincount(expert.reshape(-1), length=e))


# --- the structure: no gather or scatter of N * k indices -------------------------

_TENSOR = r"tensor<((?:\d+x)*)[a-z]\w*>"
_GATHER = re.compile(
    r'"stablehlo\.gather"\(.*?: \(' + _TENSOR + ", " + _TENSOR + r"\) -> "
    + _TENSOR)
_SCATTER = re.compile(
    r'"stablehlo\.scatter"\(.*?\}\) : \(' + _TENSOR + ", " + _TENSOR + ", "
    + _TENSOR + r"\) -> ", re.S)


def _dims(text):
    return [int(d) for d in text.split("x") if d]


def _indexed(lowered):
    """[(kind, index count, elements moved an index)] of every gather and
    scatter of a StableHLO text. jax hands an indexing ``x[i]`` indices of
    shape (count, 1)."""
    found = []
    for kind, pattern in (("gather", _GATHER), ("scatter", _SCATTER)):
        for m in pattern.finditer(lowered):
            index = _dims(m.group(2))
            count = int(np.prod(index[:-1])) if len(index) > 1 else int(
                np.prod(index))
            moved = int(np.prod(_dims(m.group(3))))
            found.append((kind, count, moved // max(count, 1)))
    return found


def _train_step_text(held, before, monkeypatch):
    """The lowered fused train step of ``MoE`` under a squared loss,
    (2, 20, H) rows: N = 40, top-3, 120 assignments."""
    with monkeypatch.context() as steer:
        if before:
            _as_before(steer)
        data = mx.sym.Variable("data")
        moe = mx.sym.MoE(data, name="moe", num_experts=E, num_hidden=F,
                         top_k=3, num_local_experts=held,
                         expert_offset=8 if held else 0, lb_coef=0.01,
                         route_norm=True)
        net = mx.sym.LinearRegressionOutput(moe, name="loss")
        rs = np.random.RandomState(0)
        it = mx.io.NDArrayIter(rs.randn(2, 20, H).astype("float32"),
                               rs.randn(2, 20, H).astype("float32"),
                               batch_size=2, label_name="loss_label")
        mod = mx.mod.Module(net, context=mx.cpu(),
                            label_names=("loss_label",))
        mod.fit(it, num_epoch=1, optimizer="sgd", eval_metric="mse",
                optimizer_params={"learning_rate": 0.1})
        return ex.fused_window_hlo()["lowered"]


@pytest.mark.parametrize("held", [0, 4], ids=["all-held", "held-range"])
def test_train_step_moves_no_scalar_an_assignment(monkeypatch, held):
    """With every expert held the rows themselves are permuted, N * k of
    them, each H wide; nothing moves N * k scalars. A held range moves
    nothing N * k times: its round is 64 rows. The forms before did both,
    which is what shows that this test can see them."""
    nk = 120
    now = _indexed(_train_step_text(held, False, monkeypatch))
    assert now, "no gather in the step: the text is not read"
    scalars = [f for f in now if f[1] == nk and f[2] == 1]
    assert not scalars, scalars
    if held:
        assert not [f for f in now if f[1] == nk], now
        assert ("gather", 64, 1) in now     # the round's window of weights
    before = _indexed(_train_step_text(held, True, monkeypatch))
    old = {f[0] for f in before if f[1] == nk and f[2] == 1}
    assert old == {"gather", "scatter"}, before


# --- the held range's rounds: nothing parameter-sized in a loop that does not run --

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_rounds_looped(rows, platform, x, weight, w_gate, w_up, w_down,
                        order, counts, runs):
    """``_held_rounds`` as it was: a loop over the further rounds whatever
    their static count, forward and backward, the backward's started by the
    first round's cotangents in the weights' dtype."""
    return _held_rounds_looped_fwd(rows, platform, x, weight, w_gate, w_up,
                                   w_down, order, counts, runs)[0]


def _held_rounds_looped_fwd(rows, platform, x, weight, w_gate, w_up, w_down,
                            order, counts, runs):
    assert runs is None   # the CPU: no row sum kernel
    wrt = (x, weight, w_gate, w_up, w_down)
    out, vjp = jax.vjp(dt._round_of(0, rows, platform, order, counts), *wrt)
    rounds = (jnp.sum(counts) + rows - 1) // rows
    out = jax.lax.fori_loop(
        1, rounds, lambda r, acc: acc + dt._round_of(
            r * rows, rows, platform, order, counts)(*wrt), out)
    return out, (vjp, wrt, order, counts, rounds)


def _held_rounds_looped_bwd(rows, platform, res, g):
    vjp, wrt, order, counts, rounds = res

    def more(r, cts):
        back = jax.vjp(dt._round_of(r * rows, rows, platform, order, counts),
                       *wrt)[1]
        return jax.tree.map(jnp.add, cts, back(g))

    return jax.lax.fori_loop(1, rounds, more, vjp(g)) + (None, None, None)


_held_rounds_looped.defvjp(_held_rounds_looped_fwd, _held_rounds_looped_bwd)

# name: (layout, static rounds, live rounds)
ROUNDS = {
    "one-static-round": ("held-range-whole-rounds", 1, 1),
    "two-static-rounds": ("held-range-padded-tail", 2, 1),
    "two-live-rounds": ("collapsed-two-rounds", 2, 2),
}
MOE_INPUTS = ["out", "x", "r", "g", "u", "o"]


def _layer(steer, rounds, rows_dtype, looped):
    """(the scalar function of a layer's five inputs, the inputs): rows of
    ``rows_dtype`` over float32 masters, ``MoE`` called as the executor calls
    it; ``looped``: with the loop as it was."""
    top_k, held, first, collapsed = LAYOUTS[ROUNDS[rounds][0]]
    if looped:
        steer.setattr(dt, "_held_rounds", _held_rounds_looped)
    params = registry.get("MoE").parse_params(dict(
        num_experts=E, num_hidden=F, top_k=top_k, num_local_experts=held,
        expert_offset=first, route_norm=True, lb_coef=0.01))
    ins = [jnp.asarray(a) for a in _inputs(False, held, first, False,
                                           collapsed)]
    ins[0] = ins[0].astype(rows_dtype)
    nk, m = N * top_k, dt.held_round_rows(N * top_k, held, E)
    assert -(-nk // m) == ROUNDS[rounds][1]
    routed = dt._router(dt._router_logits(ins[0], ins[1]), None, params)[2]
    assert -(-int(jnp.sum(routed[first:first + held])) // m) \
        == ROUNDS[rounds][2]
    head = jnp.asarray(np.random.RandomState(6).randn(N, H), jnp.float32)

    def scalar(*ins):
        out = dt._moe(list(ins), params, registry.OpMode())
        return jnp.sum(out.astype(jnp.float32) * head), out

    return scalar, ins


@pytest.mark.parametrize("rows_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rounds", list(ROUNDS))
def test_rounds_give_the_bits_of_the_loop(monkeypatch, rounds, rows_dtype):
    """Output and all five gradients. One live round: the loop's bits, at one
    static round and at several. Two live rounds: the loop's bits too, but
    for the three expert weights under bfloat16 rows, whose float32 sum over
    the rounds is rounded once to the dtype a round's wgrad is made in, as a
    kernel rounds the sum over one round's rows."""
    sides = []
    for looped in (False, True):
        with monkeypatch.context() as steer:
            scalar, ins = _layer(steer, rounds, rows_dtype, looped)
            with jax.disable_jit():
                grads, out = jax.grad(
                    scalar, argnums=tuple(range(5)), has_aux=True)(*ins)
            sides.append([np.asarray(a, np.float32)
                          for a in [out] + list(grads)])
    rounded = rounds == "two-live-rounds" and rows_dtype == "bfloat16"
    for name, a, b in zip(MOE_INPUTS, *sides):
        assert np.abs(b).max() > 0, name
        if rounded and name in "guo":
            assert not np.array_equal(a, b), name   # the test sees the sum
            np.testing.assert_allclose(a, b, rtol=2.0 ** -8, atol=0,
                                       err_msg=name)
        else:
            np.testing.assert_array_equal(a, b, name)


def _grad_text(steer, rounds, rows_dtype, looped):
    scalar, ins = _layer(steer, rounds, rows_dtype, looped)
    return jax.jit(jax.value_and_grad(
        lambda *ins: scalar(*ins)[0], argnums=tuple(range(5)))).lower(
            *ins).as_text()


def _closes(text, at):
    """Index past the parenthesis that closes the one opening at ``at``."""
    depth = 0
    for i in range(at, len(text)):
        depth += (text[i] == "(") - (text[i] == ")")
        if not depth:
            return i + 1
    raise AssertionError("unbalanced")


def _loops_and_branches(text):
    """([operand types of every ``stablehlo.while`` outside a branch],
    [result types of every ``stablehlo.case``]) of a lowered text."""
    branches, results = [], []
    for m in re.finditer(r'"stablehlo\.(?:case|if)"\(', text):
        index = _closes(text, m.end() - 1)        # (index) ({regions})
        end = _closes(text, text.index("(", index))
        branches.append((m.start(), end))
        results.append(re.findall(
            r"tensor<[^>]*>", text[end:text.index("\n", end)].split("->")[1]))
    loops = []
    for m in re.finditer(r"stablehlo\.while\(", text):
        if not any(a < m.start() < b for a, b in branches):
            end = _closes(text, m.end() - 1)
            loops.append(re.findall(r"tensor<[^>]*>",
                                    text[end:text.index("\n", end)]))
    return loops, results


def test_one_static_round_traces_no_loop(monkeypatch):
    with monkeypatch.context() as steer:
        now = _grad_text(steer, "one-static-round", "bfloat16", False)
    assert "stablehlo.while" not in now and "stablehlo.case" not in now
    with monkeypatch.context() as steer:
        before = _grad_text(steer, "one-static-round", "bfloat16", True)
    assert before.count("stablehlo.while") == 2     # what this test sees


@pytest.mark.parametrize("rows_dtype,crossing", [("bfloat16", "bf16"),
                                                 ("float32", "f32")])
def test_wgrads_cross_the_branch_in_the_rows_dtype(monkeypatch, rows_dtype,
                                                   crossing):
    """Several static rounds: the loops over the further ones sit under
    branches, no loop outside one takes an operand of an expert weight's
    size, and the backward's branch gives the three expert weights'
    cotangents in the rows' dtype, never a float32 widening of bfloat16
    wgrads."""
    held = LAYOUTS[ROUNDS["two-static-rounds"][0]][1]
    weights = [f"tensor<{held}x{H}x{F}x", f"tensor<{held}x{F}x{H}x"]

    def parameter_sized(types):
        return [t for t in types if t.startswith(tuple(weights))]

    with monkeypatch.context() as steer:
        loops, results = _loops_and_branches(
            _grad_text(steer, "two-static-rounds", rows_dtype, False))
    assert not [t for loop in loops for t in parameter_sized(loop)], loops
    backward = [r for r in results if parameter_sized(r)]
    assert len(results) == 2 and len(backward) == 1
    assert sorted(parameter_sized(backward[0])) == sorted(
        [weights[0] + crossing + ">"] * 2 + [weights[1] + crossing + ">"])
    with monkeypatch.context() as steer:
        loops, results = _loops_and_branches(
            _grad_text(steer, "two-static-rounds", rows_dtype, True))
    assert not results and len(loops) == 2          # what this test sees
    assert all(any(t.endswith("xf32>") for t in parameter_sized(loop))
               for loop in loops), loops


@pytest.mark.parametrize("held,top_k,one_round", [
    (0, 3, 1),     # every expert held: one pass over all the rows
    (8, 2, 1),     # 8 of 16 at twice the balanced share: every assignment
    (4, 3, 0),     # 4 of 16: rounds of 64 of the 120 assignments
], ids=["all-held", "one-static-round", "two-static-rounds"])
def test_launch_counts_say_which_layers_trace_no_loop(held, top_k,
                                                      one_round):
    op = registry.get("MoE")
    params = op.parse_params(dict(num_experts=E, num_hidden=F, top_k=top_k,
                                  num_local_experts=held))
    local = held or E
    ins = [jax.ShapeDtypeStruct(s, jnp.float32)
           for s in ((N, H), (E, H), (local, H, F), (local, H, F),
                     (local, F, H))]
    counts = op.launch_counts(ins, [ins[0]], params, "cpu")
    assert counts["executor.moe_one_round_layers"] == one_round
    assert counts["executor.moe_layers"] == 1


# --- the held range's rounds through the row sum kernel ----------------------

def _wide_layer(steer, layout, kernel, width=F, masked=None):
    """``_layer`` at whole blocks of tokens and whole registers of hidden
    (256 x 128 over 16 experts: the kernel's blocks), bfloat16 rows;
    ``kernel``: the two row sums of every round in the kernel; ``width``:
    of an expert; ``masked`` not None: the grouped matmuls in their kernels
    too (``_on_the_kernels``, which wants ``width`` whole registers)."""
    top_k, held, first, collapsed = LAYOUTS[layout]
    n, h = 256, 128
    if masked is not None:
        _on_the_kernels(steer, masked)
    rs = np.random.RandomState(4)
    x = rs.randn(n, h).astype(np.float32)
    router = (rs.randn(E, h) * 0.3).astype(np.float32)
    if collapsed:
        x[:, 0] = 3.0
        router[first:first + held, 0] += 2.0
    ws = [(rs.randn(*s) * 0.3).astype(np.float32)[first:first + held]
          for s in ((E, h, width), (E, h, width), (E, width, h))]
    ins = [jnp.asarray(x, jnp.bfloat16)] + [jnp.asarray(a)
                                            for a in [router] + ws]
    params = registry.get("MoE").parse_params(dict(
        num_experts=E, num_hidden=width, top_k=top_k, num_local_experts=held,
        expert_offset=first, route_norm=True, lb_coef=0.01))
    calls = []
    if kernel:
        sum_rows = rsk.sum_rows

        def shipped(platform, dtype, rows, tokens, hidden, weights, k,
                    vmem=None):
            """The blocks the rule ships, as on a v5e; the layer and each
            of its rounds ask with the layer's own sizes (a slot sized for
            fewer rows a token than ``top_k`` overflows under a collapsed
            router: on the chip a halt)."""
            assert (rows, tokens, hidden, k) == (
                dt.held_round_rows(n * top_k, held, E), n, h, top_k)
            return rsk.kernel_plan("tpu", 128 << 20, dtype, rows, tokens,
                                   hidden, weights[0].shape[0], k)

        steer.setattr(dt, "_row_sum_plan", shipped)
        steer.setattr(rsk, "sum_rows", lambda *a: calls.append(a[2] is None)
                      or sum_rows(*a, interpret=True))
    head = jnp.asarray(np.random.RandomState(6).randn(n, h), jnp.float32)

    def scalar(*ins):
        out = dt._moe(list(ins), params, registry.OpMode())
        return jnp.sum(out.astype(jnp.float32) * head), out

    grads, out = jax.jit(jax.grad(scalar, argnums=tuple(range(5)),
                                  has_aux=True))(*ins)
    rounds = -(-n * top_k // dt.held_round_rows(n * top_k, held, E))
    return [np.asarray(a, np.float32) for a in [out] + list(grads)], (
        calls, rounds)


@pytest.mark.parametrize("layout", [l for l in LAYOUTS if l != "all-held"])
def test_held_rounds_through_the_row_sum_kernel(monkeypatch, layout):
    """Output and all five gradients of the held-range layouts with every
    round's combine and the backward of its dispatch in the kernel, against
    the scatter-adds: the output's float32 sums differ by the order of their
    additions, the rows' gradient is the float32 sum rounded once where the
    scatter-add rounds after every row, and the router's and the three
    expert weights' gradients read the same rows and cotangents."""
    sides = []
    for kernel in (True, False):
        with monkeypatch.context() as steer:
            grads, (calls, rounds) = _wide_layer(steer, layout, kernel)
            sides.append(grads)
            if kernel:  # a combine and a dispatch's backward a traced round
                assert sorted(set(calls)) == [False, True]
                assert len(calls) >= 2 * (1 + (rounds > 1))
    for name, a, b in zip(MOE_INPUTS, *sides):
        assert np.abs(b).max() > 0, name
        tol = 2.0 ** -7 if name in ("out", "x") else 2.0 ** -20
        np.testing.assert_allclose(a, b, rtol=0, atol=tol * np.abs(b).max(),
                                   err_msg=name)


# --- a held round on the kernel path: the grouped matmuls own its dead rows -------

V5E_VMEM = 128 << 20
WIDE = 128          # hidden and expert width: whole registers, so a plan


def _on_the_kernels(steer, masked):
    """``MoE``'s expert matmuls through the Pallas kernels, as on a v5e
    (the rule's own plan, Pallas's interpreter); ``masked``: each wrapped in
    the two row selects a held round traced around it before the kernels
    owned the dead rows, so what ``_held_round`` then traces is that form."""
    plain = dt._expert_matmul

    def forced(counts, dtype, m, weights, platform=None):
        matmul, kernels = plain(counts, dtype, m, weights, "tpu", V5E_VMEM,
                                interpret=True)
        assert kernels
        if not masked:
            return matmul, True
        live = (jnp.arange(m) < jnp.sum(counts))[:, None]
        return lambda r, w: jnp.where(
            live, matmul(jnp.where(live, r, 0), w), 0), True

    steer.setattr(dt, "_expert_matmul", forced)


# rounds of 384 rows (three row tiles of 128) over 768 assignments of 256
# tokens; name: (rows an expert, the round's first row)
ROUND = 384
HELD_ROUNDS = {
    # 197 live rows: a full tile, a boundary inside the second, a dead one
    "one-live-round": ([100, 0, 37, 60], 0),
    "two-live-rounds-the-first": ([200, 150, 0, 130], 0),     # no dead row
    # 96 live rows: two dead tiles after the one that holds the boundary
    "two-live-rounds-the-second": ([200, 150, 0, 130], ROUND),
    "a-dead-further-round": ([100, 0, 37, 60], ROUND),
}


def _round_inputs(counts):
    rs = np.random.RandomState(9)
    n, k = 256, 3
    x = jnp.asarray(rs.randn(n, WIDE), jnp.bfloat16)
    flat = jnp.asarray(rs.rand(n * k) + 0.1, jnp.float32)
    ws = [jnp.asarray(rs.randn(*s) * 0.2, jnp.float32)
          for s in ((4, WIDE, WIDE), (4, WIDE, WIDE), (4, WIDE, WIDE))]
    order = jnp.asarray(rs.permutation(n * k), jnp.int32)
    head = jnp.asarray(rs.randn(n, WIDE), jnp.float32)
    return (x, flat, *ws), order, jnp.asarray(counts, jnp.int32), head


def _round(first, order, counts, head, looped=False):
    def scalar(x, flat, *ws):
        out = dt._held_round(first, ROUND, None, x, order, flat, counts, *ws,
                             looped=looped)
        return jnp.sum(out * head), out

    return scalar


@pytest.mark.parametrize("case", list(HELD_ROUNDS))
def test_unmasked_round_gives_the_masked_rounds_bits(monkeypatch, case):
    """Output and all five gradients of one round: the kernels alone
    against the kernels masked on both sides."""
    counts, first = HELD_ROUNDS[case]
    wrt, order, counts, head = _round_inputs(counts)
    sides = []
    for masked in (False, True):
        with monkeypatch.context() as steer:
            _on_the_kernels(steer, masked)
            grads, out = jax.jit(jax.grad(
                _round(first, order, counts, head), argnums=tuple(range(5)),
                has_aux=True))(*wrt)
            sides.append([np.asarray(a, np.float32)
                          for a in [out] + list(grads)])
    for name, a, b in zip(["out", "x", "weight", "g", "u", "o"], *sides):
        assert (np.abs(b).max() > 0) == (case != "a-dead-further-round"), name
        np.testing.assert_array_equal(a, b, name)


def _row_selects(jaxpr, shapes):
    """``select_n`` equations of ``jaxpr`` and what it calls (the Pallas
    kernels' bodies apart) over an operand of one of ``shapes``."""
    from jax._src import core as jcore

    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "select_n" and any(
                v.aval.shape in shapes for v in eqn.invars):
            found.append(eqn)
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    found += _row_selects(sub, shapes)
    return found


@pytest.mark.parametrize("looped", [False, True],
                         ids=["first-round", "backwards-loop"])
@pytest.mark.parametrize("gradient", [False, True],
                         ids=["forward", "gradient"])
def test_kernel_path_traces_no_row_select(monkeypatch, gradient, looped):
    """With a plan, the round and its gradient hold no ``select_n`` over a
    (rows, H) or (rows, F) array (F is twice H here, to tell them apart),
    but the select of ``y`` and its transpose in a round of the backward's
    loop; without a plan (the CPU as it stands: ``ragged_dot``) every
    matmul keeps its two, and autodiff transposes them."""
    counts, first = HELD_ROUNDS["one-live-round"]
    (x, flat, *_), order, counts, head = _round_inputs(counts)
    rs = np.random.RandomState(3)
    wide = 2 * WIDE
    ws = [jnp.asarray(rs.randn(*s) * 0.2, jnp.float32)
          for s in ((4, WIDE, wide), (4, WIDE, wide), (4, wide, WIDE))]
    shapes = ((ROUND, WIDE), (ROUND, wide))

    def jaxpr():    # a function of its own a trace: jax keeps traces by it
        scalar = _round(first, order, counts, head, looped)
        return jax.make_jaxpr(
            jax.grad(scalar, argnums=tuple(range(5)), has_aux=True)
            if gradient else scalar)(x, flat, *ws).jaxpr

    with monkeypatch.context() as steer:
        _on_the_kernels(steer, masked=False)
        kernels = jaxpr()
    assert "pallas_call" in str(kernels)
    assert len(_row_selects(kernels, shapes)) == looped * (1 + gradient)
    assert not _row_selects(kernels, shapes[1:])
    with monkeypatch.context() as steer:       # what this test sees
        _on_the_kernels(steer, masked=True)
        masked = jaxpr()
    plain = jaxpr()
    assert "pallas_call" not in str(plain) and "ragged_dot" in str(plain)
    both = 12 if gradient else 6
    assert len(_row_selects(plain, shapes)) == both
    assert len(_row_selects(masked, shapes)) == both + looped * (1 + gradient)


@pytest.mark.parametrize("row_sums", [False, True],
                         ids=["scatter-adds", "row-sum-kernel"])
@pytest.mark.parametrize("layout", [l for l in LAYOUTS if l != "all-held"])
def test_unmasked_layer_gives_the_masked_layers_bits(monkeypatch, layout,
                                                     row_sums):
    """A whole held-range layer, its first round and the loop over the
    further ones: the kernels alone against the kernels masked on both
    sides, with a round's rows summed by XLA's scatter-add and by the row
    sum kernel, whose chunks read dead rows of ``y`` and of the rows'
    cotangent beside the live ones (zeros either way)."""
    sides = []
    for masked in (False, True):
        with monkeypatch.context() as steer:
            grads, (calls, _) = _wide_layer(steer, layout, row_sums, WIDE,
                                            masked)
            assert bool(calls) == row_sums
            sides.append(grads)
    for name, a, b in zip(MOE_INPUTS, *sides):
        assert np.abs(b).max() > 0, name
        np.testing.assert_array_equal(a, b, name)
