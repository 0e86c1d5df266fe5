"""``MoE``'s routing bookkeeping as dense selects (``_select_scores``, the
counts, the held round's own window of weights) against a test-local copy of
the forms it had: ``take_along_axis``, ``bincount`` and a gather of all
N * k weights. Same bits, forward and backward, and no gather or scatter of
N * k indices left in a lowered train step."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import executor as ex
from mxnet_tpu.ops import defs_transformer as dt
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
    matmul = dt._expert_matmul(here, x.dtype, rows, (w_gate, w_up, w_down))

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
                        counts):
    """Today's call of ``_held_rounds`` answered as ``_moe`` answered it
    before: every assignment's weight gathered into the sorted order, the
    padding zeros of ``tok`` and ``weight``."""
    nk = flat.shape[0]
    pad = order.shape[0] - nk
    order = order[:nk]
    tok = jnp.pad(order // (nk // x.shape[0]), (0, pad))
    weight = keep(jnp.pad(flat[order], (0, pad)))
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
    # 80 assignments in rounds of 40: no padding, the tail all dead rows
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
