"""The operator forms ZAYA1's layer needs, each alone against ``jax.numpy``:
``MoE(router="graph")`` (the logits an input the graph computed) against
``MoE`` with its own ``router_weight``; ``CausalConv1D`` without its SiLU,
with a bias, and mixing channels inside groups, and with the defaults the
bits of the form it had; ``Activation("gelu")`` by erf. (The model against
its plain reference is ``test_zaya.py``.)"""

import numpy as np
import pytest
from model_cases import bind_op, rel

import mxnet_tpu as mx


# --- MoE(router="graph") ---------------------------------------------------------

E, H, F, N = 16, 32, 8, 40
NAMES = ["x", "r", "g", "u", "o"]


def _moe_inputs(held, first, bias):
    rs = np.random.RandomState(4)
    x = rs.randn(N, H).astype(np.float32)
    router = (rs.randn(E, H) * 0.3).astype(np.float32)
    ws = [(rs.randn(*s) * 0.3).astype(np.float32)[first:first + held]
          for s in ((E, H, F), (E, H, F), (E, F, H))]
    extra = [(rs.randn(E) * 0.2).astype(np.float32)] if bias else []
    return [x, router] + ws + extra


@pytest.mark.parametrize("held,first", [(E, 0), (4, 8)])
@pytest.mark.parametrize("top_k", [1, 8])
@pytest.mark.parametrize("score", [
    dict(score_func="softmax"),
    dict(score_func="softmax", route_norm=True, lb_coef=0.01, z_coef=0.001),
    dict(score_func="sigmoid", route_norm=True, route_scale=2.5,
         expert_bias=True)])
def test_graph_logits_equal_the_router_weight(held, first, top_k, score):
    """``MoE(router="graph")`` fed ``FullyConnected(x, W)`` is ``MoE`` with
    ``router_weight = W``: the output, and the gradient of the rows, of W
    (through the logits: scores, weights, both regularisers) and of the
    three expert weights, with every expert held and with a held range."""
    names = NAMES + ["b"] * score.get("expert_bias", False)
    inputs = _moe_inputs(held, first, len(names) > 5)
    params = dict(num_experts=E, num_hidden=F, top_k=top_k,
                  num_local_experts=0 if held == E else held,
                  expert_offset=first, **score)
    v = dict(zip(names, map(mx.sym.Variable, names)))
    inside = mx.sym.MoE(*[v[n] for n in names], **params)
    logits = mx.sym.FullyConnected(v["x"], weight=v["r"], num_hidden=E,
                                   no_bias=True)
    outside = mx.sym.MoE(v["x"], logits, *[v[n] for n in names[2:]],
                         router="graph", **params)
    assert outside.list_arguments() == names
    assert "router_logits" in mx.sym.MoE(
        v["x"], router="graph", name="m", **params).list_arguments()[1]
    head = np.random.RandomState(6).randn(N, H).astype(np.float32)
    got = []
    for sym in (inside, outside):
        exe = bind_op(sym, names, inputs)
        out = exe.forward(is_train=True)[0].asnumpy()
        exe.backward(out_grads=[mx.nd.array(head)])
        got.append([out] + [exe.grad_dict[n].asnumpy() for n in names])
    assert np.abs(got[0][0]).max() > 0.01
    for n, a, b in zip(["out"] + names, *got):
        if n == "b":
            assert not a.any() and not b.any()
        else:
            assert a.any(), n
            assert rel(b, a) < 2e-5, n


def test_graph_logits_keep_their_leading_axes_and_are_read_in_float32():
    """Rows (B, T, H) with logits (B, T, E); bfloat16 rows beside float32
    logits give what float32 rows rounded once would."""
    rs = np.random.RandomState(9)
    x = rs.randn(2, 12, H).astype(np.float32)
    logits = rs.randn(2, 12, E).astype(np.float32)
    ws = [(rs.randn(*s) * 0.3).astype(np.float32)
          for s in ((E, H, F), (E, H, F), (E, F, H))]
    names = ["x", "z", "g", "u", "o"]
    sym = mx.sym.MoE(*map(mx.sym.Variable, names), router="graph",
                     num_experts=E, num_hidden=F, top_k=1)
    assert sym.infer_shape(x=x.shape)[0][1] == (2, 12, E)
    out = bind_op(sym, names, [x, logits] + ws).forward()[0].asnumpy()
    assert out.shape == x.shape
    flat = bind_op(sym, names, [x.reshape(24, H), logits.reshape(24, E)]
                   + ws).forward()[0].asnumpy()
    assert np.array_equal(out.reshape(24, H), flat)
    low = mx.sym.MoE(mx.sym.Cast(mx.sym.Variable("x"), dtype="bfloat16"),
                     *map(mx.sym.Variable, names[1:]), router="graph",
                     num_experts=E, num_hidden=F, top_k=1)
    got = bind_op(low, names, [x, logits] + ws).forward()[0]
    assert got.dtype == np.dtype("bfloat16") or str(got.dtype) == "bfloat16"
    assert rel(got.asnumpy().astype(np.float32), out) < 3e-2
    with pytest.raises(mx.base.MXNetError, match="router"):
        mx.sym.MoE(*map(mx.sym.Variable, names), router="mlp", num_experts=E,
                   num_hidden=F, top_k=1).bind(
                       mx.cpu(), {n: mx.nd.array(a) for n, a in zip(
                           names, [x, logits] + ws)}).forward()[0].asnumpy()


# --- CausalConv1D ----------------------------------------------------------------

def _plain_conv(x, w, b, groups, act):
    """``jax.numpy``: taps shifted over time, the last at t."""
    import jax
    import jax.numpy as jnp

    taps, t = w.shape[-1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = 0.0
    for j in range(taps):
        xs = xp[:, j:j + t]
        if groups:
            xs = xs.reshape(xs.shape[:2] + (groups, -1))
            out = out + jnp.einsum("btgi,goi->btgo", xs,
                                   w[..., j]).reshape(x.shape)
        else:
            out = out + xs * w[:, j]
    if b is not None:
        out = out + b
    return jax.nn.silu(out) if act == "silu" else out


@pytest.mark.parametrize("form", [
    dict(act_type="none"),
    dict(no_bias=False),
    dict(num_group=3),
    dict(num_group=3, act_type="none", no_bias=False)],
    ids=["no_activation", "bias", "grouped", "grouped_bias_no_activation"])
@pytest.mark.parametrize("taps", [2, 3])
def test_causal_conv_forms_match_jax_numpy(form, taps):
    import jax
    import jax.numpy as jnp

    rs = np.random.RandomState(7)
    groups, bias = form.get("num_group", 0), not form.get("no_bias", True)
    x = rs.randn(2, 20, 24).astype(np.float32)
    w = rs.randn(*((groups, 8, 8, taps) if groups else (24, taps))).astype(
        np.float32)
    names = ["x", "w"] + ["b"] * bias
    inputs = [x, w] + [rs.randn(24).astype(np.float32)] * bias
    sym = mx.sym.CausalConv1D(*map(mx.sym.Variable, names), kernel=taps,
                              **form)
    assert sym.infer_shape(x=x.shape)[0] == [a.shape for a in inputs]
    exe = bind_op(sym, names, inputs)
    with jax.default_matmul_precision("highest"):
        out = exe.forward(is_train=True)[0].asnumpy()
        head = rs.randn(*out.shape).astype(np.float32)
        exe.backward(out_grads=[mx.nd.array(head)])
        want, vjp = jax.vjp(
            lambda x, w, *b: _plain_conv(x, w, b[0] if b else None, groups,
                                         form.get("act_type", "silu")),
            *map(jnp.asarray, inputs))
        grads = vjp(jnp.asarray(head))
    assert rel(out, want) < 1e-5
    for n, g in zip(names, grads):
        assert rel(exe.grad_dict[n].asnumpy(), g) < 1e-5, n
    # causal: a change at t = 9 reaches t = 9 .. 9 + taps - 1 and no other
    changed = x.copy()
    changed[:, 9] += 1.0
    moved = bind_op(sym, names, [changed] + inputs[1:]).forward()[0].asnumpy()
    assert np.array_equal(moved[:, :9], out[:, :9])
    assert np.array_equal(moved[:, 9 + taps:], out[:, 9 + taps:])
    assert not np.allclose(moved[:, 9:9 + taps], out[:, 9:9 + taps])


def test_a_group_mixes_its_own_channels_only():
    rs = np.random.RandomState(3)
    x = rs.randn(1, 10, 24).astype(np.float32)
    w = rs.randn(3, 8, 8, 2).astype(np.float32)
    sym = mx.sym.CausalConv1D(mx.sym.Variable("x"), mx.sym.Variable("w"),
                              kernel=2, num_group=3, act_type="none")
    out = bind_op(sym, ["x", "w"], [x, w]).forward()[0].asnumpy()
    changed = x.copy()
    changed[..., 8:16] += 1.0               # the second group's inputs
    moved = bind_op(sym, ["x", "w"], [changed, w]).forward()[0].asnumpy()
    assert np.array_equal(moved[..., :8], out[..., :8])
    assert np.array_equal(moved[..., 16:], out[..., 16:])
    assert not np.allclose(moved[..., 8:16], out[..., 8:16])
    with pytest.raises(mx.base.MXNetError, match="groups"):
        mx.sym.CausalConv1D(mx.sym.Variable("x"), kernel=2,
                            num_group=5).infer_shape(x=x.shape)
    with pytest.raises(mx.base.MXNetError, match="act_type"):
        bind_op(mx.sym.CausalConv1D(
            mx.sym.Variable("x"), mx.sym.Variable("w"), kernel=2,
            act_type="relu"), ["x", "w"],
            [x, rs.randn(24, 2).astype(np.float32)]).forward()[0].asnumpy()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_defaults_give_the_bits_of_the_form_that_was(dtype):
    """Qwen3-Next's short convolution cut small (4 taps over [q | k | v],
    depthwise, no bias, SiLU): the operator with its new parameters at
    their defaults against the body it had before them."""
    import jax
    import jax.numpy as jnp

    rs = np.random.RandomState(11)
    x = jnp.asarray(rs.randn(1, 64, 2 * 32 + 64), dtype)
    w = jnp.asarray(rs.randn(128, 4).astype(np.float32))

    def was(x, w):
        taps, t = w.shape[1], x.shape[1]
        xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        wf = w.astype(jnp.float32)
        out = sum(xp[:, j:j + t].astype(jnp.float32) * wf[:, j]
                  for j in range(taps))
        return jax.nn.silu(out).astype(x.dtype)

    sym = mx.sym.CausalConv1D(mx.sym.Variable("x"), mx.sym.Variable("w"),
                              kernel=4)
    assert sym.list_arguments() == ["x", "w"]
    exe = sym.bind(mx.cpu(), {"x": mx.nd.NDArray(x), "w": mx.nd.NDArray(w)})
    out = exe.forward()[0]._data
    assert out.dtype == x.dtype
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(jax.jit(was)(x, w), np.float32))


# --- Activation("gelu") ----------------------------------------------------------

def test_gelu_is_the_erf_form():
    import math

    x = np.linspace(-4, 4, 41).astype(np.float32)
    sym = mx.sym.Activation(mx.sym.Variable("x"), act_type="gelu")
    exe = bind_op(sym, ["x"], [x])
    out = exe.forward(is_train=True)[0].asnumpy()
    exe.backward(out_grads=[mx.nd.ones(x.shape)])
    phi = np.array([0.5 * (1 + math.erf(v / math.sqrt(2))) for v in x])
    pdf = np.exp(-0.5 * x.astype(np.float64) ** 2) / math.sqrt(2 * math.pi)
    assert np.allclose(out, x * phi, atol=1e-6)
    assert np.allclose(exe.grad_dict["x"].asnumpy(), phi + x * pdf, atol=1e-5)
    # and not the tanh approximation, which differs by up to 5e-4 here
    tanh = 0.5 * x * (1 + np.tanh(math.sqrt(2 / math.pi)
                                  * (x + 0.044715 * x ** 3)))
    assert np.abs(out - tanh).max() > 1e-4
