"""Graphs whose ``FullyConnected`` nodes share a weight, each with a plain
``jax.numpy`` reference that takes one dot a node (shared by
test_executor.py, test_rnn.py, test_fused_update.py and test_telemetry.py:
the batched groups of ``Executor._shared_fc_plan``).

Sizes are chosen so that the weights outweigh a node's rows, as in a real
recurrent model, and the byte rule takes every group: batch 2, hidden 16.
"""

import jax
import jax.numpy as jnp
import numpy as np

import mxnet_tpu as mx

B, H, E = 2, 16, 12
HIGHEST = jax.lax.Precision.HIGHEST


def _fc(x, p, pre, bias=True):
    w = p[pre + "weight"].astype(x.dtype)
    y = jnp.dot(x, w.T, precision=HIGHEST)
    return y + p[pre + "bias"].astype(x.dtype) if bias else y


def _sig(z):
    return jax.nn.sigmoid(z)


def _lstm_step(p, pre, x, state):
    h, c = state
    gates = _fc(x, p, pre + "i2h_") + _fc(h, p, pre + "h2h_")
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = _sig(f + 1.0) * c + _sig(i) * jnp.tanh(g)
    h = _sig(o) * jnp.tanh(c)
    return h, (h, c)


def _gru_step(p, pre, x, state):
    (h,) = state
    ir, iz, io = jnp.split(_fc(x, p, pre + "i2h_"), 3, axis=-1)
    hr, hz, ho = jnp.split(_fc(h, p, pre + "h2h_"), 3, axis=-1)
    tmp = jnp.tanh(io + _sig(ir + hr) * ho)
    h = tmp + _sig(iz + hz) * (h - tmp)
    return h, (h,)


def _rnn_step(p, pre, x, state):
    (h,) = state
    h = jnp.tanh(_fc(x, p, pre + "i2h_") + _fc(h, p, pre + "h2h_"))
    return h, (h,)


_CELLS = {"lstm": (mx.rnn.LSTMCell, _lstm_step, 2),
          "gru": (mx.rnn.GRUCell, _gru_step, 1),
          "rnn": (mx.rnn.RNNCell, _rnn_step, 1)}


def recurrent(kind="lstm", layers=2, steps=5, dtype="float32", tied=False):
    """(symbol, input shapes, reference loss, batched groups) of ``layers``
    unrolled cells over ``steps`` time steps: the loss is the sum of squares
    of the top layer's outputs. The i2h nodes of a layer do not read each
    other and are batched; its h2h nodes form a chain and are not. ``dtype`` bfloat16 casts the data and the begin
    states, so the matmuls run in bfloat16 over float32 masters. ``tied``
    adds the sum of squares of layer 0's i2h weight: a consumer of that
    Variable which is no ``FullyConnected``."""
    cell_cls, step_fn, n_states = _CELLS[kind]
    stack = mx.rnn.SequentialRNNCell()
    cells = [cell_cls(H, prefix=f"l{i}_") for i in range(layers)]
    for c in cells:
        stack.add(c)
    state_names = [f"l{i}_begin_state_{j}" for i in range(layers)
                   for j in range(n_states)]
    data = mx.sym.Variable("data")
    begin = [mx.sym.Variable(n) for n in state_names]
    if dtype != "float32":
        data = mx.sym.Cast(data, dtype=dtype)
        begin = [mx.sym.Cast(s, dtype=dtype) for s in begin]
    outs, _ = stack.unroll(steps, inputs=data, begin_state=begin,
                           merge_outputs=True)
    total = mx.sym.sum(mx.sym.square(mx.sym.Cast(outs, dtype="float32")))
    if tied:
        total = total + mx.sym.sum(mx.sym.square(
            cells[0].params.get("i2h_weight")))
    shapes = {"data": (B, steps, E)}
    shapes.update({n: (B, H) for n in state_names})

    def loss(p):
        x = p["data"].astype(dtype)
        seq = [x[:, t] for t in range(steps)]
        for i in range(layers):
            state = tuple(p[f"l{i}_begin_state_{j}"].astype(dtype)
                          for j in range(n_states))
            nxt = []
            for x_t in seq:
                y, state = step_fn(p, f"l{i}_", x_t, state)
                nxt.append(y)
            seq = nxt
        total = sum(jnp.sum(jnp.square(y.astype(jnp.float32))) for y in seq)
        if tied:
            total = total + jnp.sum(jnp.square(p["l0_i2h_weight"]))
        return total

    return mx.sym.MakeLoss(total), shapes, loss, layers


def _fc_params(no_bias):
    params = {"weight": mx.sym.Variable("fc_weight")}
    if not no_bias:
        params["bias"] = mx.sym.Variable("fc_bias")
    return params


def towers(steps=3, no_bias=False, flatten=True, groups=None):
    """One ``FullyConnected`` (H -> H) applied to ``steps`` inputs of their
    own, a tanh on each: nodes that do not read each other. ``flatten=False``
    runs it on the last axis of 3-D inputs; ``groups`` names a ctx group a
    tower."""
    params = _fc_params(no_bias)
    outs = []
    for t in range(steps):
        with mx.AttrScope(**({"ctx_group": groups[t]} if groups else {})):
            outs.append(mx.sym.tanh(mx.sym.FullyConnected(
                mx.sym.Variable(f"x{t}"), num_hidden=H, no_bias=no_bias,
                flatten=flatten, name=f"fc_t{t}", **params)))
    total = sum(mx.sym.sum(mx.sym.square(o)) for o in outs[1:])
    total = total + mx.sym.sum(mx.sym.square(outs[0]))
    shapes = {f"x{t}": (B, H) if flatten else (B, 2, H) for t in range(steps)}

    def loss(p):
        return sum(jnp.sum(jnp.square(jnp.tanh(
            _fc(p[f"x{t}"], p, "fc_", bias=not no_bias))))
            for t in range(steps))

    return mx.sym.MakeLoss(total), shapes, loss, 1


def chain(steps=3):
    """``steps`` applications of one ``FullyConnected`` (H -> H), each on
    the tanh of the one before: nodes that do read each other, which no
    plan batches."""
    params = _fc_params(False)
    h = mx.sym.Variable("data")
    for t in range(steps):
        h = mx.sym.tanh(mx.sym.FullyConnected(
            h, num_hidden=H, name=f"fc_t{t}", **params))

    def loss(p):
        h = p["data"]
        for _ in range(steps):
            h = jnp.tanh(_fc(h, p, "fc_"))
        return jnp.sum(jnp.square(h))

    return (mx.sym.MakeLoss(mx.sym.sum(mx.sym.square(h))), {"data": (B, H)},
            loss, 0)


def crossed():
    """Two shared weights A and B: a0 = A(x0), b0 = B(x1), a1 = A(b0),
    b1 = B(a0). Either group alone has nodes that do not read each other;
    batched together each would wait for the other. One is taken."""
    fc = {k: {"weight": mx.sym.Variable(f"{k}_weight"),
              "bias": mx.sym.Variable(f"{k}_bias")} for k in "ab"}

    def node(k, x, name):
        return mx.sym.tanh(mx.sym.FullyConnected(
            x, num_hidden=H, name=name, **fc[k]))

    a0 = node("a", mx.sym.Variable("x0"), "a0")
    b0 = node("b", mx.sym.Variable("x1"), "b0")
    a1, b1 = node("a", b0, "a1"), node("b", a0, "b1")
    total = mx.sym.sum(mx.sym.square(a1)) + mx.sym.sum(mx.sym.square(b1))

    def loss(p):
        a0 = jnp.tanh(_fc(p["x0"], p, "a_"))
        b0 = jnp.tanh(_fc(p["x1"], p, "b_"))
        a1, b1 = jnp.tanh(_fc(b0, p, "a_")), jnp.tanh(_fc(a0, p, "b_"))
        return jnp.sum(jnp.square(a1)) + jnp.sum(jnp.square(b1))

    return mx.sym.MakeLoss(total), {"x0": (B, H), "x1": (B, H)}, loss, 1


CASES = {
    "lstm": lambda: recurrent("lstm"),
    "gru": lambda: recurrent("gru"),
    "rnn": lambda: recurrent("rnn"),
    "bf16": lambda: recurrent("lstm", dtype="bfloat16"),
    "tied": lambda: recurrent("lstm", tied=True),
    "no_bias": lambda: towers(no_bias=True),
    "flatten_false": lambda: towers(flatten=False),
    "chain": chain,
    "crossed": crossed,
    "chunked": lambda: recurrent("lstm", steps=9),
}


def values(sym, shapes, seed=0):
    """{argument name: float32 numpy array} for every argument of ``sym``."""
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    rs = np.random.RandomState(seed)
    return {n: (rs.randn(*s) * 0.3).astype(np.float32)
            for n, s in zip(sym.list_arguments(), arg_shapes)}


def reference_grads(loss, vals):
    grads = jax.grad(loss)({n: jnp.asarray(v) for n, v in vals.items()})
    return {n: np.asarray(g) for n, g in grads.items()}


def bound(sym, shapes, vals, grad_req="write", **kwargs):
    exe = sym.simple_bind(mx.cpu(), grad_req=grad_req, **shapes, **kwargs)
    for n, v in vals.items():
        exe.arg_dict[n][:] = v
    return exe


def n_stacked(exe):
    """Shared weights whose gradient the executor computes as one matmul."""
    return exe._shared_fc_plan()[2]


def disable(exe):
    """The grouping pass switched off, in a test: one dot a node."""
    exe._fc_plan = ([], None, 0)
    return exe
