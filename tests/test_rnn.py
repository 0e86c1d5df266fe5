"""RNN cell tests (reference test_rnn.py)."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import assert_almost_equal


def test_rnn_cell_unroll_shapes():
    cell = mx.rnn.RNNCell(10, prefix="rnn_")
    outputs, states = cell.unroll(3)
    outs = mx.sym.Group(outputs)
    args = sorted(set(outs.list_arguments()))
    assert "rnn_i2h_weight" in args and "rnn_h2h_weight" in args
    arg_shapes, out_shapes, _ = outs.infer_shape(
        t0_data=(2, 6), t1_data=(2, 6), t2_data=(2, 6),
        rnn_begin_state_0=(2, 10),
    )
    assert out_shapes == [(2, 10)] * 3


def test_lstm_cell_forward():
    cell = mx.rnn.LSTMCell(4, prefix="lstm_", forget_bias=0.0)
    x = mx.sym.Variable("x")
    h0 = mx.sym.Variable("h0")
    c0 = mx.sym.Variable("c0")
    out, states = cell(x, [h0, c0])
    rs = np.random.RandomState(0)
    xv = rs.randn(1, 3).astype(np.float32)
    h0v = np.zeros((1, 4), dtype=np.float32)
    c0v = np.zeros((1, 4), dtype=np.float32)
    wi = rs.randn(16, 3).astype(np.float32)
    bi = np.zeros(16, dtype=np.float32)
    wh = rs.randn(16, 4).astype(np.float32)
    bh = np.zeros(16, dtype=np.float32)
    exe = out.bind(mx.cpu(), args={
        "x": mx.nd.array(xv), "h0": mx.nd.array(h0v), "c0": mx.nd.array(c0v),
        "lstm_i2h_weight": mx.nd.array(wi), "lstm_i2h_bias": mx.nd.array(bi),
        "lstm_h2h_weight": mx.nd.array(wh), "lstm_h2h_bias": mx.nd.array(bh),
    })
    exe.forward(is_train=False)
    # numpy LSTM oracle
    gates = xv @ wi.T + h0v @ wh.T
    i, f, c, o = np.split(gates, 4, axis=1)
    sig = lambda z: 1 / (1 + np.exp(-z))
    c_new = sig(f) * c0v + sig(i) * np.tanh(c)
    h_new = sig(o) * np.tanh(c_new)
    assert_almost_equal(exe.outputs[0].asnumpy(), h_new, rtol=1e-4, atol=1e-5)


def test_gru_cell_runs():
    cell = mx.rnn.GRUCell(5, prefix="gru_")
    outputs, _ = cell.unroll(2, input_prefix="g")
    outs = mx.sym.Group(outputs)
    exe = outs.simple_bind(
        ctx=mx.cpu(),
        **{"gt0_data": (2, 4), "gt1_data": (2, 4), "gru_begin_state_0": (2, 5)},
    )
    exe.forward(is_train=False)
    assert exe.outputs[0].shape == (2, 5)


def test_sequential_stack():
    stack = mx.rnn.SequentialRNNCell()
    stack.add(mx.rnn.LSTMCell(8, prefix="l0_"))
    stack.add(mx.rnn.LSTMCell(8, prefix="l1_"))
    outputs, states = stack.unroll(3)
    assert len(states) == 4  # 2 states per LSTM layer
    outs = mx.sym.Group(outputs)
    args = outs.list_arguments()
    assert "l0_i2h_weight" in args and "l1_i2h_weight" in args


def test_bidirectional_cell():
    cell = mx.rnn.BidirectionalCell(
        mx.rnn.LSTMCell(4, prefix="l_"), mx.rnn.LSTMCell(4, prefix="r_"),
    )
    data = mx.sym.Variable("data")
    outputs, _ = cell.unroll(3, inputs=data, merge_outputs=False)
    outs = mx.sym.Group(outputs)
    shapes = {
        "data": (2, 3, 6),
        **{f"{p}_begin_state_{i}": (2, 4) for p in ("l", "r") for i in (0, 1)},
    }
    arg_shapes, out_shapes, _ = outs.infer_shape(**shapes)
    assert all(s == (2, 8) for s in out_shapes)  # concat of fwd+bwd


def test_dropout_residual_cells():
    base = mx.rnn.RNNCell(6, prefix="b_")
    res = mx.rnn.ResidualCell(base)
    x = mx.sym.Variable("x")
    states = res.begin_state()
    out, _ = res(x, states)
    arg_shapes, out_shapes, _ = out.infer_shape(
        x=(2, 6), b_begin_state_0=(2, 6)
    )
    assert out_shapes[0] == (2, 6)


def test_bucket_sentence_iter():
    sentences = [[1, 2, 3], [2, 3], [1, 2, 3, 4, 5], [3, 4]] * 10
    it = mx.rnn.BucketSentenceIter(
        sentences, batch_size=4, buckets=[3, 5], invalid_label=0
    )
    batch = next(iter(it))
    assert batch.bucket_key in (3, 5)
    assert batch.data[0].shape[0] == 4
    assert batch.data[0].shape[1] == batch.bucket_key


def test_encode_sentences():
    sents, vocab = mx.rnn.encode_sentences(
        [["a", "b"], ["b", "c"]], start_label=1
    )
    assert len(vocab) >= 3
    assert sents[0][1] == sents[1][0]  # same token 'b' → same id


@pytest.mark.parametrize("mode", ["lstm", "gru", "rnn_tanh"])
def test_fused_cell_unroll_batches_its_i2h(mode):
    """FusedRNNCell.unroll unfuses to per-step cells that share their
    weights: the executor runs each layer's i2h as one batched matmul,
    forward and backward, and the gradients equal the per-step ones."""
    import stacked_wgrad_cases as swc

    cell = mx.rnn.FusedRNNCell(swc.H, num_layers=2, mode=mode, prefix="f_")
    outs, _ = cell.unroll(5, inputs=mx.sym.Variable("data"),
                          merge_outputs=True)
    sym = mx.sym.MakeLoss(mx.sym.sum(mx.sym.square(outs)))
    shapes = {"data": (swc.B, 5, swc.E)}
    shapes.update({n: (swc.B, swc.H) for n in sym.list_arguments()
                   if "begin_state" in n})
    vals = swc.values(sym, shapes)
    grads = []
    for stacked in (True, False):
        exe = swc.bound(sym, shapes, vals)
        if not stacked:
            swc.disable(exe)
        assert swc.n_stacked(exe) == (2 if stacked else 0)
        exe.forward(is_train=True)
        exe.backward()
        grads.append({n: g.asnumpy() for n, g in exe.grad_dict.items()})
    for n, g in grads[0].items():
        assert_almost_equal(g, grads[1][n], rtol=1e-5, atol=1e-5,
                            names=(f"stacked[{n}]", "per-step"))
