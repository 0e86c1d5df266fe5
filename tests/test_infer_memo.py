"""The inference memo (``ops/registry._InferMemo``): abstract evaluation of
an operator runs once per distinct signature, and nothing else changes.

Equality is against the same calls made through ``infer_memo_table(0)``, a
table that stores nothing: the path the framework had before the memo.
"""

import functools
import sys
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu import operator as mxop
from mxnet_tpu import telemetry as tm
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import registry
from mxnet_tpu.test_utils import infer_memo_table

BUCKETS = (60, 50, 40, 30, 20, 10)


def evals():
    return tm.counter("symbol.infer_eval").value


def hits():
    return tm.counter("symbol.infer_memo_hit").value


def _lstm_gen():
    return models.lstm_lm_sym_gen(num_hidden=16, num_layers=2, num_embed=16,
                                  vocab_size=50, dropout=0.5)


def _lstm():
    gen, states = _lstm_gen()
    shapes = {"data": (4, 10), "softmax_label": (4, 10)}
    shapes.update({n: (4, 16) for n in states})
    return gen(10)[0], shapes, {"data": "float32"}


def _olmoe():
    gen = models.olmoe_sym_gen(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        num_experts=8, expert_width=16, top_k=2, dtype="float32")
    sym = gen(16)[0]
    return sym, {"data": (2, 16), "softmax_label": (2, 16)}, \
        {"data": "float32"}


# name -> (symbol, full input shapes, input dtypes)
SYMBOLS = {
    "resnet50_v2": lambda: (
        models.resnet(num_classes=10, num_layers=50,
                      image_shape="3,224,224"),
        {"data": (2, 3, 224, 224), "softmax_label": (2,)},
        {"data": "bfloat16"}),
    "lstm_t10_dropout": _lstm,
    "olmoe_tiny": _olmoe,
    # BatchNorm: three outputs, one visible, two auxiliary states a node
    "inception_bn": lambda: (
        models.inception_bn(num_classes=10),
        {"data": (2, 3, 224, 224), "softmax_label": (2,)},
        {"data": "float32"}),
    # MultiBoxTarget: three outputs, all read; a group of four heads
    "ssd_train": lambda: (
        models.ssd.get_symbol_train(num_classes=3, data_shape=300),
        {"data": (2, 3, 300, 300), "label": (2, 3, 5)},
        {"data": "float32"}),
}


@functools.lru_cache(maxsize=None)
def built(name):
    return SYMBOLS[name]()


CALLS = {
    "infer_shape": lambda s, shapes, dtypes: s.infer_shape(**shapes),
    # the data alone: what cannot be completed from it stays None
    "infer_shape_partial": lambda s, shapes, dtypes: (
        s.infer_shape_partial(**shapes),
        s.infer_shape_partial(data=shapes["data"]),
        s.infer_shape_partial()),
    "infer_type": lambda s, shapes, dtypes: (
        s.infer_type(**dtypes), s.infer_type()),
}


@pytest.mark.parametrize("call", sorted(CALLS))
@pytest.mark.parametrize("name", sorted(SYMBOLS))
def test_memo_equals_the_unmemoised_path(name, call):
    sym, shapes, dtypes = built(name)
    with infer_memo_table(0) as table:
        before = evals()
        want = CALLS[call](sym, shapes, dtypes)
        assert evals() > before and len(table) == 0
    first = CALLS[call](sym, shapes, dtypes)    # fills or hits
    before = evals()
    again = CALLS[call](sym, shapes, dtypes)    # hits only
    assert first == want and again == want
    assert evals() == before


def test_aux_and_multi_output_symbols_are_what_they_claim():
    sym, shapes, _ = built("inception_bn")
    _, _, aux = sym.infer_shape(**shapes)
    assert len(aux) == len(sym.list_auxiliary_states()) > 100
    assert all(a is not None for a in aux)
    sym, shapes, _ = built("ssd_train")
    _, outs, _ = sym.infer_shape(**shapes)
    assert len(outs) == 4


def test_six_lstm_buckets_bind_in_under_150_evaluations():
    gen, states = _lstm_gen()
    mod = mx.mod.BucketingModule(
        sym_gen=gen, default_bucket_key=max(BUCKETS), state_names=states,
        context=[mx.cpu()])

    def desc(t):
        return ([mx.io.DataDesc("data", (4, t))],
                [mx.io.DataDesc("softmax_label", (4, t))])

    with infer_memo_table(registry._MEMO.cap):
        start, start_hits = evals(), hits()
        mod.bind(*desc(BUCKETS[0]), for_training=True)
        mod.init_params()
        first = evals() - start
        added = []
        for t in BUCKETS[1:]:
            before = evals()
            mod.switch_bucket(t, *desc(t))
            added.append(evals() - before)
        total = evals() - start
        asked = total + hits() - start_hits
    assert 0 < first <= total < 150
    # a further bucket asks anew only where the signature holds T: the
    # embedding, the two reshapes, SliceChannel, Concat, the head's
    # FullyConnected and SoftmaxOutput (7 shapes, 2 dtypes)
    assert all(0 < n < 10 for n in added), added
    # ~30 operator nodes a time step, 210 time steps, two passes
    assert asked > 10000


def test_writing_into_an_answer_does_not_change_the_next():
    fc = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                               name="fc")
    want = fc.infer_shape(data=(2, 3))
    got = fc.infer_shape(data=(2, 3))
    got[0][1] = (9, 9)
    got[1][:] = [None]
    got[0].append("x")
    assert fc.infer_shape(data=(2, 3)) == want

    op = registry.get("FullyConnected")
    params = op.parse_params({"num_hidden": 4})
    a = op.infer_shape([(2, 3), None, None], params)
    assert all(isinstance(part, list) for part in a)
    a[0][0] = None
    a[1].clear()
    b = op.infer_shape([(2, 3), None, None], params)
    assert b == ([(2, 3), (4, 3), (4,)], [(2, 4)], [])
    d = op.infer_dtype([np.dtype("float32"), None, None], params)
    d[1][0] = np.dtype("int8")
    assert op.infer_dtype([np.dtype("float32"), None, None], params)[1] == \
        [np.dtype("float32")]


def test_an_operator_that_cannot_infer_raises_every_time():
    fc = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                               name="fc")
    net = mx.sym.Activation(fc, act_type="relu", name="act")
    for _ in range(2):
        with pytest.raises(MXNetError, match="cannot infer"):
            net.infer_shape()
        args, outs, aux = net.infer_shape_partial()
        assert args == [None, None, None] and outs == [None] and aux == []
    # a failing evaluation is run again, not remembered
    add = mx.sym.Variable("a") + mx.sym.Variable("b")
    for _ in range(2):
        before = evals()
        with pytest.raises(MXNetError, match="shape inference failed"):
            add.infer_shape(a=(2, 3), b=(4, 5))
        assert evals() == before + 1
        assert add.infer_shape_partial(a=(2, 3), b=(4, 5))[1] == [None]


def test_a_custom_operators_callbacks_run_every_time():
    calls = {"shape": 0, "type": 0}

    @mxop.register("infer_memo_probe")
    class Probe(mxop.CustomOpProp):
        def infer_shape(self, in_shape):
            calls["shape"] += 1
            return in_shape, [in_shape[0]], []

        def infer_type(self, in_type):
            calls["type"] += 1
            return in_type, [in_type[0]], []

    net = mx.sym.Custom(mx.sym.Variable("x"), op_type="infer_memo_probe")
    before_hits, before = hits(), evals()
    for n in (1, 2, 3):
        assert net.infer_shape(x=(2, 3))[1] == [(2, 3)]
        assert net.infer_type(x="float32")[1] == [np.dtype("float32")]
        assert calls == {"shape": n, "type": n}
    assert (hits(), evals()) == (before_hits, before)


def test_nodes_that_differ_in_one_parameter_do_not_share_an_entry():
    x = mx.sym.Variable("data")
    four = mx.sym.FullyConnected(x, num_hidden=4, name="fc")
    five = mx.sym.FullyConnected(x, num_hidden=5, name="fc")
    for _ in range(2):
        assert four.infer_shape(data=(2, 3))[1] == [(2, 4)]
        assert five.infer_shape(data=(2, 3))[1] == [(2, 5)]
    wide = mx.sym.Cast(x, dtype="float16")
    narrow = mx.sym.Cast(x, dtype="int32")
    for _ in range(2):
        assert wide.infer_type(data="float32")[1] == [np.dtype("float16")]
        assert narrow.infer_type(data="float32")[1] == [np.dtype("int32")]

    def key(**params):
        return registry._signature("shape", "op", params, [(2, 3)], None)

    # values that compare and hash alike but parse differently
    keys = [key(p=1), key(p=1.0), key(p=True), key(p="1"), key(p=(1,)),
            key(p=[1]), key(p=None), key(q=1), key(p=1, q=None)]
    assert len(set(keys)) == len(keys)
    assert key(p=(1, 2), q="a") == key(q="a", p=(1, 2))
    # not hashable by value: not memoised, and not by repr either
    assert key(p=np.zeros(3)) is None
    assert key(p=lambda: 0) is None
    # never equal to itself: would miss every time and fill the table
    assert key(p=float("nan")) is None
    assert key(p=(1.0, np.float32("nan"))) is None


def test_x64_is_part_of_the_question():
    import jax

    net = mx.sym.Cast(mx.sym.Variable("x"), dtype="float64")
    assert net.infer_type(x="float32")[1] == [np.dtype("float32")]
    with jax.enable_x64(True):
        assert net.infer_type(x="float32")[1] == [np.dtype("float64")]
    assert net.infer_type(x="float32")[1] == [np.dtype("float32")]


def test_the_table_never_exceeds_its_cap():
    assert registry._MEMO.cap == 4096      # fixed: no option sets it
    assert len(registry._MEMO) <= registry._MEMO.cap
    relu = mx.sym.Activation(mx.sym.Variable("x"), act_type="relu")
    with infer_memo_table(8) as table:
        for n in range(1, 21):
            relu.infer_shape(x=(n, 2))
            assert len(table) <= 8
        assert len(table) == 8
        before = evals()
        relu.infer_shape(x=(20, 2))         # among the newest: kept
        assert evals() == before
        relu.infer_shape(x=(1, 2))          # the oldest went first
        assert evals() == before + 1
        assert len(table) == 8


def test_a_signature_asked_again_outlives_newer_ones():
    """Least recently asked goes first, not first stored: a server's steady
    buckets stay while one-off shapes pass through."""
    relu = mx.sym.Activation(mx.sym.Variable("x"), act_type="relu")
    with infer_memo_table(4) as table:
        relu.infer_shape(x=(1, 2))          # the steady one, stored first
        for n in range(2, 12):
            relu.infer_shape(x=(n, 2))      # one-off shapes
            before = evals()
            relu.infer_shape(x=(1, 2))
            assert evals() == before        # still a hit
            assert len(table) <= 4
        before = evals()
        relu.infer_shape(x=(2, 2))          # not asked since: gone
        assert evals() == before + 1


def test_threads_share_the_table_without_losing_or_growing():
    """More binders than cores on a table smaller than their working set:
    every answer is right, nothing raises, the cap holds throughout."""
    relu = mx.sym.Activation(mx.sym.Variable("x"), act_type="relu")
    errors, over = [], []

    def worker(k, table):
        try:
            for n in range(60):
                rows = 1 + (n * (k + 1)) % 24
                if relu.infer_shape(x=(rows, 3))[1] != [(rows, 3)]:
                    errors.append((k, rows))
                if len(table) > table.cap:
                    over.append(len(table))
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with infer_memo_table(8) as table:
            threads = [threading.Thread(target=worker, args=(k, table))
                       for k in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
            assert not errors and not over
            assert len(table) == 8
    finally:
        sys.setswitchinterval(interval)


def test_both_counters_are_in_the_snapshot():
    mx.sym.Activation(mx.sym.Variable("x"), act_type="tanh") \
        .infer_shape(x=(3, 3))
    snap = tm.snapshot()["symbol"]
    assert snap["infer_eval"] >= 1 and "infer_memo_hit" in snap
