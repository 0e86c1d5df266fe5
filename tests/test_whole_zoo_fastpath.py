"""Whole-zoo fast path (ISSUE 13): every BASELINE workload through the
modern stack.

Pins, for each of the six BASELINE workloads (one parametrised test), the
two invariants of the fast path — counter-verified on the framework's own
telemetry, mirroring tests/test_async_pipeline.py:

* ZERO steady-state compiles: once a workload's programs are warm,
  ``executor.jit_compile`` (AOT forward/train-step builds) and
  ``executor.fused_plan_compile`` (fused-window plan builds) both stay 0.
  The warmup phase must show ``fused_plan_compile > 0`` first — a counter
  that never fires would make the steady-state assert vacuous.
* ZERO per-batch host syncs: ``ndarray.asnumpy`` / ``wait_to_read`` /
  ``metric.numpy_fallback`` / ``metric.drain_sync`` do not scale with
  batches in the steady state.

Plus the numerical anchors: the fused DCGAN step bit-matches the
reference imperative loop after one adam step, the FLOPs estimator
reproduces its closed forms (MAC convention), and the zoo registry covers
the published 14-symbol table.
"""

import os
import sys

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import models  # noqa: E402
from mxnet_tpu import telemetry as tm  # noqa: E402

_SYNC_COUNTERS = ("ndarray.asnumpy", "ndarray.wait_to_read",
                  "metric.numpy_fallback", "metric.drain_sync")


def _sync_counts():
    return {name: tm.counter(name).value for name in _SYNC_COUNTERS}


def _outputs_of(boundary):
    return lambda: [np.asarray(o._data, np.float32)
                    for o in boundary.outputs]


def _compiles():
    return (tm.counter("executor.jit_compile").value,
            tm.counter("executor.fused_plan_compile").value)


# ---------------------------------------------------------------------------
# bucketed LSTM-PTB


def _lstm_fixture(bs=4, hidden=16, vocab=50, buckets=(6, 10), k=2):
    rs = np.random.RandomState(0)
    sents = [[int(x) for x in rs.randint(1, vocab, int(rs.choice(buckets)))]
             for _ in range(bs * 4)]
    it = mx.rnn.BucketSentenceIter(sents, bs, buckets=list(buckets),
                                   invalid_label=0)
    sym_gen, state_names = models.lstm_lm_sym_gen(
        num_hidden=hidden, num_layers=1, num_embed=hidden, vocab_size=vocab)
    mod = mx.mod.BucketingModule(sym_gen=sym_gen,
                                 default_bucket_key=it.default_bucket_key,
                                 state_names=state_names, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    batches = list(it)
    chunks = [batches[i:i + k] for i in range(0, len(batches), k)]
    return mod, chunks


def _steady_lstm(monkeypatch):
    """After one warmup epoch over the bucket mix, a steady epoch of
    grouped K-batch windows issues no compiles and no per-batch host
    syncs — switch_bucket is a pure cache pick."""
    mod, chunks = _lstm_fixture()
    tm.reset()
    for ch in chunks:
        mod.train_window(None, batches=ch, publish_grads=False).wait()
    # the warmup epoch proves the compile counter fires (one fused plan
    # per (bucket, group size) pair) — without this the steady assert
    # could pass vacuously with a dead counter
    assert _compiles()[1] > 0
    windows_warm = tm.counter("bucketing.window").value
    assert windows_warm > 0

    tm.reset()
    for ch in chunks:
        last = mod.train_window(None, batches=ch, publish_grads=False)
        last.wait()
    assert tm.counter("executor.fused_plan_hit").value == windows_warm
    assert tm.counter("bucketing.window").value == windows_warm
    return 0, _outputs_of(last)


# ---------------------------------------------------------------------------
# DCGAN


_GAN_BS, _GAN_Z, _GAN_NF = 4, 8, 4


def _gan_fixture(seed=7):
    mx.random.seed(seed)
    gan = mx.mod.GANModule(
        models.dcgan_generator(ngf=_GAN_NF, nc=3),
        models.dcgan_discriminator(ndf=_GAN_NF),
        context=mx.cpu(), batch_size=_GAN_BS, code_shape=(_GAN_Z, 1, 1),
        data_shape=(3, 64, 64))
    gan.bind()
    gan.init_params()
    gan.init_optimizer()
    return gan


def _gan_state(gan):
    state = {}
    for tag, mod in (("g", gan.mod_g), ("d", gan.mod_d)):
        exe = mod._exec_group._exec
        inputs = set(mod.data_names) | set(mod.label_names or ())
        for n, v in exe.arg_dict.items():
            if n in inputs:  # data/label slots, not trained state
                continue
            state[f"{tag}.{n}"] = np.asarray(v._data, np.float32)
        for n, v in exe.aux_dict.items():
            state[f"{tag}.aux.{n}"] = np.asarray(v._data, np.float32)
    return state


def test_dcgan_fused_step_matches_reference_loop():
    """One fused G/D step under pinned latents reproduces the reference
    imperative loop's weights, aux state and published outputs (adam at
    t=1 is sign-SGD-like, so any ordering bug amplifies to full +/-lr
    weight diffs — exact agreement here pins the whole step ordering)."""
    rng = np.random.RandomState(3)
    real_np = (rng.rand(_GAN_BS, 3, 64, 64).astype(np.float32) * 2 - 1)
    lat_np = rng.randn(_GAN_BS, _GAN_Z, 1, 1).astype(np.float32)

    gan_f = _gan_fixture()
    b_f = gan_f.train_window(mx.nd.array(real_np),
                             latents=[mx.nd.array(lat_np)])
    fused = _gan_state(gan_f)
    outs_f = [o.asnumpy() for o in b_f.outputs]

    gan_s = _gan_fixture()
    b_s = gan_s._serial_window([mx.nd.array(real_np)],
                               [mx.nd.array(lat_np)])
    serial = _gan_state(gan_s)
    outs_s = [o.asnumpy() for o in b_s.outputs]

    assert fused.keys() == serial.keys()
    for key in fused:
        np.testing.assert_allclose(fused[key], serial[key], rtol=1e-4,
                                   atol=1e-5, err_msg=key)
    for a, b in zip(outs_f, outs_s):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def _steady_dcgan(monkeypatch):
    gan = _gan_fixture()
    real = mx.nd.array(
        np.random.RandomState(5).rand(_GAN_BS, 3, 64, 64).astype(np.float32))
    tm.reset()
    gan.train_window(real, 2).wait()
    assert _compiles()[1] > 0  # the plan-compile counter fires on warmup

    tm.reset()
    for _ in range(3):
        last = gan.train_window(real, 2)
        last.wait()
    assert tm.counter("executor.fused_plan_hit").value == 3
    assert tm.counter("gan.window").value == 3
    return 0, _outputs_of(last)


# ---------------------------------------------------------------------------
# SSD through fit's window branch


def _mini_ssd_train_sym(num_classes=2):
    """The SSD loss head (multibox_layer → MultiBoxTarget → multi-loss
    Group, verbatim from models/ssd.py's get_symbol_train tail) on a
    3-conv trunk: the fit-window invariants exercise the SAME detection
    path — in-graph target assignment, hard negative mining, the Group of
    heterogeneous losses — without the VGG16 compile bill."""
    s = mx.sym
    body = s.Variable("data")
    feats = []
    for i, nf in enumerate((8, 16, 32)):
        body = s.Activation(
            s.Convolution(body, num_filter=nf, kernel=(3, 3),
                          stride=(2, 2), pad=(1, 1), name=f"trunk_{i}"),
            act_type="relu")
        feats.append(body)
    loc_preds, cls_preds, anchor_boxes = models.ssd.multibox_layer(
        feats[-2:], num_classes,
        sizes=[(0.2, 0.272), (0.54, 0.619)],
        ratios=[(1, 2, 0.5), (1, 2, 0.5)])
    tmp = s.MultiBoxTarget(
        anchor_boxes, s.Variable("label"), cls_preds,
        overlap_threshold=0.5, ignore_label=-1, negative_mining_ratio=3,
        minimum_negative_samples=0, negative_mining_thresh=0.5,
        variances=(0.1, 0.1, 0.2, 0.2), name="multibox_target")
    cls_prob = s.SoftmaxOutput(
        cls_preds, tmp[2], ignore_label=-1, use_ignore=True,
        multi_output=True, normalization="valid", name="cls_prob")
    loc_loss = s.MakeLoss(
        s.smooth_l1(tmp[1] * (loc_preds - tmp[0]), scalar=1.0,
                    name="loc_loss_"),
        grad_scale=1.0, normalization="valid", name="loc_loss")
    cls_label = s.MakeLoss(tmp[2], grad_scale=0.0, name="cls_label")
    return s.Group([cls_prob, loc_loss, cls_label])


def _steady_fit(monkeypatch, net, data, label, label_name="softmax_label",
                metric="acc", **optimizer_params):
    """``Module.fit`` over fused 2-step windows, two in flight, for two
    epochs of four batches: the compile epoch is discarded, and the device
    metric drains once, at the steady epoch's end."""
    monkeypatch.setenv("MXNET_TRAIN_WINDOW", "2")
    monkeypatch.setenv("MXNET_DISPATCH_DEPTH", "2")
    monkeypatch.setenv("MXNET_DEVICE_PREFETCH", "1")
    it = mx.io.NDArrayIter({"data": data}, {label_name: label},
                           batch_size=len(data) // 4,
                           last_batch_handle="discard")
    mod = mx.mod.Module(net, data_names=("data",), label_names=(label_name,),
                        context=mx.cpu())
    warm = []

    def epoch_cb(epoch, sym=None, arg=None, aux=None):
        if epoch == 0:
            warm.append(_compiles())
            tm.reset()

    metric = mx.metric.create(metric)
    tm.reset()
    mod.fit(it, eval_metric=metric, optimizer="sgd",
            optimizer_params=dict(momentum=0.9, **optimizer_params),
            initializer=mx.init.Xavier(), num_epoch=2,
            epoch_end_callback=epoch_cb)
    assert warm[0][1] > 0  # the plan-compile counter fired while warming
    return 1, lambda: [metric.get()[1]]


def _steady_classifier(build, shape, classes, **build_kw):
    def steady(monkeypatch):
        rng = np.random.RandomState(0)
        n = 4 * shape[0]
        return _steady_fit(
            monkeypatch, build(num_classes=classes, **build_kw),
            rng.uniform(-1, 1, (n,) + shape[1:]).astype(np.float32),
            rng.randint(0, classes, (n,)).astype(np.float32),
            learning_rate=0.01)
    return steady


def _steady_ssd(monkeypatch):
    """The multi-loss SSD Group rides fit's fused-window pipeline, with the
    device-resident Loss metric."""
    bs, size, max_obj = 2, 32, 3
    rng = np.random.RandomState(0)
    n = bs * 4
    data = rng.uniform(-1, 1, (n, 3, size, size)).astype(np.float32)
    label = np.full((n, max_obj, 5), -1.0, np.float32)
    for i in range(n):
        x1, y1 = rng.uniform(0, 0.4, 2)
        label[i, 0] = [rng.randint(0, 2), x1, y1, x1 + 0.4, y1 + 0.4]
    return _steady_fit(monkeypatch, _mini_ssd_train_sym(num_classes=2), data,
                       label, label_name="label",
                       metric=mx.metric.Loss(name="ssd_loss"),
                       learning_rate=0.002)


_STEADY = {
    "mlp": _steady_classifier(models.mlp, (8, 784), 10),
    "lenet": _steady_classifier(models.lenet, (8, 1, 28, 28), 10),
    "resnet-50": _steady_classifier(models.resnet, (2, 3, 64, 64), 10,
                                    num_layers=50, image_shape="3,64,64"),
    "lstm-ptb": _steady_lstm,
    "ssd": _steady_ssd,
    "dcgan": _steady_dcgan,
}


@pytest.mark.parametrize("workload", sorted(_STEADY))
def test_steady_state_compiles_nothing_and_syncs_nothing(workload,
                                                         monkeypatch):
    """Every BASELINE workload, once its programs are warm: no compile, no
    per-batch host sync (a ``fit`` drains its device metric once an
    epoch), finite outputs."""
    drains, outputs = _STEADY[workload](monkeypatch)
    assert _compiles() == (0, 0), f"steady {workload} recompiled"
    assert _sync_counts() == dict(dict.fromkeys(_SYNC_COUNTERS, 0),
                                  **{"metric.drain_sync": drains})
    for out in outputs():
        assert np.all(np.isfinite(out)), workload


# ---------------------------------------------------------------------------
# bf16 recipes


def test_bf16_recipes_train_finite():
    """The bf16 recipe nets must TRAIN without NaN/inf through the fused
    K-step window (low-precision trunk, f32 loss/update math) — the
    in-process mirror of the suite record's `train_outputs_finite`
    probe."""
    bs = 8
    for build, shape in ((models.mlp, (bs, 784)),
                         (models.lenet, (bs, 1, 28, 28))):
        net = build(num_classes=10, dtype="bfloat16")
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.bind(data_shapes=[mx.io.DataDesc("data", shape, "bfloat16")],
                 label_shapes=[mx.io.DataDesc("softmax_label", (bs,))])
        mod.init_params(initializer=mx.init.Xavier())
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        rng = np.random.RandomState(0)
        batch = mx.io.DataBatch(
            data=[mx.nd.array(rng.rand(*shape).astype(np.float32),
                              dtype="bfloat16")],
            label=[mx.nd.array(
                rng.randint(0, 10, (bs,)).astype(np.float32))])
        boundary = mod.train_window(batch, 2, publish_grads=False)
        boundary.wait()
        for out in boundary.outputs:
            arr = np.asarray(out._data, dtype=np.float32)
            assert np.all(np.isfinite(arr)), build.__name__


# ---------------------------------------------------------------------------
# FLOPs estimator + zoo registry


def test_flops_estimator_closed_forms():
    from mxnet_tpu.models import recipe

    # dense closed form: the MLP is exactly its three FC weight products
    mlp_sym = models.mlp(num_classes=10)
    expected = 784 * 128 + 128 * 64 + 64 * 10
    assert recipe.estimate_flops(mlp_sym, data=(4, 784)) == pytest.approx(
        expected, rel=1e-6)

    # MAC convention anchor: ResNet-50 @224 is the published ~4.1 GFLOPs
    resnet50 = models.resnet(num_classes=1000, num_layers=50,
                             image_shape="3,224,224")
    g = recipe.estimate_flops(resnet50, data=(1, 3, 224, 224))
    assert 3.8e9 < g < 4.3e9, g

    # VGG-16 @224 (~15.3e9) must land above ResNet-50 — conv cost scales
    vgg16 = models.vgg(num_classes=1000, num_layers=16)
    v = recipe.estimate_flops(vgg16, data=(1, 3, 224, 224))
    assert 14e9 < v < 17e9, v

    # estimate is per SAMPLE: batch size must not change it
    g8 = recipe.estimate_flops(resnet50, data=(8, 3, 224, 224))
    assert g8 == pytest.approx(g, rel=1e-3)


def test_flops_estimator_grouped_depthwise():
    from mxnet_tpu.models import recipe

    # grouped closed form: out_positions x num_filter x (in_ch/g) x kh x kw
    data = mx.sym.Variable("data")
    g4 = mx.sym.Convolution(data, num_filter=32, kernel=(3, 3), pad=(1, 1),
                            num_group=4, no_bias=True, name="g4")
    assert recipe.estimate_flops(g4, data=(1, 16, 8, 8)) == pytest.approx(
        8 * 8 * 32 * (16 // 4) * 3 * 3, rel=1e-6)

    # depthwise (num_group == channels): one input channel per filter
    dw = mx.sym.Convolution(data, num_filter=16, kernel=(3, 3), pad=(1, 1),
                            num_group=16, no_bias=True, name="dw")
    assert recipe.estimate_flops(dw, data=(1, 16, 8, 8)) == pytest.approx(
        8 * 8 * 16 * 1 * 3 * 3, rel=1e-6)

    # ResNeXt-50 32x4d @224: the published ~4.23 GFLOPs. An estimator
    # that ignores num_group overcounts the grouped bottlenecks ~8x
    rx = models.resnext(num_classes=1000, num_layers=50,
                        image_shape="3,224,224")
    g = recipe.estimate_flops(rx, data=(1, 3, 224, 224))
    assert g == pytest.approx(4.2305e9, rel=0.02), g


def test_score_symbol_list_is_shared():
    """examples/benchmark_score.py sweeps the registry
    (models.SCORE_SYMBOLS), not a list of its own."""
    with open(os.path.join(_ROOT, "examples", "benchmark_score.py")) as f:
        assert "SCORE_SYMBOLS" in f.read()


def test_zoo_registry_covers_published_table():
    assert len(models.SCORE_SYMBOLS) >= 14
    for net in models.SCORE_SYMBOLS:
        sym = models.zoo.get_symbol(net)
        assert sym.list_arguments(), net
    with pytest.raises(ValueError):
        models.zoo.get_symbol("not-a-net")
