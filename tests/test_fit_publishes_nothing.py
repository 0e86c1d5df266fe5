"""``fit`` reads no gradients and says so (PR 54): each of its per-batch
steps calls ``update(publish_grads=False)`` (``BaseModule._update_unread``),
as its windows have since PR 6, so the fused step returns no float32 set of
them. The weights move exactly as under a loop that publishes; a read of
``grad_dict`` after a ``fit`` step raises and says where to read them
instead; ``update()`` by hand and ``fit(monitor=...)`` leave them readable."""

import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.telemetry as tm
from mxnet_tpu.base import MXNetError

ROWS = 4
STEPS = 5
ADAM = dict(learning_rate=0.01, beta1=0.9, beta2=0.95, epsilon=1e-8)


def _net():
    h = mx.sym.Embedding(mx.sym.Variable("data"), input_dim=10, output_dim=6,
                         name="emb")
    h = mx.sym.sum(h, axis=1)  # (rows, 6) whatever the bucket's length
    h = mx.sym.FullyConnected(h, num_hidden=8, name="fc0")
    h = mx.sym.BatchNorm(h, fix_gamma=False, name="bn0")
    h = mx.sym.Activation(h, act_type="relu", name="act0")
    h = mx.sym.FullyConnected(h, num_hidden=4, name="out")
    return mx.sym.SoftmaxOutput(h, name="softmax")


class _Kind:
    """How one kind of module is built and looked into; ``lengths`` are the
    sequence lengths of the batches, one a step."""

    def __init__(self, name, lengths):
        self.name, self.lengths = name, lengths

    def __repr__(self):
        return self.name

    def module(self):
        if self.name == "Module":
            return mx.mod.Module(_net(), context=mx.cpu())
        return mx.mod.BucketingModule(
            lambda t: (_net(), ("data",), ("softmax_label",)),
            default_bucket_key=8, context=mx.cpu())

    def executors(self, mod):
        if self.name == "Module":
            return [mod._exec_group._exec]
        return [m._exec_group._exec for m in mod._buckets.values()]

    def last(self, mod):
        """The ``Module`` that ran the last step."""
        return mod if self.name == "Module" else mod._curr_module

    def current(self, mod):
        return self.last(mod)._exec_group._exec

    def updater(self, mod):
        return self.last(mod)._updater


KINDS = [_Kind("Module", (8,) * STEPS),
         _Kind("BucketingModule", (8, 4, 8, 4, 8))]


class _Batches(mx.io.DataIter):
    def __init__(self, lengths):
        super().__init__()
        rs = np.random.RandomState(3)
        self.batch_size, self.default_bucket_key = ROWS, 8
        self.provide_data = [mx.io.DataDesc("data", (ROWS, 8))]
        self.provide_label = [mx.io.DataDesc("softmax_label", (ROWS,))]
        self.batches = [
            mx.io.DataBatch(
                data=[mx.nd.array(rs.randint(0, 10, (ROWS, t))
                                  .astype(np.float32))],
                label=[mx.nd.array(rs.randint(0, 4, (ROWS,))
                                   .astype(np.float32))],
                bucket_key=t,
                provide_data=[mx.io.DataDesc("data", (ROWS, t))],
                provide_label=[mx.io.DataDesc("softmax_label", (ROWS,))])
            for t in lengths]
        self.at = 0

    def reset(self):
        self.at = 0

    def next(self):
        if self.at == len(self.batches):
            raise StopIteration
        self.at += 1
        return self.batches[self.at - 1]


def _start():
    """Seeded parameters and statistics, the same for every loop."""
    shapes, _, aux_shapes = _net().infer_shape(data=(ROWS, 8),
                                                softmax_label=(ROWS,))
    rs = np.random.RandomState(7)
    args = {n: mx.nd.array(rs.uniform(-0.5, 0.5, s).astype(np.float32))
            for n, s in zip(_net().list_arguments(), shapes)
            if n not in ("data", "softmax_label")}
    aux = {n: mx.nd.array((np.ones(s) if n.endswith("var")
                           else np.zeros(s)).astype(np.float32))
           for n, s in zip(_net().list_auxiliary_states(), aux_shapes)}
    return args, aux


def _fit(kind, **how):
    args, aux = _start()
    mod = kind.module()
    mod.fit(_Batches(kind.lengths), num_epoch=1, eval_metric="acc",
            optimizer="adam", optimizer_params=ADAM, arg_params=args,
            aux_params=aux, **how)
    return mod


def _by_hand(kind, **how):
    args, aux = _start()
    mod = kind.module()
    it = _Batches(kind.lengths)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(arg_params=args, aux_params=aux)
    mod.init_optimizer(optimizer="adam", optimizer_params=ADAM)
    for batch in it:
        mod.prepare(batch)  # as fit stages it: on the device, placed
        mod.forward_backward(batch)
        mod.update(**how)
    return mod


def _gradient_bytes(exe):
    return sum(int(np.prod(exe.arg_dict[n].shape)) * 4
               for n in exe._wrt_names)


def _leaves(state):
    if state is None:
        return []
    if isinstance(state, (tuple, list)):
        return [leaf for s in state for leaf in _leaves(s)]
    return [state.asnumpy()]


@pytest.mark.parametrize("kind", KINDS, ids=repr)
def test_fit_returns_no_gradients_from_its_fused_steps(kind):
    tm.reset()
    hand = _by_hand(kind, publish_grads=True)
    want = _gradient_bytes(kind.current(hand))
    assert tm.gauge("executor.published_grad_bytes").value == want > 0
    kept_publishing = tm.gauge("executor.program_kept_output_bytes").value

    tm.reset()
    mod = _fit(kind)
    assert tm.gauge("executor.published_grad_bytes").value == 0
    # smaller by the gradients, and by at most a pointer each in the table
    # of the program's outputs
    saved = kept_publishing - tm.gauge(
        "executor.program_kept_output_bytes").value
    assert want <= saved <= want + 8 * len(kind.current(mod)._wrt_names)
    # every step ran fused, and every bucket's plan is the one that
    # leaves the gradients out
    plans = [p for exe in kind.executors(mod)
             for p in exe._fused_plan.values()]
    assert len(plans) == len(set(kind.lengths))
    assert not any(p.key.publish for p in plans)
    assert sum(p.program.launches for p in plans) == STEPS


@pytest.mark.parametrize("kind", KINDS, ids=repr)
def test_a_read_of_grad_dict_after_fit_raises_and_names_fit(kind):
    mod = _fit(kind)
    for exe in kind.executors(mod):
        with pytest.raises(MXNetError, match=r"not published.*fit\(\)") as e:
            exe.grad_dict["out_weight"].asnumpy()
        # the two ways out
        assert "after backward() and before update()" in str(e.value)
        assert "update(publish_grads=True)" in str(e.value)
        # the handle still knows its shape without a value behind it
        assert exe.grad_dict["out_weight"].shape == (4, 8)
    # the first way out, on the module fit left behind
    exe = kind.current(mod)
    mod.forward_backward(_Batches(kind.lengths[-1:]).batches[0])
    assert exe.grad_dict["out_weight"].asnumpy().any()
    # and the second
    mod.update(publish_grads=True)
    assert exe.grad_dict["out_weight"].asnumpy().any()


@pytest.mark.parametrize("kind", KINDS, ids=repr)
def test_a_callback_that_reads_gradients_inside_fit_raises(kind):
    def read(param):
        kind.current(param.locals["self"]).grad_dict["out_weight"].asnumpy()

    with pytest.raises(MXNetError, match=r"fit\(\)"):
        _fit(kind, batch_end_callback=read)


@pytest.mark.parametrize("kind", KINDS, ids=repr)
def test_fit_moves_parameters_and_states_as_the_publishing_loop_does(kind):
    """Bit for bit: leaving an output out of the program changes nothing
    that the update computes."""
    hand, mod = _by_hand(kind, publish_grads=True), _fit(kind)
    for a, b in zip(hand.get_params(), mod.get_params()):
        assert sorted(a) == sorted(b) and a
        for n in a:
            assert np.array_equal(a[n].asnumpy(), b[n].asnumpy()), n
    want, got = kind.updater(hand).states, kind.updater(mod).states
    assert sorted(want) == sorted(got) and want
    for i in want:
        moments = list(zip(_leaves(want[i]), _leaves(got[i])))
        assert len(moments) == 2  # adam: mean and variance
        for w, g in moments:
            assert w.any() and np.array_equal(w, g), i
    assert kind.updater(hand).optimizer._index_update_count == \
        kind.updater(mod).optimizer._index_update_count


@pytest.mark.parametrize("kind", KINDS, ids=repr)
def test_update_by_hand_keeps_its_default_and_publishes(kind):
    tm.reset()
    mod = _by_hand(kind)
    exe = kind.current(mod)
    assert tm.gauge("executor.published_grad_bytes").value == \
        _gradient_bytes(exe)
    assert all(p.key.publish for e in kind.executors(mod)
               for p in e._fused_plan.values())
    assert exe.grad_dict["out_weight"].asnumpy().any()


@pytest.mark.parametrize("kind", KINDS, ids=repr)
def test_fit_with_a_monitor_leaves_gradients_readable(kind):
    """A monitor takes the step off the fused path; the gradients of the
    last backward stay where they were computed."""
    mod = _fit(kind, monitor=mx.monitor.Monitor(1000))
    exe = kind.current(mod)
    assert not exe._fused_plan
    assert exe.grad_dict["out_weight"].asnumpy().any()


def test_the_hook_of_a_module_that_knows_no_publication_is_update():
    """``SequentialModule`` and ``PythonModule`` take ``fit``'s word through
    the base class's hook, which is their own ``update()``."""
    from mxnet_tpu.module.base_module import BaseModule

    calls = []

    class Counting(BaseModule):
        def update(self):
            calls.append("update")

    Counting()._update_unread()
    assert calls == ["update"]
    for cls in (mx.mod.SequentialModule, mx.mod.PythonModule):
        assert cls._update_unread is BaseModule._update_unread
    for cls in (mx.mod.Module, mx.mod.BucketingModule):
        assert cls._update_unread is not BaseModule._update_unread


def test_a_sequential_module_still_trains_through_fit():
    first = mx.sym.Activation(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=8, name="s0_fc"),
        act_type="relu", name="s0_act")
    second = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=4, name="s1_fc"), name="softmax")
    seq = mx.mod.SequentialModule()
    seq.add(mx.mod.Module(first, label_names=None, context=mx.cpu()))
    seq.add(mx.mod.Module(second, context=mx.cpu()), take_labels=True,
            auto_wiring=True)
    rs = np.random.RandomState(0)
    x = rs.randn(32, 6).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=8)
    seq.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.init.Xavier())
    w = seq.get_params()[0]["s1_fc_weight"].asnumpy()
    assert np.isfinite(w).all() and w.any()


@pytest.mark.parametrize("eighths, same", [(7.9, True), (8.1, False)])
@pytest.mark.parametrize("kind", KINDS, ids=repr)
def test_over_the_eighth_fit_runs_the_program_update_alone_ran(
        monkeypatch, kind, eighths, same):
    """Where one set of gradients is over an eighth of the device, a loop of
    ``update()`` with no word already left them out: ``fit`` then runs that
    very program (the OLMoE and Kimi-Linear cells of the benchmark). Under
    the eighth the hand loop still publishes, and ``fit`` does not."""
    from mxnet_tpu import aot
    from mxnet_tpu.context import Context

    limit = int(_gradient_bytes(kind.current(_by_hand(kind))) * eighths)
    monkeypatch.setattr(Context, "memory_stats",
                        lambda self: {"bytes_limit": limit})
    lowered, resolve = {}, aot.AOTProgram._resolve

    def noting(self, args):
        # without locations and name stacks: who called is not the program
        lowered[self] = self.jit_fn.lower(*args).as_text()
        return resolve(self, args)

    monkeypatch.setattr(aot.AOTProgram, "_resolve", noting)

    def programs(mod):
        plans = sorted((p for exe in kind.executors(mod)
                        for p in exe._fused_plan.values()),
                       key=lambda p: p.program.label)
        return ([p.key._replace(mesh=None) for p in plans],
                [lowered[p.program] for p in plans])

    hand, mod = programs(_by_hand(kind)), programs(_fit(kind))
    assert len(mod[0]) == len(set(kind.lengths))
    assert not any(key.publish for key in mod[0])
    assert (hand == mod) == same
    assert all(key.publish != same for key in hand[0])


@pytest.mark.parametrize("kind", KINDS, ids=repr)
def test_fits_program_writes_each_donated_array_over_itself(
        monkeypatch, kind):
    """``jax.jit`` pairs donated arguments with outputs by shape, in order.
    A published gradient has its weight's shape, comes first, and takes the
    weight's buffer; the new weight takes the first moment's, and XLA
    copies every donated array before its first use (23 parameter-sized
    copies a step of the ZAYA1 cell, PERF.md section 6, PR 54). With
    nothing published a weight or a moment pairs with its own new value."""
    import re

    from mxnet_tpu import aot

    lowered, resolve = [], aot.AOTProgram._resolve

    def noting(self, args):
        lowered.append(self.jit_fn.lower(*args).as_text())
        return resolve(self, args)

    monkeypatch.setattr(aot.AOTProgram, "_resolve", noting)
    _fit(kind)
    fused = [text for text in lowered if '"result.params[0]"' in text]
    assert len(fused) == len(set(kind.lengths))
    for text in fused:
        head = text[text.index("func.func public @main("):]
        args, results = head[:head.index(") {\n")].split(") -> (", 1)
        names = re.findall(r'jax.result_info = "([^"]+)"', results)
        assert not any(name.startswith("result.grads") for name in names)
        pairs = [names[int(k)] for k in re.findall(
            r"tf.aliasing_output = (\d+)", args)]
        kinds = [name.split("[")[0].split(".")[1] for name in pairs]
        # seven weights and their fourteen moments, each over a new value
        # of its own kind and no output taken twice
        assert kinds.count("params") == 7 and kinds.count("states") == 14
        assert len(set(pairs)) == len(pairs)
