"""Driver ``fit``: ``Module.fit`` on one resident batch.

Parameters of the traffic file: ``batch_per_chip``, ``kvstore``,
``eval_metric``, ``slice_steps``, ``warmup_cycle_steps``, ``min_slices``,
``trace_steps`` and ``reference_check.batch`` (``env`` is run.py's). With more than one
chip the module is bound over ``[tpu(0), ..]`` and the batch is sharded as
the module's own input shardings say.
"""

from __future__ import annotations

from benchmark.drivers import _train
from benchmark.lib import gen
from benchmark.lib import harness as hx


class ResidentIter(_train.StoppableIter):
    """One batch that lives on the device, for one epoch without end."""

    def __init__(self, mx, data, label, data_desc, label_desc):
        self.batch_size = data.shape[0]
        self.provide_data, self.provide_label = [data_desc], [label_desc]
        self._batch = mx.io.DataBatch(
            data=[data], label=[label], pad=0, index=None,
            provide_data=self.provide_data, provide_label=self.provide_label)

    def __iter__(self):
        return self

    def __next__(self):
        if self.stop:
            raise StopIteration
        return self._batch

    next = __next__

    def reset(self):
        pass


def _sharding(jax, placement):
    if isinstance(placement, jax.sharding.Sharding):
        return placement
    return jax.sharding.SingleDeviceSharding(placement)


def _bound(run, sym, ctxs, batch, seed):
    """(module bound for training, its seeded inputs placed as its own
    input shardings say, seeded arg_params, aux_params, descriptors)."""
    mx, jax = run["mx"], run["jax"]
    cfg, builder = run["config"], run["builder"]
    dtype = cfg["compute_dtype"]
    shapes = builder.input_shapes(cfg, batch)
    descs = (mx.io.DataDesc("data", shapes["data"], dtype),
             mx.io.DataDesc("softmax_label", shapes["softmax_label"]))
    mod = mx.mod.Module(sym, context=ctxs)
    mod.bind(data_shapes=[descs[0]], label_shapes=[descs[1]],
             for_training=True)
    place = {n: _sharding(jax, p) for n, p in mod.input_shardings.items()}
    inputs = gen.make_leaves(
        jax, seed,
        [("data", shapes["data"], dtype, "uniform", 1.0, 0.0),
         ("softmax_label", shapes["softmax_label"], "float32", "randint",
          float(cfg["num_classes"]), 0.0)],
        out_shardings=place)
    arg_shapes, _, aux_shapes = sym.infer_shape(**shapes)
    arg_shapes = dict(zip(sym.list_arguments(), arg_shapes))
    aux_shapes = dict(zip(sym.list_auxiliary_states(), aux_shapes))
    params = {n: arg_shapes[n] for n in mod._param_names}
    arg_params, aux_params = _train.make_params(run, params, aux_shapes)
    return mod, inputs, arg_params, aux_params, descs


def run(run):
    mx = run["mx"]
    cfg, traffic = run["config"], run["traffic"]
    chips = run["cell"]["chips"]
    batch = traffic["batch_per_chip"] * chips
    ctxs = [run["ctx_of"](i) for i in range(chips)]
    sym = run["builder"].symbol(cfg, mx)
    mod, inputs, arg_params, aux_params, descs = _bound(
        run, sym, ctxs, batch, run["args"].seed)

    it = ResidentIter(mx, mx.nd.NDArray(inputs["data"]),
                      mx.nd.NDArray(inputs["softmax_label"]), *descs)
    session = _train.Session(
        run, mod, it, slice_steps=traffic["slice_steps"],
        cycle_steps=traffic["warmup_cycle_steps"],
        min_slices=traffic["min_slices"], units_of=lambda first, n: n * batch,
        trace_steps=traffic["trace_steps"], resident=True)
    opt = dict(cfg["optimizer"])
    mod.fit(it, num_epoch=1, eval_metric=traffic["eval_metric"],
            kvstore=traffic["kvstore"], optimizer=opt.pop("name"),
            optimizer_params=opt, arg_params=arg_params,
            aux_params=aux_params,
            batch_end_callback=session.callback)
    session.finish(lambda steps: steps * batch)
    run["end_to_end"] = {"train_samples_per_s": run["summary"]["mean_rate"],
                         "setup_s": run["setup_s"]}
    if run["tracer"].on:
        reference_check(run, sym, ctxs, traffic["reference_check"]["batch"])


def reference_check(run, sym, ctxs, batch):
    """Outside the window: the program's first training step on ``batch``
    seeded images, bound over the cell's own contexts (on four chips the
    batch is sharded over the dp mesh and the gradients come back reduced
    across it), against the plain float32 reference on the whole batch."""
    mx, jax = run["mx"], run["jax"]
    import jax.numpy as jnp

    cfg = run["config"]
    ref = hx.config_module("reference", cfg["name"])
    mod, inputs, arg_params, aux_params, _ = _bound(
        run, sym, ctxs, batch, run["args"].seed + 1)
    mod.init_params(arg_params=arg_params, aux_params=aux_params)
    nd = mx.nd.NDArray
    mod.forward_backward(mx.io.DataBatch(
        data=[nd(inputs["data"])], label=[nd(inputs["softmax_label"])]))
    prob = mod.get_outputs()[0]._data
    exe = mod._exec_group.execs[0]
    grads = [exe.grad_dict[n]._data for n in mod._param_names]
    lab = inputs["softmax_label"].astype(jnp.int32)
    picked = jnp.take_along_axis(prob.astype(jnp.float32), lab[:, None], 1)
    got = {
        "loss": float(-jnp.mean(jnp.log(jnp.maximum(picked, 1e-30)))),
        # SoftmaxOutput's gradient is of the SUMMED loss: / batch
        "grad_norm": float(jnp.sqrt(sum(
            jnp.sum(g.astype(jnp.float32) ** 2) for g in grads))) / batch,
    }
    # the reference runs on one chip, on the whole batch
    leaves, data, label = jax.device_put(
        ({n: a._data for n, a in {**arg_params, **aux_params}.items()},
         inputs["data"], inputs["softmax_label"]), run["devices"][0])
    want = ref.first_step(jax, cfg, leaves, data, label)
    run["checked_module"] = mod
    _train.check_against_reference(run, cfg["name"] + ".first_step", got,
                                   want, ref.TOLERANCES)
