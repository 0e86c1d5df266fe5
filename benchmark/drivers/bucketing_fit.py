"""Driver ``bucketing_fit``: ``BucketingModule.fit`` over
``BucketSentenceIter``.

One *cycle* is one whole pass of the iterator: every seed's pass holds the
same multiset of sentence lengths (``lib/sentences.py``) and each bucket a
whole number of batches, so every cycle retires the same real tokens through
the same programs, in another order. A slice is ``cycles_per_slice`` cycles,
and a warm-up cycle is one pass, so warm-up has seen every bucket. The pass
is repeated inside ONE ``fit`` epoch (the iterator re-shuffles itself at the
end of each pass, as it does at an epoch end), so ``fit``'s own epoch
boundary falls after the window.

Parameters of the traffic file: ``batch_size``, ``length_mean``,
``length_std``, ``zipf_a``, ``batches_per_cycle``, ``cycles_per_slice``,
``min_slices``, ``trace_steps``, ``kvstore`` and
``reference_check.{batch,seq_len}`` (``env`` is run.py's).
"""

from __future__ import annotations

from benchmark.drivers import _train
from benchmark.lib import gen, sentences
from benchmark.lib import harness as hx


class CycledIter(_train.StoppableIter):
    """Passes of a ``BucketSentenceIter`` presented as one epoch."""

    def __init__(self, inner):
        self.inner = inner
        self.batch_size = inner.batch_size
        self.default_bucket_key = inner.default_bucket_key
        self.passes = 0

    @property
    def provide_data(self):
        return self.inner.provide_data

    @property
    def provide_label(self):
        return self.inner.provide_label

    def __iter__(self):
        return self

    def __next__(self):
        if self.stop:
            raise StopIteration
        try:
            return self.inner.next()
        except StopIteration:
            self.passes += 1
            self.inner.reset()
            return self.inner.next()

    next = __next__

    def reset(self):
        pass


def run(run):
    mx = run["mx"]
    cfg, traffic, builder = run["config"], run["traffic"], run["builder"]
    batch, buckets = traffic["batch_size"], cfg["buckets"]
    ctxs = [run["ctx_of"](i) for i in range(run["cell"]["chips"])]
    sents = sentences.make(
        run["args"].seed, buckets=buckets, mean=traffic["length_mean"],
        std=traffic["length_std"], batches=traffic["batches_per_cycle"],
        batch_size=batch, vocab_size=cfg["vocab_size"],
        zipf_a=traffic["zipf_a"])
    inner = mx.rnn.BucketSentenceIter(
        sents, batch, buckets=buckets, invalid_label=0,
        seed=int(gen.seed_word(run["args"].seed)))
    cycle = len(inner._plan)
    if cycle != traffic["batches_per_cycle"]:
        raise hx.BenchError(f"one pass is {cycle} batches, the traffic file "
                            f"says {traffic['batches_per_cycle']}")
    per_bucket, _ = sentences.bucket_batches(
        buckets, traffic["length_mean"], traffic["length_std"], cycle)
    tokens_per_cycle = sum(len(s) for s in sents)
    positions_per_cycle = sum(n * batch * b
                              for n, b in zip(per_bucket, buckets))

    gen_sym, state_names = builder.sym_gen(cfg, mx)
    mod = mx.mod.BucketingModule(
        sym_gen=gen_sym, default_bucket_key=inner.default_bucket_key,
        state_names=state_names, context=ctxs)
    arg_params = _params(run, gen_sym, state_names, batch, max(buckets))

    it = CycledIter(inner)
    slice_steps = cycle * traffic["cycles_per_slice"]
    session = _train.Session(
        run, mod, it, slice_steps=slice_steps, cycle_steps=cycle,
        min_slices=traffic["min_slices"], units_of=lambda first, n: tokens_per_cycle * n // cycle,
        trace_steps=traffic["trace_steps"], resident=False)
    opt = dict(cfg["optimizer"])
    mod.fit(it, num_epoch=1, eval_metric=mx.metric.Perplexity(0),
            kvstore=traffic["kvstore"], optimizer=opt.pop("name"),
            optimizer_params=opt, arg_params=arg_params, aux_params={},
            batch_end_callback=session.callback)
    session.finish(lambda steps: tokens_per_cycle * steps // cycle)
    run["obs"].update(pad_tokens=positions_per_cycle - tokens_per_cycle,
                      all_tokens=positions_per_cycle)
    run["end_to_end"] = {"train_tokens_per_s": run["summary"]["mean_rate"],
                         "setup_s": run["setup_s"]}
    if run["tracer"].on:
        reference_check(run, ctxs[0], **traffic["reference_check"])


def _params(run, gen_sym, state_names, batch, seq_len):
    sym = gen_sym(seq_len)[0]
    shapes = run["builder"].input_shapes(run["config"], batch, seq_len)
    shapes.update({n: (batch, run["config"]["num_hidden"])
                   for n in state_names})
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    fed = set(shapes) | set(state_names)
    params = {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in fed}
    return _train.make_params(run, params, {})[0]


def reference_check(run, ctx, batch, seq_len):
    """Outside the window: the program's first training step (bound with
    dropout 0) on seeded token ids against the plain float32 reference."""
    mx, jax = run["mx"], run["jax"]
    import jax.numpy as jnp

    cfg, builder = run["config"], run["builder"]
    ref = hx.config_module("reference", cfg["name"])
    gen_sym, state_names = builder.sym_gen(cfg, mx, dropout=0.0)
    shapes = builder.input_shapes(cfg, batch, seq_len)
    ids = gen.make_leaves(
        jax, run["args"].seed + 1,
        [("data", shapes["data"], "float32", "randint",
          float(cfg["vocab_size"] - 1), 1.0)])["data"]
    label = jnp.concatenate([ids[:, 1:], jnp.zeros((batch, 1))], axis=1)
    mod = mx.mod.Module(gen_sym(seq_len)[0], data_names=("data",),
                        label_names=("softmax_label",),
                        state_names=state_names, context=[ctx])
    mod.bind(data_shapes=[mx.io.DataDesc("data", shapes["data"])],
             label_shapes=[mx.io.DataDesc("softmax_label",
                                          shapes["softmax_label"])],
             for_training=True)
    arg_params = _params(run, gen_sym, state_names, batch, seq_len)
    mod.init_params(arg_params=arg_params, aux_params={})
    nd = mx.nd.NDArray
    mod.forward_backward(mx.io.DataBatch(data=[nd(ids)], label=[nd(label)]))
    prob = mod.get_outputs()[0]._data
    exe = mod._exec_group.execs[0]
    grads = [exe.grad_dict[n]._data for n in mod._param_names]
    lab = label.reshape(-1).astype(jnp.int32)
    picked = jnp.take_along_axis(prob, lab[:, None], 1)
    rows = batch * seq_len
    got = {
        "loss": float(-jnp.mean(jnp.log(jnp.maximum(picked, 1e-30)))),
        # SoftmaxOutput's gradient is of the SUMMED loss: / positions
        "grad_norm": float(jnp.sqrt(sum(jnp.sum(g ** 2) for g in grads)))
        / rows,
    }
    want = ref.first_step(jax, cfg, {n: a._data for n, a in
                                     arg_params.items()}, ids, label)
    _train.check_against_reference(run, cfg["name"] + ".first_step", got,
                                   want, ref.TOLERANCES)
