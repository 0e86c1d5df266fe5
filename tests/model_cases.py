"""What the test file of a model configuration shares with the others: a
tiny model against its plain reference ``benchmark/reference/<name>.py``.

A new configuration's ``tests/test_<model>.py`` supplies the tiny
configuration (``TINY``: the published file's keys at widths of 32-64), how
its parameters are drawn (a rule ``name -> (scale, mean)`` for
:func:`seeded_params`; :func:`gains_and_weights` is the common one), and its
mutations: functions ``(ref, monkeypatch)`` that each leave one piece of the
reference out. It gets the loaders, the seeded inputs, the program's first
step as the benchmark's driver reads it, and :func:`first_step_case`: ONE
bind, compile and run of the program and ONE evaluation of the plain
reference for a (dtype, batch), made a module-scoped fixture, so that a
mutation case pays only for the mutated reference.
"""

import functools
import importlib.util
import os
import types

import numpy as np

import mxnet_tpu as mx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=None)
def load(kind, name):
    """The module ``benchmark/<kind>/<name>.py`` (``configs``: the builder,
    ``reference``: the plain reference), executed once a process."""
    path = os.path.join(ROOT, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"{name.replace('-', '_').replace('.', '_')}_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def gains_and_weights(name, weight=0.3):
    """Gains normal(1, 0.1), every other parameter normal(0, ``weight``):
    far larger than a configuration's 0.02, which at 32-64 features is what
    makes every branch of a tiny model matter."""
    return (0.1, 1.0) if name.endswith("_gamma") else (weight, 0.0)


def seeded_params(sym, rule=gains_and_weights, seed=0, **shapes):
    """{name: float32 array} for every argument of ``sym`` but its inputs
    (``shapes``), drawn in argument order from one ``RandomState(seed)``:
    ``rule(name)`` gives ``(scale, mean)`` of a normal, or ``("uniform",
    low, high)``."""
    rs = np.random.RandomState(seed)
    arg_shapes, _, _ = sym.infer_shape(**shapes)
    out = {}
    for name, shape in zip(sym.list_arguments(), arg_shapes):
        if name in shapes:
            continue
        how = rule(name)
        if how[0] == "uniform":
            draw = rs.uniform(how[1], how[2], shape)
        else:
            draw = rs.randn(*shape) * how[0] + how[1]
        out[name] = draw.astype(np.float32)
    return out


def seeded_tokens(seed=1, *, batch, seq_len, vocab, pads=0):
    """Ids 1..vocab-1 (``pads`` pad positions, id 0, at the end of row 0)
    and the next-token labels an iterator would feed."""
    rs = np.random.RandomState(seed)
    ids = rs.randint(1, vocab, size=(batch, seq_len)).astype(np.float32)
    if pads:
        ids[0, -pads:] = 0
    label = np.concatenate([ids[:, 1:], np.zeros((batch, 1), np.float32)], 1)
    return ids, label


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def bind_op(sym, names, inputs):
    return sym.bind(mx.cpu(), {n: mx.nd.array(a) for n, a in
                               zip(names, inputs)},
                    args_grad={n: mx.nd.zeros(a.shape) for n, a in
                               zip(names, inputs)})


def bound(sym, params, ids, label):
    exe = sym.simple_bind(mx.cpu(), data=ids.shape, softmax_label=label.shape)
    for n, a in params.items():
        exe.arg_dict[n][:] = a
    exe.arg_dict["data"][:] = ids
    exe.arg_dict["softmax_label"][:] = label
    return exe


def program_first_step(sym, params, ids, label):
    """(probabilities, {name: gradient / rows}) of one forward/backward."""
    exe = bound(sym, params, ids, label)
    prob = exe.forward(is_train=True)[0].asnumpy()
    exe.backward()
    return prob, {n: exe.grad_dict[n].asnumpy() / ids.size for n in params}


def reading(prob, grads, label):
    """What the benchmark's driver reads of a first step: loss from the
    probabilities, gradient norm over rows."""
    lab = label.reshape(-1).astype(int)
    picked = prob[np.arange(lab.size), lab]
    return {"loss": float(-np.mean(np.log(np.maximum(picked, 1e-30)))),
            "grad_norm": float(np.sqrt(sum(
                np.sum(np.square(g, dtype=np.float64))
                for g in grads.values())))}


def first_step_of_program(sym, params, ids, label):
    return reading(*program_first_step(sym, params, ids, label), label)


def misses(got, want, tolerances):
    return [k for k, tol in tolerances.items()
            if abs(got[k] - want[k]) / abs(want[k]) > tol]


def reference_args(cfg, params, ids, label):
    """``(jax, cfg, leaves, ids, label)``: what a reference's ``first_step``,
    ``value_and_grads`` and ``losses`` take."""
    import jax
    import jax.numpy as jnp

    return (jax, cfg, {n: jnp.asarray(a) for n, a in params.items()},
            jnp.asarray(ids), jnp.asarray(label))


def first_step_case(ref, cfg, sym, params, ids, label):
    """One first step on seeded inputs, computed once: the program's
    (``prob``, ``grads`` and ``got``, the driver's reading of them), the
    reference's arguments (``args``) and the plain reference's reading
    (``want``). Make it a module-scoped fixture; a mutation then evaluates
    only ``ref.first_step(*case.args)`` under its patch."""
    prob, grads = program_first_step(sym, params, ids, label)
    args = reference_args(cfg, params, ids, label)
    return types.SimpleNamespace(
        params=params, ids=ids, label=label, prob=prob, grads=grads,
        got=reading(prob, grads, label), args=args,
        want=ref.first_step(*args))
