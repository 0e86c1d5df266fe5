"""Imperative autograd.

Reference: ``src/ndarray/autograd.h:54-119`` (``AutogradRuntime`` building an
``AGNode`` tape of recorded imperative ops) and the python surface
``mx.contrib.autograd`` / ``mx.autograd``. The reference replays the tape by
constructing an nnvm graph and binding a backward executor; here the tape is
replayed through ``jax.vjp`` — the recorded ops are pure jax functions, so
the whole backward is one XLA-differentiated computation.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field

from .base import MXNetError
from .ops.registry import OpMode, platform_of

_state = threading.local()


def _st():
    if not hasattr(_state, "recording"):
        _state.recording = False
        _state.training = False
        _state.tape = []
        _state.marked = {}  # id(nd) -> (nd, grad_req)
    return _state


@dataclass
class TapeEntry:
    opdef: object
    params: dict
    inputs: list
    outputs: list
    rng: object = None
    # values of inputs AT RECORD TIME — replay must not read a handle's
    # current (possibly later-mutated) data for inputs outside the env
    input_values: list = field(default_factory=list)


def is_recording():
    return _st().recording


def is_training():
    return _st().training


def set_recording(is_record):
    st = _st()
    prev = st.recording
    st.recording = bool(is_record)
    return prev


def set_training(train_mode):
    st = _st()
    prev = st.training
    st.training = bool(train_mode)
    return prev


class _RecordingStateScope:
    def __init__(self, is_record, train_mode):
        self._enter_is_record = is_record
        self._enter_train_mode = train_mode
        self._prev_is_record = None
        self._prev_train_mode = None

    def __enter__(self):
        if self._enter_is_record is not None:
            self._prev_is_record = set_recording(self._enter_is_record)
        if self._enter_train_mode is not None:
            self._prev_train_mode = set_training(self._enter_train_mode)
        return self

    def __exit__(self, ptype, value, trace):
        if self._enter_is_record is not None:
            set_recording(self._prev_is_record)
        if self._enter_train_mode is not None:
            set_training(self._prev_train_mode)


def record(train_mode=True):
    """``with autograd.record():`` — record imperative ops for backward."""
    return _RecordingStateScope(True, train_mode)


def pause(train_mode=False):
    return _RecordingStateScope(False, train_mode)


def train_mode():
    return _RecordingStateScope(None, True)


def predict_mode():
    return _RecordingStateScope(None, False)


def mark_variable(nd, grad_req="write"):
    """Mark an NDArray as requiring gradient (reference MarkVariables)."""
    st = _st()
    st.marked[id(nd)] = (nd, grad_req)


def mark_variables(variables, gradients=None, grad_reqs="write"):
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for i, v in enumerate(variables):
        mark_variable(v, grad_reqs[i])
        if gradients is not None:
            v._grad = gradients[i]


def record_op(opdef, params, inputs, outputs, rng=None):
    st = _st()
    if st.recording:
        st.tape.append(
            TapeEntry(
                opdef, params, list(inputs), list(outputs), rng,
                [nd._data for nd in inputs],
            )
        )


def backward(heads, head_grads=None, retain_graph=False, train_mode=True):
    """Compute gradients of heads wrt all marked variables.

    Replays the tape as one jax function of the leaf values and calls
    ``jax.vjp`` — a single traced backward, no per-op dispatch.
    """
    import jax
    import jax.numpy as jnp

    st = _st()
    tape = st.tape
    leaves = [nd for nd, _req in st.marked.values()]
    if not leaves:
        raise MXNetError("autograd.backward: no variables marked for gradient")

    leaf_ids = {id(nd): i for i, nd in enumerate(leaves)}
    captured = {}  # id -> current value for non-leaf inputs

    def replay(leaf_vals):
        env = {}
        for nd, v in zip(leaves, leaf_vals):
            env[id(nd)] = v
        for entry in tape:
            ins = []
            for nd, recorded in zip(entry.inputs, entry.input_values):
                ins.append(env.get(id(nd), recorded))
            mode = OpMode(is_train=train_mode, rng=entry.rng,
                          platform=platform_of(entry.input_values))
            outs, _aux = entry.opdef.apply(ins, entry.params, mode)
            for nd, o in zip(entry.outputs, outs):
                env[id(nd)] = o
        return [env.get(id(h), h._data) for h in heads]

    leaf_vals = [nd._data for nd in leaves]
    outs, vjp_fn = jax.vjp(lambda lv: replay(lv), leaf_vals)
    if head_grads is None:
        cots = [jnp.ones_like(o) for o in outs]
    else:
        cots = [
            (g._data if g is not None else jnp.ones_like(o))
            for g, o in zip(head_grads, outs)
        ]
    (grads,) = vjp_fn(cots)
    from .ndarray import NDArray

    for nd, g in zip(leaves, grads):
        req = st.marked[id(nd)][1]
        if req == "null":
            continue
        if nd._grad is None:
            nd._grad = NDArray(g)
        elif req == "add":
            nd._grad._data = nd._grad._data + g
        else:
            nd._grad._data = g
    if not retain_graph:
        st.tape = []


def grad(heads, variables, head_grads=None, retain_graph=None, create_graph=False,
         train_mode=True):
    """Return gradients of heads wrt variables without touching .grad."""
    import jax
    import jax.numpy as jnp

    st = _st()
    tape = st.tape
    var_list = list(variables)

    def replay(leaf_vals):
        env = {id(nd): v for nd, v in zip(var_list, leaf_vals)}
        for entry in tape:
            ins = [
                env.get(id(nd), rec)
                for nd, rec in zip(entry.inputs, entry.input_values)
            ]
            mode = OpMode(is_train=train_mode, rng=entry.rng,
                          platform=platform_of(entry.input_values))
            outs, _aux = entry.opdef.apply(ins, entry.params, mode)
            for nd, o in zip(entry.outputs, outs):
                env[id(nd)] = o
        return [env.get(id(h), h._data) for h in heads]

    outs, vjp_fn = jax.vjp(lambda lv: replay(lv), [nd._data for nd in var_list])
    if head_grads is None:
        cots = [jnp.ones_like(o) for o in outs]
    else:
        cots = [g._data for g in head_grads]
    (grads,) = vjp_fn(cots)
    from .ndarray import NDArray

    return [NDArray(g) for g in grads]


# reference compatibility: mx.contrib.autograd exposed these names
compute_gradient = backward
