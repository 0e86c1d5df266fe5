"""Operator registry — the TPU-native analogue of the reference's NNVM op
registry (``NNVM_REGISTER_OP`` + ``FCompute``/``FInferShape``/``FGradient``
attrs, reference ``include/mxnet/op_attr_types.h:32-73``).

Design
------
Each op is registered once with:

* ``fn(inputs, params, mode) -> (outputs, new_aux)`` — a **pure jax
  function**. This replaces both ``FCompute<cpu>`` and ``FCompute<gpu>``:
  XLA compiles it for whatever backend the arrays live on, and because it is
  pure jax, *gradients come for free* via jax autodiff — there is no
  ``FGradient`` table. Ops with non-standard gradients (SoftmaxOutput,
  MakeLoss, BlockGrad) encode them with ``jax.custom_vjp`` inside ``fn``.
* ``param_schema`` — typed parameters with defaults, the analogue of
  ``dmlc::Parameter`` structs; values parse from python natives *or* the
  string form used in Symbol attributes / saved JSON.
* ``fill_in_shapes(in_shapes, params)`` — optional completion of *unknown
  input* shapes (e.g. FullyConnected's weight from data + num_hidden). The
  reference writes a full bidirectional ``FInferShape`` per op; here output
  shapes/dtypes are derived from ``jax.eval_shape`` on ``fn`` itself, so
  inference can never disagree with execution, and only parameter-creating
  layers need custom code.

``mode`` carries execution-time state: ``is_train`` (static under jit) and a
jax PRNG ``rng`` for stochastic ops (dropout, samplers). Under jit the rng is
a traced input, making whole training steps reproducible from one seed.
"""

from __future__ import annotations

import functools
import threading
from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence

import numpy as np

from .. import telemetry as _tm
from ..base import MXNetError, np_dtype

_REQUIRED = object()

# Graph-level node attributes (AttrScope metadata consumed by the executor,
# not op parameters) — the reference keeps these in the generic nnvm attr
# dict: ctx_group drives PlaceDevice (graph_executor.cc:286-385), the others
# feed optimizer/memory passes.
_GRAPH_ATTRS = {"ctx_group", "lr_mult", "wd_mult", "force_mirroring",
                "mirror_stage"}


@dataclass(frozen=True)
class OpMode:
    """Execution-time context handed to every op ``fn``."""

    is_train: bool = False
    rng: object = None  # jax PRNG key, present iff opdef.need_rng
    # device layout for the conv stack: "NHWC" means the activation input
    # arrives channels-last and the op must lower channels-last (set only
    # for layout-aware ops — see ops/layout.py); None = logical NCHW
    layout: str = None
    # the platform the program is lowered for ("tpu", "cpu"): an executor
    # gives its context's, an imperative call that of its concrete operands
    # (``platform_of``); None (a bare traced call) = jax's default backend.
    # Read by the ops whose lowering is per platform: the four that have
    # Pallas kernels (MoE, RingAttention, GatedDeltaRule, CausalConv1D)
    platform: str = None


def platform_of(arrays):
    """The platform the first concrete jax array of ``arrays`` lives on: an
    imperative call's ``OpMode.platform``. None where none is concrete."""
    from jax.core import Tracer

    for a in arrays:
        if hasattr(a, "devices") and not isinstance(a, Tracer):
            return next(iter(a.devices())).platform
    return None


# The one name ``keep`` puts on a value and the executor's per-operator
# ``jax.checkpoint`` saves (``KeptResiduals``).
_KEPT = "mxnet_tpu.kept_residual"


def keep(values):
    """``values`` (an array or a tree of them) marked as residuals that an
    operator's backward reads and cannot have from its operands for free:
    under ``MXNET_BACKWARD_DO_MIRROR`` the executor's per-operator
    ``jax.checkpoint`` keeps them and recomputes everything unmarked
    (``KeptResiduals``). Anywhere else the mark lowers to nothing. What
    may be marked: values of the order of the operator's operands and
    outputs, never an interior that grows with a score tile, a
    vocabulary-wide softmax or a float32 copy of the trunk
    (docs/architecture.md)."""
    import jax
    from jax.ad_checkpoint import checkpoint_name

    return jax.tree.map(lambda x: checkpoint_name(x, _KEPT), values)


class KeptResiduals:
    """The ``jax.checkpoint`` policy that saves what ``keep`` marked and
    nothing else, and the count of the nodes it kept something for. One
    object serves every node of a program: jax answers a jitted function's
    second partial evaluation under the same policy from its cache (three
    alike layers are evaluated, and lowered, once), where a policy a node
    cost the Qwen3-Next cell 2.5 s of set-up (PERF.md section 6, PR 39).
    The price is that a cached answer does not ask the policy again, so
    ``kept_since`` also counts a node alike one already counted."""

    def __init__(self):
        import jax

        self._named = jax.checkpoint_policies.save_only_these_names(_KEPT)
        self.answers = 0     # times the policy has said yes
        self._alike = set()  # signatures of the nodes that kept something

    def __call__(self, prim, *avals, **params):
        saved = self._named(prim, *avals, **params)
        self.answers += bool(saved)
        return saved

    def kept_since(self, answers, op_name, params, ins):
        """Whether the node just lowered (operator ``op_name`` with
        ``params`` over ``ins``) kept a residual: the policy said yes
        since it had said yes ``answers`` times, or did for a node of the
        same operator, parameters and operand types."""
        alike = _signature("kept", op_name, params,
                           [x.shape for x in ins], [x.dtype for x in ins])
        if self.answers > answers and alike is not None:
            self._alike.add(alike)
        return self.answers > answers or alike in self._alike


class Param:
    """One typed op parameter (analogue of a dmlc::Parameter field)."""

    __slots__ = ("parse", "default", "doc")

    def __init__(self, parse, default=_REQUIRED, doc=""):
        self.parse = parse
        self.default = default
        self.doc = doc

    @property
    def required(self):
        return self.default is _REQUIRED


class OpDef:
    """A registered operator."""

    def __init__(
        self,
        name: str,
        fn: Callable,
        arg_names,
        param_schema: Optional[dict] = None,
        aux_names=None,
        fill_in_shapes: Optional[Callable] = None,
        infer_dtype: Optional[Callable] = None,
        num_outputs=1,
        num_visible_outputs=None,
        need_rng: bool = False,
        aliases: Sequence[str] = (),
        mutate: Sequence = (),
        is_loss: bool = False,
        doc: str = "",
        launch_counts: Optional[Callable] = None,
        launch_instruments: Sequence[str] = (),
    ):
        self.name = name
        self.fn = fn
        self._arg_names = arg_names
        self.param_schema = param_schema or {}
        self._aux_names = aux_names or []
        self.fill_in_shapes = fill_in_shapes
        self._infer_dtype = infer_dtype
        self._num_outputs = num_outputs
        self._num_visible_outputs = num_visible_outputs
        self.need_rng = need_rng
        self.aliases = tuple(aliases)
        # mutate: [(input_name, hidden_output_index)] — imperative calls
        # rebind these input handles to the given outputs (the analogue of
        # the reference's mutable-input declaration on optimizer ops).
        self.mutate = tuple(mutate)
        # loss layers: backward ignores the head gradient (the reference's
        # convention for SoftmaxOutput/MakeLoss/...); drives the implicit
        # head-grad decision in executor.backward() instead of a name list
        self.is_loss = bool(is_loss)
        self.doc = doc
        # what one launch of a train program counts for a node of this op:
        # ``launch_counts(ins, outs, params, platform) -> {instrument: int}``
        # over anything with ``.shape`` and ``.dtype`` (the operands and
        # results as the node was lowered, or ShapeDtypeStructs), asked by
        # the executor where it lowers the node and summed over the graph;
        # ``launch_instruments`` lists, with no graph, every name it may
        # return (docs/observability.md catalogues them). None: nothing
        self._launch_counts = launch_counts
        self.launch_instruments = tuple(launch_instruments)

    # --- introspection ---------------------------------------------------
    def arg_names(self, params) -> list:
        if callable(self._arg_names):
            return list(self._arg_names(params))
        return list(self._arg_names)

    def aux_names(self, params) -> list:
        if callable(self._aux_names):
            return list(self._aux_names(params))
        return list(self._aux_names)

    def num_outputs(self, params) -> int:
        if callable(self._num_outputs):
            return int(self._num_outputs(params))
        return int(self._num_outputs)

    def num_visible_outputs(self, params) -> int:
        if self._num_visible_outputs is None:
            return self.num_outputs(params)
        if callable(self._num_visible_outputs):
            return int(self._num_visible_outputs(params))
        return int(self._num_visible_outputs)

    def launch_counts(self, ins, outs, params, platform) -> dict:
        """What the op declared a launch counts for this node ({}: nothing),
        held to the instruments it listed."""
        if self._launch_counts is None:
            return {}
        counts = self._launch_counts(ins, outs, params, platform)
        unlisted = set(counts) - set(self.launch_instruments)
        if unlisted:
            raise MXNetError(f"op {self.name}: counts {sorted(unlisted)} "
                             "are not among its launch_instruments")
        return counts

    # --- params ----------------------------------------------------------
    def parse_params(self, raw: dict, strict: bool = True) -> dict:
        """Parse raw attrs (python values or strings) into typed params.

        Attribute keys wrapped in double underscores (``__ctx_group__`` etc.)
        are Symbol-level metadata, not op params, and are skipped. With
        ``strict`` (the op-creation path), unknown keys raise, mirroring
        dmlc::Parameter strictness on kwargs. Non-strict (node re-parse at
        execution, legacy JSON loads) ignores them: a node's attrs dict also
        carries free-form graph attributes — AttrScope user keys, reference
        attr sections — which the reference keeps outside the param struct.
        """
        out = {}
        for k, spec in self.param_schema.items():
            if k in raw and raw[k] is not None:
                try:
                    out[k] = spec.parse(raw[k])
                except (ValueError, SyntaxError) as e:
                    raise MXNetError(
                        f"op {self.name}: cannot parse param {k}={raw[k]!r}"
                    ) from e
            elif spec.required:
                raise MXNetError(f"op {self.name}: missing required param {k}")
            else:
                out[k] = spec.default
        if strict:
            for k in raw:
                if k not in self.param_schema and not (
                    k.startswith("__") and k.endswith("__")
                ) and k not in _GRAPH_ATTRS:
                    raise MXNetError(f"op {self.name}: unknown param {k!r}")
        return out

    # --- execution -------------------------------------------------------
    def apply(self, inputs, params, mode: OpMode):
        """Run ``fn``; normalise the result to ``(outputs, new_aux)`` lists."""
        res = self.fn(list(inputs), params, mode)
        if isinstance(res, tuple) and len(res) == 2 and isinstance(res[0], list):
            outputs, new_aux = res
        elif isinstance(res, (list, tuple)):
            outputs, new_aux = list(res), []
        else:
            outputs, new_aux = [res], []
        return outputs, new_aux

    # --- inference -------------------------------------------------------
    def infer_shape(self, in_shapes, params, in_dtypes=None):
        """Return (completed_in_shapes, out_shapes, aux_shapes).

        ``in_shapes`` covers args then aux, entries may be None (unknown).
        Answered from the process-wide memo where this signature was
        evaluated before (see :class:`_InferMemo`).
        """
        key = _signature(
            "shape", self.name, params,
            [None if s is None else tuple(s) for s in in_shapes],
            None if in_dtypes is None
            else [None if d is None else np_dtype(d) for d in in_dtypes])
        return _MEMO.answer(
            key, lambda: self._eval_shapes(in_shapes, params, in_dtypes))

    def _eval_shapes(self, in_shapes, params, in_dtypes):
        import jax

        names = self.arg_names(params) + self.aux_names(params)
        if len(in_shapes) != len(names):
            raise MXNetError(
                f"op {self.name}: expected {len(names)} inputs "
                f"({names}), got {len(in_shapes)} shapes"
            )
        shapes = list(in_shapes)
        if self.fill_in_shapes is not None:
            shapes = list(self.fill_in_shapes(shapes, params))
        if any(s is None for s in shapes):
            missing = [n for n, s in zip(names, shapes) if s is None]
            raise MXNetError(
                f"op {self.name}: cannot infer shapes of inputs {missing}"
            )
        if in_dtypes is None:
            in_dtypes = [None] * len(shapes)
        dtypes = self._complete_dtypes(in_dtypes, params)
        structs = [
            jax.ShapeDtypeStruct(tuple(s), np_dtype(d))
            for s, d in zip(shapes, dtypes)
        ]
        mode = OpMode(is_train=True, rng=_dummy_key_struct() if self.need_rng else None)
        _INFER_EVAL.inc()
        try:
            outs, new_aux = jax.eval_shape(
                lambda ins: self.apply(ins, params, mode), structs
            )
        except Exception as e:
            raise MXNetError(
                f"op {self.name}: shape inference failed for inputs "
                f"{list(zip(names, shapes))}: {e}"
            ) from e
        n_args = len(self.arg_names(params))
        arg_shapes = [tuple(s) for s in shapes[:n_args]]
        aux_shapes = [tuple(s) for s in shapes[n_args:]]
        out_shapes = [tuple(o.shape) for o in outs]
        return arg_shapes, out_shapes, aux_shapes

    def infer_dtype(self, in_dtypes, params):
        """Return (arg_dtypes, out_dtypes, aux_dtypes), memoised as
        :meth:`infer_shape` is."""
        key = _signature(
            "dtype", self.name, params,
            [None if d is None else np_dtype(d) for d in in_dtypes], None)
        return _MEMO.answer(
            key, lambda: self._eval_dtypes(in_dtypes, params))

    def _eval_dtypes(self, in_dtypes, params):
        import jax

        dtypes = self._complete_dtypes(list(in_dtypes), params)
        # Outputs via eval_shape on rank-consistent dummy shapes is not
        # possible without shapes; use scalar-broadcastable probe shapes.
        mode = OpMode(is_train=True, rng=_dummy_key_struct() if self.need_rng else None)
        _INFER_EVAL.inc()
        try:
            structs = [
                jax.ShapeDtypeStruct((), np_dtype(d)) for d in dtypes
            ]
            outs, _ = jax.eval_shape(
                lambda ins: self.apply(ins, params, mode), structs
            )
            out_dtypes = [np_dtype(o.dtype) for o in outs]
        except Exception:
            out_dtypes = [np_dtype(dtypes[0] if dtypes else "float32")] * self.num_outputs(params)
        n_args = len(self.arg_names(params))
        return dtypes[:n_args], out_dtypes, dtypes[n_args:]

    def _complete_dtypes(self, in_dtypes, params):
        if self._infer_dtype is not None:
            return [np_dtype(d) for d in self._infer_dtype(in_dtypes, params)]
        known = next((d for d in in_dtypes if d is not None), "float32")
        return [np_dtype(d if d is not None else known) for d in in_dtypes]


def _dummy_key_struct():
    # concrete key: eval_shape abstracts it, and jax's typed-PRNG checks pass
    import jax

    return jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# Inference memo
# ---------------------------------------------------------------------------
_INFER_EVAL = _tm.counter("symbol.infer_eval")
_INFER_MEMO_HIT = _tm.counter("symbol.infer_memo_hit")


class _InferMemo:
    """Results of abstract evaluation by node signature, for the process.

    Abstract evaluation of a registered op is a pure function of (op,
    params, input shapes, input dtypes), and an unrolled or repeated graph
    asks the same few questions thousands of times (``Module.bind`` of six
    unrolled LSTM graphs: 12 684 evaluations, 82 distinct). Entries are
    immutable tuples; an error is never stored, so a failed inference fails
    again. At most ``cap`` entries, the least recently asked out first: a
    server that binds ever new shapes does not grow, and keeps the
    signatures of its steady buckets. There is no option and no switch;
    ``cap=0`` (store nothing) is the tests' bypass
    (``test_utils.infer_memo_table``) and nothing else passes it.
    ``Custom`` overrides both inference methods (its prop's callbacks are
    user code that may hold state) and never gets here.
    """

    def __init__(self, cap=4096):
        self.cap = cap
        self._table = {}
        self._lock = threading.Lock()

    def __len__(self):
        return len(self._table)

    def answer(self, key, evaluate):
        """The three lists ``evaluate()`` returns, from the table where
        ``key`` (None: not memoised) was answered before. Callers write
        into what they get: fresh lists of immutable entries each time."""
        res = None
        if key is not None:
            with self._lock:
                res = self._table.pop(key, None)
                if res is not None:
                    self._table[key] = res  # asked last: evicted last
        if res is not None:
            _INFER_MEMO_HIT.inc()
        else:
            res = tuple(tuple(part) for part in evaluate())
            if key is not None and self.cap > 0:
                with self._lock:
                    # room first: a reader never sees more than ``cap``
                    self._table.pop(key, None)
                    if len(self._table) >= self.cap:
                        del self._table[next(iter(self._table))]
                    self._table[key] = res
        return list(res[0]), list(res[1]), list(res[2])


_MEMO = _InferMemo()


def _frozen(v):
    """``v`` as a hashable value that differs wherever ``v`` does: the type
    goes in, since ``1 == 1.0 == True`` hash alike. TypeError for a value
    it does not know (an array, say) or that never equals itself (NaN,
    which would miss every time and fill the table): that node is then not
    memoised."""
    if v is None or isinstance(v, (str, np.dtype)):
        return v
    if isinstance(v, (bool, int, float, np.generic)):
        if v != v:
            raise TypeError("nan")
        return (type(v).__name__, v)
    if isinstance(v, (tuple, list)):
        return (type(v).__name__,) + tuple(_frozen(x) for x in v)
    raise TypeError(type(v).__name__)


def _signature(kind, op_name, params, ins, in_dtypes):
    """Memo key of one inference question, or None where a parameter value
    cannot be frozen. ``jax_enable_x64`` changes the dtypes jax answers
    with, so it is part of the question."""
    import jax

    try:
        return (kind, op_name, bool(jax.config.jax_enable_x64),
                tuple(sorted((k, _frozen(v)) for k, v in params.items())),
                tuple(ins), None if in_dtypes is None else tuple(in_dtypes))
    except TypeError:
        return None


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
_OPS: dict = {}


def register(name, fn=None, **kwargs):
    """Register an op. Usable directly or as a decorator."""

    def _do(f):
        opdef = OpDef(name, f, **kwargs)
        if name in _OPS:
            raise MXNetError(f"op {name} registered twice")
        _OPS[name] = opdef
        for alias in opdef.aliases:
            _OPS[alias] = opdef
        return f

    if fn is not None:
        return _do(fn)
    return _do


def get(name: str) -> OpDef:
    op = _OPS.get(name)
    if op is None:
        raise MXNetError(f"unknown operator {name!r}")
    return op


def exists(name: str) -> bool:
    return name in _OPS


def list_ops():
    return sorted(_OPS.keys())


def canonical_ops():
    """Unique OpDefs (aliases collapsed), keyed by canonical name."""
    seen = {}
    for name, op in _OPS.items():
        if op.name == name:
            seen[name] = op
    return seen
