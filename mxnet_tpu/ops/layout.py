"""Channels-last (NHWC) lowering plane for the 2-D conv stack.

The reference framework is NCHW end to end (src/operator/nn/convolution.cc
defaults ``layout=NCHW``); XLA:TPU wants the channel dimension on the
128-wide vector lanes, i.e. minor-most — NHWC. Rather than rewrite the
graph (every shape, every checkpoint, every script would change), the
executor keeps the *logical* graph NCHW and re-lowers the conv stack
channels-last at interpretation time:

- **Aware ops** (:data:`AWARE`) — Convolution / Pooling / BatchNorm over
  4-D activations — accept a channels-last activation and lower with
  channels-last dimension numbers when ``OpMode.layout == "NHWC"``.
  Parameters (conv weights, BN gamma/beta/moving stats) keep their logical
  layout; the weight permutation to HWIO happens inside the lowering, so
  gradients and checkpoints stay in reference layout bit-for-bit.
- **Follower ops** (:data:`FOLLOWERS`) — elementwise math, activations,
  dropout, casts — are layout-oblivious: a channels-last array flows
  straight through, keeping the whole residual trunk of ResNet-style nets
  transpose-free.
- Every other op is a **graph edge**: the interpreter inserts a transpose
  back to NCHW before it (and the first aware op transposes its activation
  in). On ResNet-50 that is exactly two transposes — data in, pre-Flatten
  out — both fused into neighbours by XLA.

Because transposes are value-exact and conv/pool/BN reductions sum the
same terms in either layout, integer-lattice inputs reproduce NCHW results
*bitwise* — the parity contract tests/test_layout_parity.py pins.

:func:`resolve` maps ``MXNET_CONV_LAYOUT`` (``NCHW`` | ``NHWC`` | ``auto``)
to the lowering layout for a target context; ``auto`` picks NHWC exactly
when the target is a TPU. The resolved layout is part of the jit cache
signature and the AOT fingerprint (a cached executable compiled under the
other layout never false-hits).
"""

from __future__ import annotations

from ..base import MXNetError
from ..context import is_tpu

__all__ = [
    "resolve", "aware", "follower", "to_cl", "from_cl",
    "AWARE", "FOLLOWERS",
]


def resolve(ctx=None):
    """The lowering layout ("NCHW" or "NHWC") for ``ctx`` per
    ``MXNET_CONV_LAYOUT``. ``auto`` resolves to NHWC on TPU targets and
    NCHW everywhere else; ``ctx=None`` consults the default jax backend."""
    from .. import env

    val = str(env.get("MXNET_CONV_LAYOUT") or "auto").upper()
    if val in ("NCHW", "NHWC"):
        return val
    if val != "AUTO":
        raise MXNetError(
            f"MXNET_CONV_LAYOUT={val!r}: expected NCHW, NHWC or auto")
    return "NHWC" if is_tpu(ctx) else "NCHW"


def to_cl(x):
    """NCHW activation → channels-last (N, H, W, C)."""
    return x.transpose(0, 2, 3, 1)


def from_cl(x):
    """Channels-last activation → NCHW."""
    return x.transpose(0, 3, 1, 2)


# --- aware ops: re-lower channels-last when OpMode.layout == "NHWC" --------

def _conv_aware(params):
    # 2-D, reference layout only (an explicit layout param means the
    # caller already chose); grouped convs lower channels-last fine.
    return (len(params["kernel"]) == 2
            and params.get("layout") in (None, "NCHW"))


def _pool_aware(params):
    k = params["kernel"]
    return params["global_pool"] or len(k) == 2


def _bn_aware(params):
    return params.get("axis", 1) == 1


AWARE = {
    "Convolution": _conv_aware,
    "Pooling": _pool_aware,
    "BatchNorm": _bn_aware,
}


def aware(op_name, params, data_ndim):
    """True when this op node can lower channels-last: a 4-D activation
    and parameters the channels-last kernels cover."""
    pred = AWARE.get(op_name)
    return data_ndim == 4 and pred is not None and pred(params)


# --- follower ops: layout-oblivious elementwise pass-through ---------------

# Canonical registered names (node.op.name) of ops that compute the same
# values on a channels-last array as on NCHW — elementwise, shape-preserving,
# no axis semantics. Reductions, reshapes, Flatten/FC/Concat/slice and
# anything with an axis parameter are deliberately NOT here: they become
# graph edges and get an explicit transpose.
FOLLOWERS = frozenset([
    # nn
    "Activation", "Dropout",
    # tensor utilities
    "Cast", "BlockGrad", "identity", "clip",
    "zeros_like", "ones_like",
    # binary elementwise (same-shape)
    "_plus", "_minus", "_mul", "_div", "_power", "_maximum", "_minimum",
    "_mod",
    # comparisons (same-shape)
    "_equal", "_not_equal", "_greater", "_greater_equal", "_lesser",
    "_lesser_equal",
    # scalar variants
    "_plus_scalar", "_minus_scalar", "_rminus_scalar", "_mul_scalar",
    "_div_scalar", "_rdiv_scalar", "_power_scalar", "_rpower_scalar",
    "_maximum_scalar", "_minimum_scalar", "_mod_scalar", "_rmod_scalar",
    "_equal_scalar", "_not_equal_scalar", "_greater_scalar",
    "_greater_equal_scalar", "_lesser_scalar", "_lesser_equal_scalar",
    # unary math zoo (defs_elemwise._UNARY)
    "abs", "sign", "rint", "round", "ceil", "floor", "trunc", "fix",
    "square", "sqrt", "rsqrt", "cbrt", "rcbrt", "exp", "log", "log10",
    "log2", "log1p", "expm1", "sin", "cos", "tan", "arcsin", "arccos",
    "arctan", "degrees", "radians", "sinh", "cosh", "tanh", "arcsinh",
    "arccosh", "arctanh", "gamma", "gammaln", "negative", "reciprocal",
    "sigmoid", "relu", "softsign", "erf", "logical_not",
    # n-ary sum of same-shape operands
    "add_n",
])


def follower(op_name, params):
    """True when the op passes channels-last arrays through unchanged."""
    if op_name == "LeakyReLU":
        # prelu's gamma broadcasts against the (logical) channel axis
        return params.get("act_type") != "prelu"
    return op_name in FOLLOWERS
