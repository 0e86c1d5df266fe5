"""Neural-network layer operators.

Reference: the legacy ``OperatorProperty`` layers under ``src/operator/``
(``fully_connected``, ``convolution``, ``batch_norm``, ``pooling``,
``dropout``, ``softmax_output``, ``lrn``, ``leaky_relu``, ``instance_norm``,
``l2_normalization``, ``make_loss``, ``regression_output``, ``svm_output``,
``upsampling``, ``sequence_*``) plus their cuDNN twins. Here each layer is
one jax function lowered by XLA: convolutions hit the MXU via
``lax.conv_general_dilated`` (the cuDNN-autotuning machinery in
``cudnn_algoreg`` has no analogue — XLA picks the algorithm), and loss layers
encode their reference ``FGradient`` behaviour with ``jax.custom_vjp``.

Layers with state (BatchNorm moving stats) follow the aux-state protocol:
``fn`` returns ``(outputs, new_aux)`` and the executor writes new_aux back,
reproducing the reference's mutable ``aux_states`` contract
(``include/mxnet/operator.h`` Forward aux semantics).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..base import (
    MXNetError,
    np_dtype,
    parse_bool,
    parse_float,
    parse_int,
    parse_shape,
    parse_str,
)
from .registry import Param, register


def _acc(dt):
    return jnp.float32 if dt in (jnp.bfloat16, jnp.float16) else None


def _prec(dt):
    from .defs_tensor import matmul_precision

    return matmul_precision(dt)


def _castp(param, data):
    """Cast a parameter to the activation dtype (mixed precision: master
    weights stay f32, compute runs in the activation dtype — bf16 on the
    MXU; the cast's transpose accumulates the gradient back in f32)."""
    if param is not None and param.dtype != data.dtype:
        return param.astype(data.dtype)
    return param


# --- FullyConnected --------------------------------------------------------
def _fc(ins, params, mode):
    if params["no_bias"]:
        data, weight = ins
        bias = None
    else:
        data, weight, bias = ins
    weight, bias = _castp(weight, data), _castp(bias, data)
    if params["flatten"]:
        x = data.reshape((data.shape[0], -1))
    else:
        # flatten=False: FC applies to the LAST axis, leading dims kept
        # (reference fully_connected-inl.h Flatten=false path)
        x = data
    out = jax.lax.dot_general(
        x,
        weight,
        (((x.ndim - 1,), (1,)), ((), ())),
        precision=_prec(x.dtype),
    )
    if bias is not None:
        out = out + bias
    return out


def _fc_fill(shapes, params):
    data, *rest = shapes
    n = params["num_hidden"]
    if data is not None:
        in_dim = (
            int(np.prod(data[1:])) if params["flatten"] else int(data[-1])
        )
        if shapes[1] is None:
            shapes[1] = (n, in_dim)
    if not params["no_bias"] and shapes[2] is None:
        shapes[2] = (n,)
    return shapes


register(
    "FullyConnected",
    _fc,
    arg_names=lambda p: ["data", "weight"] + ([] if p["no_bias"] else ["bias"]),
    param_schema={
        "num_hidden": Param(parse_int),
        "no_bias": Param(parse_bool, False),
        "flatten": Param(parse_bool, True),
    },
    fill_in_shapes=_fc_fill,
)


# --- Convolution / Deconvolution ------------------------------------------
def _conv_dn(ndim):
    spec = tuple(range(ndim))
    return jax.lax.ConvDimensionNumbers(spec, spec, spec)


def _space_to_depth_conv(data, weight, k, stride, pad, prec):
    """Stride-2 small-channel 2-D conv via space-to-depth (MXU-friendly).

    The stem conv of image nets (e.g. ResNet 7x7/s2 on 3 channels) runs at
    ~1% MXU efficiency as written: 3 input channels leave the 128-wide MXU
    lanes almost empty. The classic TPU rewrite packs 2x2 spatial blocks
    into channels (3->12) and pads the kernel to even size, turning it into
    an exactly-equivalent stride-1 conv with 4x the channel depth — the
    same surgery MLPerf TPU ResNet submissions apply. Gradients flow
    through the reshapes/transposes automatically.
    """
    B, C, H, W = data.shape
    kh, kw = k
    ph, pw = pad
    out_h = (H + 2 * ph - kh) // 2 + 1
    out_w = (W + 2 * pw - kw) // 2 + 1
    kh2 = kh + (kh % 2)
    kw2 = kw + (kw % 2)
    # pad input: left by pad, right so every (even-start, padded-kernel)
    # window is in range
    Hp = (out_h - 1) * 2 + kh2
    Wp = (out_w - 1) * 2 + kw2
    x = jnp.pad(data, ((0, 0), (0, 0), (ph, Hp - H - ph), (pw, Wp - W - pw)))
    x = x.reshape(B, C, Hp // 2, 2, Wp // 2, 2)
    x = x.transpose(0, 1, 3, 5, 2, 4).reshape(B, C * 4, Hp // 2, Wp // 2)
    w = jnp.pad(weight, ((0, 0), (0, 0), (0, kh2 - kh), (0, kw2 - kw)))
    O = w.shape[0]
    w = w.reshape(O, C, kh2 // 2, 2, kw2 // 2, 2)
    w = w.transpose(0, 1, 3, 5, 2, 4).reshape(O, C * 4, kh2 // 2, kw2 // 2)
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=[(0, 0), (0, 0)],
        dimension_numbers=_conv_dn(4), precision=prec,
    )


def _conv(ins, params, mode):
    if params["no_bias"]:
        data, weight = ins
        bias = None
    else:
        data, weight, bias = ins
    weight, bias = _castp(weight, data), _castp(bias, data)
    k = params["kernel"]
    nsp = len(k)
    stride = params["stride"] or (1,) * nsp
    dilate = params["dilate"] or (1,) * nsp
    pad = params["pad"] or (0,) * nsp
    if mode.layout == "NHWC" and nsp == 2 and data.ndim == 4:
        # channels-last lowering (ops/layout.py): the activation arrives
        # (N, H, W, C); the weight stays logical OIHW — permuting it here
        # keeps its gradient and every checkpoint in reference layout.
        out = jax.lax.conv_general_dilated(
            data,
            weight.transpose(2, 3, 1, 0),  # OIHW -> HWIO
            window_strides=stride,
            padding=[(p, p) for p in pad],
            rhs_dilation=dilate,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=params["num_group"],
            precision=_prec(data.dtype),
        )
        if bias is not None:
            out = out + bias  # broadcasts over the minor-most channel axis
        return out
    if (
        nsp == 2 and stride == (2, 2) and dilate == (1, 1)
        and params["num_group"] == 1 and data.shape[1] <= 4
        and k[0] % 2 == 1 and k[1] % 2 == 1  # even kernels mis-pad
        and data.shape[2] >= k[0] and data.shape[3] >= k[1]
    ):
        out = _space_to_depth_conv(data, weight, k, stride, pad,
                                   _prec(data.dtype))
    else:
        out = jax.lax.conv_general_dilated(
            data,
            weight,
            window_strides=stride,
            padding=[(p, p) for p in pad],
            rhs_dilation=dilate,
            dimension_numbers=_conv_dn(data.ndim),
            feature_group_count=params["num_group"],
            precision=_prec(data.dtype),
        )
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nsp)
    return out


def _conv_fill(shapes, params):
    data = shapes[0]
    k = params["kernel"]
    nf = params["num_filter"]
    ng = params["num_group"]
    if data is not None and shapes[1] is None:
        shapes[1] = (nf, data[1] // ng) + tuple(k)
    if not params["no_bias"] and shapes[2] is None:
        shapes[2] = (nf,)
    return shapes


_CONV_SCHEMA = {
    "kernel": Param(parse_shape),
    "stride": Param(parse_shape, None),
    "dilate": Param(parse_shape, None),
    "pad": Param(parse_shape, None),
    "num_filter": Param(parse_int),
    "num_group": Param(parse_int, 1),
    "no_bias": Param(parse_bool, False),
    "workspace": Param(parse_int, 1024),  # reference knob; XLA manages scratch
    "cudnn_tune": Param(parse_str, None),  # accepted for script parity, unused
    "cudnn_off": Param(parse_bool, False),
    "layout": Param(parse_str, None),
}

register(
    "Convolution",
    _conv,
    arg_names=lambda p: ["data", "weight"] + ([] if p["no_bias"] else ["bias"]),
    param_schema=dict(_CONV_SCHEMA),
    fill_in_shapes=_conv_fill,
    aliases=("Convolution_v1",),  # legacy twin (src/operator/convolution_v1)
)


def _deconv(ins, params, mode):
    """Transposed convolution = gradient of Convolution wrt its input
    (reference ``src/operator/deconvolution-inl.h`` computes exactly that via
    the conv backward kernels). Expressed as lhs-dilated conv so XLA lowers
    it onto the MXU like any other conv.
    """
    if params["no_bias"]:
        data, weight = ins
        bias = None
    else:
        data, weight, bias = ins
    weight, bias = _castp(weight, data), _castp(bias, data)
    k = params["kernel"]
    nsp = len(k)
    stride = params["stride"] or (1,) * nsp
    dilate = params["dilate"] or (1,) * nsp
    pad = params["pad"] or (0,) * nsp
    adj = params["adj"] or (0,) * nsp
    # weight layout (C_in, num_filter//num_group, *k): flip spatially and
    # swap in/out channels to express deconv as a conv.
    w = weight
    for ax in range(2, 2 + nsp):
        w = jnp.flip(w, axis=ax)
    ng = params["num_group"]
    if ng > 1:
        cin, cpg = w.shape[0], w.shape[1]
        w = w.reshape((ng, cin // ng) + w.shape[1:])
        w = jnp.swapaxes(w, 1, 2)
        w = w.reshape((ng * cpg, cin // ng) + w.shape[3:])
    else:
        w = jnp.swapaxes(w, 0, 1)
    eff_k = tuple((kk - 1) * d + 1 for kk, d in zip(k, dilate))
    padding = [
        (ek - 1 - p, ek - 1 - p + a) for ek, p, a in zip(eff_k, pad, adj)
    ]
    out = jax.lax.conv_general_dilated(
        data,
        w,
        window_strides=(1,) * nsp,
        padding=padding,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=_conv_dn(data.ndim),
        feature_group_count=ng,
        precision=_prec(data.dtype),
    )
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nsp)
    return out


def _deconv_fill(shapes, params):
    data = shapes[0]
    k = params["kernel"]
    nf = params["num_filter"]
    ng = params["num_group"]
    if data is not None and shapes[1] is None:
        shapes[1] = (data[1], nf // ng) + tuple(k)
    if not params["no_bias"] and shapes[2] is None:
        shapes[2] = (nf,)
    return shapes


register(
    "Deconvolution",
    _deconv,
    arg_names=lambda p: ["data", "weight"] + ([] if p["no_bias"] else ["bias"]),
    param_schema={
        **_CONV_SCHEMA,
        "adj": Param(parse_shape, None),
        "target_shape": Param(parse_shape, None),
    },
    fill_in_shapes=_deconv_fill,
)


# --- Activation / LeakyReLU ------------------------------------------------
_ACTS = {
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softrelu": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    "silu": jax.nn.silu,  # x * sigmoid(x): the SwiGLU blocks of models/afmoe
    # x * Phi(x) through erf, the exact form (torch's nn.GELU() default),
    # not the tanh approximation: the router MLP of models/zaya
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
}


def _activation(ins, params, mode):
    return _ACTS[params["act_type"]](ins[0])


register(
    "Activation",
    _activation,
    arg_names=["data"],
    param_schema={"act_type": Param(parse_str)},
)


def _leaky_relu(ins, params, mode):
    act = params["act_type"]
    x = ins[0]
    if act == "prelu":
        gamma = ins[1].reshape((1, -1) + (1,) * (x.ndim - 2))
        return jnp.where(x > 0, x, gamma * x)
    if act == "leaky":
        s = params["slope"]
        return jnp.where(x > 0, x, s * x)
    if act == "elu":
        s = params["slope"]
        return jnp.where(x > 0, x, s * jnp.expm1(x))
    if act == "rrelu":
        lo, hi = params["lower_bound"], params["upper_bound"]
        if mode.is_train:
            slope = jax.random.uniform(
                mode.rng, x.shape, dtype=x.dtype, minval=lo, maxval=hi
            )
        else:
            slope = (lo + hi) / 2.0
        return jnp.where(x > 0, x, slope * x)
    raise MXNetError(f"LeakyReLU: unknown act_type {act}")


register(
    "LeakyReLU",
    _leaky_relu,
    arg_names=lambda p: ["data", "gamma"] if p["act_type"] == "prelu" else ["data"],
    param_schema={
        "act_type": Param(parse_str, "leaky"),
        "slope": Param(parse_float, 0.25),
        "lower_bound": Param(parse_float, 0.125),
        "upper_bound": Param(parse_float, 0.334),
    },
    fill_in_shapes=lambda shapes, p: (
        [shapes[0], shapes[1] or ((shapes[0][1],) if shapes[0] else None)]
        if p["act_type"] == "prelu"
        else shapes
    ),
    need_rng=True,
)


# --- BatchNorm -------------------------------------------------------------
def _batch_norm(ins, params, mode):
    data, gamma, beta, moving_mean, moving_var = ins
    eps = params["eps"]
    momentum = params["momentum"]
    if params["fix_gamma"]:
        gamma = jnp.ones_like(gamma)  # constant → zero gradient, as reference
    if mode.layout == "NHWC" and data.ndim == 4:
        # channels-last lowering (ops/layout.py): reduce over N/H/W, channel
        # params broadcast on the minor-most axis
        axes = (0, 1, 2)
        bshape = (1, 1, 1, -1)
    else:
        axes = tuple(i for i in range(data.ndim) if i != 1)
        bshape = (1, -1) + (1,) * (data.ndim - 2)
    use_global = params["use_global_stats"] or not mode.is_train
    if use_global:
        mean, var = moving_mean, moving_var
        new_aux = [moving_mean, moving_var]
        out_mean, out_var = moving_mean, moving_var
    else:
        # One-pass stats: both reductions are independent, so XLA fuses them
        # into a single read of the activation — usually the epilogue of the
        # conv that produced it (jnp.mean followed by jnp.var chains two
        # full passes, the dominant cost of training BN on a bandwidth-bound
        # chip). Plain E[x^2]-E[x]^2 catastrophically cancels in fp32 when
        # |mean| >> std, so the pass is shifted by an anchor m0:
        # var = E[(x-m0)^2] - (mean-m0)^2, exact for any m0, with relative
        # error ~eps_f32 * dmean^2/var where dmean = mean - m0.
        #
        # The anchor MUST be a graph input, not a statistic of `data`: any
        # data-dependent anchor serializes the stats pass behind the full
        # materialization of `data`, losing the epilogue fusion (~4% step
        # time on ResNet-50), and a lax.cond rescue pass breaks the fused
        # train step entirely (~30%, measured). The moving mean is the only
        # free anchor, and it tracks the batch mean in steady state
        # (dmean ~ std/sqrt(n): error vanishes). Documented accuracy bound
        # when the anchor is stale (zero-init first steps, checkpoint
        # resumed on shifted data): staleness of k standard deviations
        # costs ~eps_f32*k^2 relative error in var — still 1e-4-accurate at
        # k=30, and self-healing within a few steps as the moving mean
        # re-converges (momentum 0.9 closes 30 sigma in ~3 steps). The
        # max(.,0) clamp bounds the pathological k>1e3 case (var can read
        # 0, never negative), where normalization degrades to an
        # eps-regularized mean-shift for those first steps.
        n = float(np.prod([data.shape[i] for i in axes]))
        m0 = jax.lax.stop_gradient(moving_mean).astype(jnp.float32)
        xc = data.astype(jnp.float32) - m0.reshape(bshape)
        dmean = jnp.sum(xc, axis=axes) / n
        mean = m0 + dmean
        var = jnp.maximum(
            jnp.sum(xc * xc, axis=axes) / n - dmean * dmean, 0.0
        )
        new_aux = [
            moving_mean * momentum + jax.lax.stop_gradient(mean) * (1 - momentum),
            moving_var * momentum + jax.lax.stop_gradient(var) * (1 - momentum),
        ]
        out_mean, out_var = mean, var
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps).astype(data.dtype)
    out = (data - mean.reshape(bshape).astype(data.dtype)) * inv.reshape(
        bshape
    ) * _castp(gamma, data).reshape(bshape) + _castp(beta, data).reshape(bshape)
    return [out, out_mean, out_var], new_aux


def _bn_fill(shapes, params):
    data = shapes[0]
    if data is not None:
        c = (data[1],)
        for i in range(1, 5):
            if shapes[i] is None:
                shapes[i] = c
    return shapes


register(
    "BatchNorm",
    _batch_norm,
    arg_names=["data", "gamma", "beta"],
    aux_names=["moving_mean", "moving_var"],
    param_schema={
        "eps": Param(parse_float, 1e-3),
        "momentum": Param(parse_float, 0.9),
        "fix_gamma": Param(parse_bool, True),
        "use_global_stats": Param(parse_bool, False),
        "output_mean_var": Param(parse_bool, False),
        "cudnn_off": Param(parse_bool, False),
        "axis": Param(parse_int, 1),
    },
    aliases=("BatchNorm_v1",),  # legacy twin (src/operator/batch_norm_v1)
    fill_in_shapes=_bn_fill,
    num_outputs=3,
    num_visible_outputs=lambda p: 3 if p["output_mean_var"] else 1,
)


# --- InstanceNorm / L2Normalization ---------------------------------------
def _instance_norm(ins, params, mode):
    data, gamma, beta = ins
    eps = params["eps"]
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    out = (data - mean) * jax.lax.rsqrt(var + eps)
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


register(
    "InstanceNorm",
    _instance_norm,
    arg_names=["data", "gamma", "beta"],
    param_schema={"eps": Param(parse_float, 1e-3)},
    fill_in_shapes=lambda shapes, p: [
        shapes[0],
        shapes[1] or ((shapes[0][1],) if shapes[0] else None),
        shapes[2] or ((shapes[0][1],) if shapes[0] else None),
    ],
)


def _l2_normalization(ins, params, mode):
    (x,) = ins
    eps = params["eps"]
    m = params["mode"]
    if m == "instance":
        axes = tuple(range(1, x.ndim))
    elif m == "channel":
        axes = (1,)
    elif m == "spatial":
        axes = tuple(range(2, x.ndim))
    else:
        raise MXNetError(f"L2Normalization: unknown mode {m}")
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + eps)
    return x / norm


register(
    "L2Normalization",
    _l2_normalization,
    arg_names=["data"],
    param_schema={
        "eps": Param(parse_float, 1e-10),
        "mode": Param(parse_str, "instance"),
    },
)


# --- LRN -------------------------------------------------------------------
def _lrn(ins, params, mode):
    (x,) = ins
    n = params["nsize"]
    alpha, beta, knorm = params["alpha"], params["beta"], params["knorm"]
    sq = jnp.square(x)
    half = n // 2
    # cross-channel window sum via pad + reduce_window on channel axis
    summed = jax.lax.reduce_window(
        sq,
        0.0,
        jax.lax.add,
        window_dimensions=(1, n) + (1,) * (x.ndim - 2),
        window_strides=(1,) * x.ndim,
        padding=((0, 0), (half, half)) + ((0, 0),) * (x.ndim - 2),
    )
    norm = jnp.power(knorm + (alpha / n) * summed, -beta)
    return [x * norm, norm]


register(
    "LRN",
    _lrn,
    arg_names=["data"],
    param_schema={
        "nsize": Param(parse_int),
        "alpha": Param(parse_float, 1e-4),
        "beta": Param(parse_float, 0.75),
        "knorm": Param(parse_float, 2.0),
    },
    num_outputs=2,
    num_visible_outputs=1,
)


# --- Pooling ---------------------------------------------------------------
def _pooling(ins, params, mode):
    (x,) = ins
    nsp = x.ndim - 2
    # channels-last lowering (ops/layout.py): spatial axes start at 1 and
    # the channel axis is minor-most
    cl = mode.layout == "NHWC" and x.ndim == 4
    sp0 = 1 if cl else 2
    if params["global_pool"]:
        k = x.shape[sp0:sp0 + nsp]
        stride = (1,) * nsp
        pad = (0,) * nsp
    else:
        k = params["kernel"]
        stride = params["stride"] or (1,) * nsp
        pad = params["pad"] or (0,) * nsp
    ptype = params["pool_type"]
    pads = []
    for i in range(nsp):
        lo = pad[i]
        hi = pad[i]
        if params["pooling_convention"] == "full" and not params["global_pool"]:
            size = x.shape[sp0 + i]
            full_out = -(-(size + 2 * pad[i] - k[i]) // stride[i]) + 1
            valid_out = (size + 2 * pad[i] - k[i]) // stride[i] + 1
            hi += (full_out - valid_out) * stride[i]
        pads.append((lo, hi))
    if cl:
        window = (1,) + tuple(k) + (1,)
        strides = (1,) + tuple(stride) + (1,)
        padding = ((0, 0),) + tuple(pads) + ((0, 0),)
    else:
        window = (1, 1) + tuple(k)
        strides = (1, 1) + tuple(stride)
        padding = ((0, 0), (0, 0)) + tuple(pads)
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return jax.lax.reduce_window(x, init, jax.lax.max, window, strides, padding)
    summed = jax.lax.reduce_window(
        x.astype(jnp.float32), 0.0, jax.lax.add, window, strides, padding
    )
    if ptype == "sum":
        return summed.astype(x.dtype)
    if ptype == "avg":
        return (summed / float(np.prod(k))).astype(x.dtype)
    raise MXNetError(f"Pooling: unknown pool_type {ptype}")


register(
    "Pooling",
    _pooling,
    arg_names=["data"],
    param_schema={
        "kernel": Param(parse_shape, ()),
        "pool_type": Param(parse_str, "max"),
        "global_pool": Param(parse_bool, False),
        "stride": Param(parse_shape, None),
        "pad": Param(parse_shape, None),
        "pooling_convention": Param(parse_str, "valid"),
        "cudnn_off": Param(parse_bool, False),
    },
    aliases=("Pooling_v1",),  # legacy twin (src/operator/pooling_v1)
)


# --- Dropout ---------------------------------------------------------------
def _dropout(ins, params, mode):
    (x,) = ins
    p = params["p"]
    if not mode.is_train or p <= 0.0:
        return [x, jnp.ones_like(x)]
    keep = 1.0 - p
    mask = jax.random.bernoulli(mode.rng, keep, x.shape).astype(x.dtype) / keep
    return [x * mask, mask]


register(
    "Dropout",
    _dropout,
    arg_names=["data"],
    param_schema={"p": Param(parse_float, 0.5), "mode": Param(parse_str, "training")},
    need_rng=True,
    num_outputs=2,
    num_visible_outputs=1,
)


# --- softmax family --------------------------------------------------------
register(
    "softmax",
    lambda ins, p, m: jax.nn.softmax(ins[0] / p["temperature"], axis=p["axis"]),
    arg_names=["data"],
    param_schema={
        "axis": Param(parse_int, -1),
        "temperature": Param(parse_float, 1.0),
    },
)

register(
    "log_softmax",
    lambda ins, p, m: jax.nn.log_softmax(ins[0] / p["temperature"], axis=p["axis"]),
    arg_names=["data"],
    param_schema={
        "axis": Param(parse_int, -1),
        "temperature": Param(parse_float, 1.0),
    },
)


def _softmax_activation(ins, params, mode):
    (x,) = ins
    if params["mode"] == "channel":
        return jax.nn.softmax(x, axis=1)
    return jax.nn.softmax(x.reshape(x.shape[0], -1), axis=-1).reshape(x.shape)


register(
    "SoftmaxActivation",
    _softmax_activation,
    arg_names=["data"],
    param_schema={"mode": Param(parse_str, "instance")},
)


def _softmax_output(ins, params, mode):
    """Softmax forward with the classic fused cross-entropy backward.

    Reference ``src/operator/softmax_output-inl.h``: Backward ignores the
    incoming head gradient entirely and writes ``(p - onehot(label)) *
    grad_scale`` with optional ignore-label masking and batch/valid
    normalisation. Encoded with jax.custom_vjp so executor backward() with no
    out_grads reproduces the loss-layer semantics exactly.

    ``sample_weight`` (beyond the reference): a third input ``weight`` of the
    label's shape weighs each row's loss, so its gradient is ``(p -
    onehot(label)) * weight`` (0 where the weight is 0 or the label is
    ignored): the 1/t-weighted loss on the masked tokens of a diffusion
    step, whose weights ``BlockDiffusionNoise`` makes. The weight receives
    no gradient. Off, the operator traces to what it was.
    """
    data, label = ins[:2]
    multi = params["multi_output"]
    preserve = params["preserve_shape"]
    grad_scale = params["grad_scale"]
    use_ignore = params["use_ignore"]
    ignore_label = params["ignore_label"]
    normalization = params["normalization"]

    def forward(d):
        if multi:
            return jax.nn.softmax(d, axis=1)
        if preserve:
            return jax.nn.softmax(d, axis=-1)
        return jax.nn.softmax(d.reshape(d.shape[0], -1), axis=-1).reshape(d.shape)

    @jax.custom_vjp
    def f(d, l, *w):
        return forward(d)

    def fwd(d, l, *w):
        out = forward(d)
        return out, (out, l) + w

    def bwd(res, g):
        out, l = res[:2]
        axis = 1 if multi else out.ndim - 1
        li = l.astype(jnp.int32)
        onehot = jax.nn.one_hot(li, out.shape[axis], axis=axis, dtype=out.dtype)
        grad = out - onehot
        valid = jnp.ones(l.shape, dtype=out.dtype)
        if use_ignore:
            valid = (l != ignore_label).astype(out.dtype)
            grad = grad * jnp.expand_dims(valid, axis)
        for w in res[2:]:
            grad = grad * jnp.expand_dims(
                w.reshape(l.shape).astype(out.dtype), axis)
        scale = grad_scale
        if normalization == "batch":
            grad = grad / out.shape[0]
        elif normalization == "valid":
            grad = grad / jnp.maximum(jnp.sum(valid), 1.0)
        return (grad * scale, jnp.zeros_like(l)) + tuple(
            jnp.zeros_like(w) for w in res[2:])

    f.defvjp(fwd, bwd)
    return f(data, label, *ins[2:])


def _softmax_output_fill(shapes, params):
    data = shapes[0]
    if data is not None and shapes[1] is None:
        if params["multi_output"]:
            shapes[1] = (data[0],) + tuple(data[2:])
        elif params["preserve_shape"]:
            shapes[1] = tuple(data[:-1])
        else:
            shapes[1] = (data[0],)
    if len(shapes) > 2 and shapes[2] is None and shapes[1] is not None:
        shapes[2] = tuple(shapes[1])
    return shapes


register(
    "SoftmaxOutput",
    _softmax_output,
    arg_names=lambda p: ["data", "label"] + ["weight"] * p["sample_weight"],
    param_schema={
        "grad_scale": Param(parse_float, 1.0),
        "ignore_label": Param(parse_float, -1.0),
        "multi_output": Param(parse_bool, False),
        "use_ignore": Param(parse_bool, False),
        "preserve_shape": Param(parse_bool, False),
        "normalization": Param(parse_str, "null"),
        "out_grad": Param(parse_bool, False),
        # a third input, a weight a row of the loss
        "sample_weight": Param(parse_bool, False),
    },
    fill_in_shapes=_softmax_output_fill,
    aliases=("Softmax",),
    is_loss=True,
)


# --- BlockDiffusionNoise: the forward process of a block-diffusion step -----
def diffusion_noise(key, ids, block, eps):
    """(mask (rows, T) bool, t (rows, T) float32) of the forward process
    over token ids ``ids`` (rows, T), by two draws from ``key``::

        k_t, k_m = jax.random.split(key)
        t = eps + (1 - eps) * jax.random.uniform(k_t, (rows, T // block))
        m = jax.random.uniform(k_m, (rows, T)) < repeat(t, block, axis=1)

    one noise level ``t`` in [eps, 1) a block of ``block`` positions, and a
    position masked with probability its block's ``t`` (the linear
    schedule), independently. A plain reference that writes the same lines
    draws the same noise."""
    rows, T = ids.shape
    k_t, k_m = jax.random.split(key)
    t = eps + (1.0 - eps) * jax.random.uniform(k_t, (rows, T // block),
                                               jnp.float32)
    t = jnp.repeat(t, block, axis=1)
    return jax.random.uniform(k_m, (rows, T), jnp.float32) < t, t


def _block_diffusion_noise(ins, params, mode):
    """The noise of a block-diffusion training step (Arriola et al. 2025,
    arXiv:2503.09573, over the masked diffusion of Sahoo et al. 2024,
    arXiv:2406.07524), drawn inside the program: token ids ``data`` (B, T)
    in; out the noised ids (a masked position holds ``mask_id``), the mask
    m (1 where masked) and the loss weights m / t, each (B, T) in ``data``'s
    dtype (:func:`diffusion_noise`: t uniform in [eps, 1) a block of
    ``block`` positions, m Bernoulli(t) a position). A position whose id is
    ``pad_id`` is never masked and weighs nothing. No output passes a
    gradient.

    ``seed`` < 0 (the default, and what ``fit`` runs): the executor's
    stream, new noise every step. ``seed`` >= 0: the key is
    ``jax.random.fold_in(jax.random.PRNGKey(seed), 0)``, the same noise
    every call, which a reference can draw too. Not in training: no mask,
    the clean ids, weights 0."""
    (ids,) = ins
    block = params["block"]
    if ids.ndim == 0:   # ``infer_type``'s probe
        return [ids, jnp.zeros_like(ids), jnp.zeros_like(ids)]
    if ids.ndim != 2 or block < 1 or ids.shape[1] % block:
        raise MXNetError(
            f"BlockDiffusionNoise: data {tuple(ids.shape)} is not (rows, T) "
            f"with T a multiple of block={block}")
    if not mode.is_train:
        return [ids, jnp.zeros_like(ids), jnp.zeros_like(ids)]
    key = mode.rng if params["seed"] < 0 else jax.random.fold_in(
        jax.random.PRNGKey(params["seed"]), 0)
    mask, t = diffusion_noise(key, ids, block, params["eps"])
    mask = jnp.logical_and(mask, ids != params["pad_id"])
    noised = jnp.where(mask, jnp.asarray(params["mask_id"], ids.dtype), ids)
    weight = jnp.where(mask, 1.0 / t, 0.0)
    return [jax.lax.stop_gradient(x.astype(ids.dtype))
            for x in (noised, mask, weight)]


def _block_diffusion_noise_counts(ins, outs, params, platform):
    """A launch's counts for one node: the token positions it noises. (What
    the trunk makes of them is the trunk's to say: ``RingAttention`` under
    ``diffusion_block`` counts ``executor.diffusion_trunk_rows`` from the rows
    it is handed.)"""
    return {"executor.diffusion_noised_rows":
            int(ins[0].shape[0]) * int(ins[0].shape[1])}


register(
    "BlockDiffusionNoise",
    _block_diffusion_noise,
    arg_names=["data"],
    param_schema={
        "block": Param(parse_int),
        "mask_id": Param(parse_int),
        "eps": Param(parse_float, 1e-3),
        "pad_id": Param(parse_int, 0),
        "seed": Param(parse_int, -1),
    },
    need_rng=True,
    num_outputs=3,
    launch_counts=_block_diffusion_noise_counts,
    launch_instruments=("executor.diffusion_noised_rows",),
)


# --- ExitSoftmaxOutput: a mixture over the exits of a looped model ----------
def _exit_log_shares(scores):
    """``log p`` (rows, R) of the exit distribution of ``scores`` (rows, R),
    float32: with ``lam_t = sigmoid(score_t)``, ``p_t = lam_t prod_{j<t}
    (1 - lam_j)`` for t < R and ``p_R = prod_{j<R} (1 - lam_j)``, so every
    row sums to 1 and the last score is not read. In logs, so that a
    saturated gate gives a large negative number and never ``log 0``."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-scores[:, :-1]), axis=1)
    stay = jnp.pad(stay, ((0, 0), (1, 0)))  # log S_(t-1), S_0 = 1
    leave = jax.nn.log_sigmoid(scores[:, :-1])
    return jnp.concatenate([leave + stay[:, :-1], stay[:, -1:]], axis=1)


def _exit_operands(ins, params):
    """(exits' logits, gate scores, label) of ``ExitSoftmaxOutput``'s inputs,
    held to the shapes the operator defines."""
    r = params["num_exits"]
    if r < 2:
        raise MXNetError(
            f"ExitSoftmaxOutput: num_exits={r}: the mixture needs at least 2 "
            "exits (one exit is SoftmaxOutput)")
    if len(ins) != 2 * r + 1:
        raise MXNetError(f"ExitSoftmaxOutput: num_exits={r} takes {r} exits, "
                         f"{r} gates and a label, got {len(ins)} inputs")
    exits, gates, label = ins[:r], ins[r:2 * r], ins[2 * r]
    if exits[0].ndim == 0:
        # ``infer_type``'s probe hands every operand over as a scalar: one
        # row of one class
        return ([x.reshape(1, 1) for x in exits],
                [g.reshape(1, 1) for g in gates], label.reshape(1))
    shape = tuple(exits[0].shape)
    if len(shape) != 2 or any(tuple(x.shape) != shape for x in exits):
        raise MXNetError(
            "ExitSoftmaxOutput: every exit is (rows, classes) logits of one "
            f"shape, got {[tuple(x.shape) for x in exits]}")
    if any(tuple(g.shape) != (shape[0], 1) for g in gates):
        raise MXNetError(
            f"ExitSoftmaxOutput: a gate is (rows, 1) = ({shape[0]}, 1) "
            f"scores, got {[tuple(g.shape) for g in gates]}")
    if tuple(label.shape) != (shape[0],):
        raise MXNetError(
            f"ExitSoftmaxOutput: the label is one class id a row, "
            f"({shape[0]},), got {tuple(label.shape)}")
    return exits, gates, label


def _exit_softmax_output(ins, params, mode):
    """The loss layer of a looped model that may leave after any of its R
    passes (Ouro, Zhu et al. 2025, arXiv:2510.25741, Stage I): exit ``t``
    has logits ``z_t`` and a gate score ``s_t``; the scores make the exit
    distribution ``p`` (:func:`_exit_log_shares`); and the objective a row
    is the expected cross-entropy less ``beta`` times the entropy of ``p``::

        J = sum_t p_t l_t + beta sum_t p_t log p_t,  l_t = -log softmax(z_t)[y]

    Forward gives ``softmax(z_R)`` in float32, what the model predicts when
    it never leaves early. Backward ignores the head gradient, as every
    loss layer does, and writes ``dJ/dz_t = p_t (softmax(z_t) - onehot(y))``
    and ``dJ/ds`` through ``dJ/dp_t = l_t + beta (log p_t + 1)``, summed
    over the rows whose label is not ``ignore_label`` (``use_ignore``); the
    last gate gets none. Softmaxes, ``log p`` and the sums are float32
    whatever the logits' dtype. What backward keeps beyond its operands is
    two float32 numbers a row and exit (the log-sum-exp and the label's
    logit), named for the per-operator recomputation: each exit's softmax
    is made again from its logits, in their dtype, where its gradient is
    written, and no float32 (rows, classes) tensor is kept."""
    from .registry import keep

    exits, gates, label = _exit_operands(ins, params)
    beta = params["beta"]
    use_ignore, ignore_label = params["use_ignore"], params["ignore_label"]

    def rows_stats(z, li):
        """(log-sum-exp, the label's logit) a row, one pass over ``z``."""
        z = z.astype(jnp.float32)
        hit = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) == li[:, None]
        return (jax.scipy.special.logsumexp(z, axis=-1),
                jnp.sum(jnp.where(hit, z, 0.0), axis=-1))

    def forward(zs, l):
        li = l.astype(jnp.int32)
        stats = [rows_stats(z, li) for z in zs]
        out = jnp.exp(zs[-1].astype(jnp.float32) - stats[-1][0][:, None])
        return out, (jnp.stack([s[0] for s in stats], 1),
                     jnp.stack([s[1] for s in stats], 1))

    @jax.custom_vjp
    def f(zs, ss, l):
        return forward(zs, l)[0]

    def fwd(zs, ss, l):
        out, (lse, picked) = forward(zs, l)
        return out, (zs, ss, l, keep((lse, picked)))

    def bwd(res, g):
        zs, ss, l, (lse, picked) = res
        li = l.astype(jnp.int32)
        valid = (l != ignore_label).astype(jnp.float32) if use_ignore \
            else jnp.ones(l.shape, jnp.float32)
        nll = lse - picked  # (rows, R)
        scores = jnp.concatenate(ss, axis=1).astype(jnp.float32)

        def objective(scores):
            logp = _exit_log_shares(scores)
            p = jnp.exp(logp)
            return jnp.sum(valid[:, None] * p * (nll + beta * logp)), p

        d_scores, p = jax.grad(objective, has_aux=True)(scores)
        weight = valid[:, None] * p
        d_zs = []
        for t, z in enumerate(zs):
            hit = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) \
                == li[:, None]
            soft = jnp.exp(z.astype(jnp.float32) - lse[:, t:t + 1])
            d_zs.append((weight[:, t:t + 1]
                         * (soft - hit.astype(jnp.float32))).astype(z.dtype))
        d_ss = [d_scores[:, t:t + 1].astype(s.dtype)
                for t, s in enumerate(ss)]
        return d_zs, d_ss, jnp.zeros_like(l)

    f.defvjp(fwd, bwd)
    out = f(list(exits), list(gates), label)
    return out.reshape(()) if ins[0].ndim == 0 else out


def _exit_softmax_output_args(p):
    r = p["num_exits"]
    return [f"exit{t}" for t in range(1, r + 1)] \
        + [f"gate{t}" for t in range(1, r + 1)] + ["label"]


def _exit_softmax_output_fill(shapes, params):
    r = params["num_exits"]
    known = next((s for s in shapes[:r] if s is not None), None)
    if known is not None and len(shapes) == 2 * r + 1:
        for i in range(r):
            shapes[i] = shapes[i] or tuple(known)
            shapes[r + i] = shapes[r + i] or (known[0], 1)
        shapes[2 * r] = shapes[2 * r] or (known[0],)
    return shapes


def _exit_softmax_output_dtypes(in_dtypes, params):
    """The exits' and gates' dtype is the trunk's; the label keeps its own
    (float32 unless said): class ids do not fit the trunk's bfloat16."""
    r = params["num_exits"]
    trunk = next((d for d in in_dtypes[:2 * r] if d is not None), "float32")
    return [d if d is not None else trunk for d in in_dtypes[:2 * r]] \
        + [d if d is not None else "float32" for d in in_dtypes[2 * r:]]


def _exit_softmax_output_counts(ins, outs, params, platform):
    """A launch's counts for one node: its exits, and the rows each of them
    reads."""
    r = params["num_exits"]
    return {"executor.exit_loss_heads": r,
            "executor.exit_loss_rows": r * int(ins[0].shape[0])}


register(
    "ExitSoftmaxOutput",
    _exit_softmax_output,
    arg_names=_exit_softmax_output_args,
    param_schema={
        "num_exits": Param(parse_int),
        "beta": Param(parse_float, 0.0),
        "use_ignore": Param(parse_bool, False),
        "ignore_label": Param(parse_float, -1.0),
    },
    fill_in_shapes=_exit_softmax_output_fill,
    infer_dtype=_exit_softmax_output_dtypes,
    is_loss=True,
    launch_counts=_exit_softmax_output_counts,
    launch_instruments=("executor.exit_loss_heads",
                        "executor.exit_loss_rows"),
)


# --- losses ----------------------------------------------------------------
def _make_loss(ins, params, mode):
    (data,) = ins
    grad_scale = params["grad_scale"]
    normalization = params["normalization"]
    valid_thresh = params["valid_thresh"]

    @jax.custom_vjp
    def f(d):
        return d

    def fwd(d):
        return d, d

    def bwd(d, g):
        grad = jnp.full_like(d, grad_scale)
        if normalization == "batch":
            grad = grad / d.shape[0]
        elif normalization == "valid":
            valid = jnp.sum((d > valid_thresh).astype(d.dtype))
            grad = grad / jnp.maximum(valid, 1.0)
        return (grad,)

    f.defvjp(fwd, bwd)
    return f(data)


register(
    "MakeLoss",
    _make_loss,
    arg_names=["data"],
    param_schema={
        "grad_scale": Param(parse_float, 1.0),
        "valid_thresh": Param(parse_float, 0.0),
        "normalization": Param(parse_str, "null"),
    },
    aliases=("make_loss",),
    is_loss=True,
)


def _regression_output(transform, grad_fn):
    def op(ins, params, mode):
        data, label = ins
        grad_scale = params["grad_scale"]

        @jax.custom_vjp
        def f(d, l):
            return transform(d)

        def fwd(d, l):
            out = transform(d)
            return out, (out, l)

        def bwd(res, g):
            out, l = res
            num = float(np.prod(out.shape[1:])) or 1.0
            grad = grad_fn(out, l.reshape(out.shape)) * (grad_scale / num)
            return grad, jnp.zeros_like(l)

        f.defvjp(fwd, bwd)
        return f(data, label)

    return op


_REG_SCHEMA = {"grad_scale": Param(parse_float, 1.0)}

register(
    "LinearRegressionOutput",
    _regression_output(lambda d: d, lambda o, l: o - l),
    arg_names=["data", "label"],
    param_schema=dict(_REG_SCHEMA),
    fill_in_shapes=lambda shapes, p: [shapes[0], shapes[1] or shapes[0]],
    is_loss=True,
)

register(
    "MAERegressionOutput",
    _regression_output(lambda d: d, lambda o, l: jnp.sign(o - l)),
    arg_names=["data", "label"],
    param_schema=dict(_REG_SCHEMA),
    fill_in_shapes=lambda shapes, p: [shapes[0], shapes[1] or shapes[0]],
    is_loss=True,
)

register(
    "LogisticRegressionOutput",
    _regression_output(jax.nn.sigmoid, lambda o, l: o - l),
    arg_names=["data", "label"],
    param_schema=dict(_REG_SCHEMA),
    fill_in_shapes=lambda shapes, p: [shapes[0], shapes[1] or shapes[0]],
    is_loss=True,
)


def _svm_output(ins, params, mode):
    data, label = ins
    margin = params["margin"]
    coef = params["regularization_coefficient"]
    use_linear = params["use_linear"]

    @jax.custom_vjp
    def f(d, l):
        return d

    def fwd(d, l):
        return d, (d, l)

    def bwd(res, g):
        d, l = res
        li = l.astype(jnp.int32)
        onehot = jax.nn.one_hot(li, d.shape[1], dtype=d.dtype)
        score_y = jnp.sum(d * onehot, axis=1, keepdims=True)
        viol = margin - score_y + d  # margin violation per class
        mask = ((viol > 0) & (onehot == 0)).astype(d.dtype)
        if use_linear:
            grad_wrong = mask
        else:
            grad_wrong = 2.0 * viol * mask
        grad_correct = -jnp.sum(grad_wrong, axis=1, keepdims=True)
        grad = (grad_wrong + grad_correct * onehot) * coef
        return grad, jnp.zeros_like(l)

    f.defvjp(fwd, bwd)
    return f(data, label)


register(
    "SVMOutput",
    _svm_output,
    arg_names=["data", "label"],
    param_schema={
        "margin": Param(parse_float, 1.0),
        "regularization_coefficient": Param(parse_float, 1.0),
        "use_linear": Param(parse_bool, False),
    },
    fill_in_shapes=lambda shapes, p: [
        shapes[0],
        shapes[1] or ((shapes[0][0],) if shapes[0] else None),
    ],
    is_loss=True,
)


def _smooth_l1(ins, params, mode):
    (x,) = ins
    s2 = params["scalar"] ** 2
    return jnp.where(
        jnp.abs(x) < 1.0 / s2, 0.5 * s2 * jnp.square(x), jnp.abs(x) - 0.5 / s2
    )


register(
    "smooth_l1",
    _smooth_l1,
    arg_names=["data"],
    param_schema={"scalar": Param(parse_float, 1.0)},
)


# --- UpSampling ------------------------------------------------------------
def _upsampling(ins, params, mode):
    scale = params["scale"]
    stype = params["sample_type"]
    if stype == "nearest":
        outs = []
        target = None
        for x in ins:
            up = jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
            if target is None:
                target = up.shape[2:]
            outs.append(up)
        return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    if stype == "bilinear":
        data, weight = ins
        # deconvolution with stride=scale, kernel 2*scale - scale%2
        k = 2 * scale - scale % 2
        p = (scale - 1) // 2 if scale % 2 else scale // 2 - 1
        pad_amt = int(np.ceil((scale - 1) / 2.0))
        return _deconv(
            [data, weight],
            {
                "kernel": (k, k),
                "stride": (scale, scale),
                "pad": (pad_amt, pad_amt),
                "dilate": (1, 1),
                "adj": None,
                "num_filter": params["num_filter"],
                "num_group": data.shape[1],
                "no_bias": True,
                "workspace": 512,
                "cudnn_tune": None,
                "cudnn_off": False,
                "layout": None,
                "target_shape": None,
            },
            mode,
        )
    raise MXNetError(f"UpSampling: unknown sample_type {stype}")


def _upsampling_args(p):
    if p["sample_type"] == "bilinear":
        return ["data", "weight"]
    return [f"arg{i}" for i in range(p["num_args"])] if p["num_args"] > 1 else ["data"]


def _upsampling_fill(shapes, params):
    if params["sample_type"] == "bilinear" and shapes[0] is not None and shapes[1] is None:
        scale = params["scale"]
        k = 2 * scale - scale % 2
        c = shapes[0][1]
        shapes[1] = (c, 1, k, k)
    return shapes


register(
    "UpSampling",
    _upsampling,
    arg_names=_upsampling_args,
    param_schema={
        "scale": Param(parse_int),
        "sample_type": Param(parse_str, "nearest"),
        "num_args": Param(parse_int, 1),
        "num_filter": Param(parse_int, 0),
        "multi_input_mode": Param(parse_str, "concat"),
        "workspace": Param(parse_int, 512),
    },
    fill_in_shapes=_upsampling_fill,
)


# --- sequence ops ----------------------------------------------------------
def _seq_args(p):
    return ["data", "sequence_length"] if p["use_sequence_length"] else ["data"]


_SEQ_SCHEMA = {"use_sequence_length": Param(parse_bool, False)}


def _sequence_last(ins, params, mode):
    x = ins[0]
    if params["use_sequence_length"]:
        seqlen = ins[1].astype(jnp.int32)
        idx = jnp.maximum(seqlen - 1, 0)
        return jnp.take_along_axis(
            x, idx.reshape((1, -1) + (1,) * (x.ndim - 2)), axis=0
        )[0]
    return x[-1]


register(
    "SequenceLast",
    _sequence_last,
    arg_names=_seq_args,
    param_schema=dict(_SEQ_SCHEMA),
)


def _sequence_mask(ins, params, mode):
    x = ins[0]
    if not params["use_sequence_length"]:
        return x
    seqlen = ins[1]
    steps = jnp.arange(x.shape[0]).reshape((-1, 1) + (1,) * (x.ndim - 2))
    mask = steps < seqlen.reshape((1, -1) + (1,) * (x.ndim - 2))
    return jnp.where(mask, x, jnp.asarray(params["value"], x.dtype))


register(
    "SequenceMask",
    _sequence_mask,
    arg_names=_seq_args,
    param_schema={**_SEQ_SCHEMA, "value": Param(parse_float, 0.0)},
)


def _sequence_reverse(ins, params, mode):
    x = ins[0]
    if not params["use_sequence_length"]:
        return jnp.flip(x, axis=0)
    seqlen = ins[1].astype(jnp.int32)
    steps = jnp.arange(x.shape[0]).reshape(-1, 1)
    sl = seqlen.reshape(1, -1)
    rev_idx = jnp.where(steps < sl, sl - 1 - steps, steps)
    return jnp.take_along_axis(
        x, rev_idx.reshape(rev_idx.shape + (1,) * (x.ndim - 2)), axis=0
    )


register(
    "SequenceReverse",
    _sequence_reverse,
    arg_names=_seq_args,
    param_schema=dict(_SEQ_SCHEMA),
)
