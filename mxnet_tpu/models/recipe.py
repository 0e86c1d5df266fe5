"""Shared precision/layout recipes and the analytic FLOPs estimator.

Reference: the explicit fp16 symbol variants
(``example/image-classification/symbols/resnet_fp16.py`` /
``alexnet_fp16.py``) cast the input to fp16 right after the data variable
and cast back to fp32 before the classifier so the softmax/loss runs in
full precision. The TPU recipes generalize that:

- ``f32`` — everything float32 (the parity oracle).
- ``bf16_master`` — bf16 everywhere with f32 master weights: the symbol
  casts activations into the bf16 trunk (:func:`low_precision_io`), the
  executor's master-dtype rule keeps parameters and optimizer state f32
  and casts each parameter at its point of use, and the fused train-update
  epilogue applies the f32 update in the same program — no extra
  parameter-sized writes appear (``tools/hlo_audit.py`` verifies the
  lowered window program: every donated buffer aliased, no stray f32
  upcasts of parameter-sized bf16 values). ``bf16`` is an alias: with the
  master-dtype rule always on, plain bf16 *is* the master-weight recipe.
- ``int8_serving`` — post-training weight quantization for the serving
  path (:func:`int8_weights`): per-tensor symmetric fake-quant of the
  matrix/conv weights, applied by ``ModelServer(variant="int8")`` after
  BN folding; activations stay f32/bf16.

:func:`conv_layout` reports the device layout the executor will lower the
conv stack in (``MXNET_CONV_LAYOUT``, ops/layout.py) so benches and tools
can stamp records without re-deriving the resolution rule.

``estimate_flops`` is the per-symbol analytic model that lets bench report
MFU for every workload (conv/deconv/dense/rnn/attention/routed experts
counted from the serialized graph + inferred shapes) instead of hardcoding
ResNet-50@224. Grouped and
depthwise Convolution count ``in_ch/num_group`` MACs per output — computed
from the node attrs, not the weight-shape lookup, so ResNeXt-style MFU is
not overstated even when the weight input is an already-shaped composite.
"""

import json

import numpy as np

from .. import symbol as sym
from ..base import parse_bool, parse_shape

# name -> (compute/activation dtype, parameter master dtype)
RECIPES = {
    "f32": {"compute_dtype": "float32", "master_dtype": "float32"},
    "bf16": {"compute_dtype": "bfloat16", "master_dtype": "float32"},
    "bf16_master": {"compute_dtype": "bfloat16", "master_dtype": "float32"},
    "int8_serving": {"compute_dtype": "float32", "master_dtype": "float32",
                     "weight_dtype": "int8"},
}


def get(name):
    """The named recipe dict (KeyError lists the catalogue)."""
    try:
        return dict(RECIPES[name])
    except KeyError:
        raise KeyError(
            f"unknown recipe {name!r} (have: {sorted(RECIPES)})") from None


def recipe_name(dtype):
    """Canonical recipe name for a trunk dtype string (bench stamping)."""
    return "bf16_master" if str(dtype) == "bfloat16" else "f32"


def conv_layout(ctx=None):
    """The resolved conv-stack device layout for ``ctx`` ("NCHW"/"NHWC")."""
    from ..ops import layout as _lay

    return _lay.resolve(ctx)


def low_precision_io(x, dtype, out=False):
    """Cast into the low-precision trunk (``out=False``, after data) or
    back to f32 for the classifier head (``out=True``). No-op for f32."""
    if dtype in (None, "float32"):
        return x
    return sym.Cast(x, dtype="float32" if out else dtype)


def quantize_int8(arr):
    """Per-tensor symmetric int8 quantization: ``(q, scale)`` with
    ``q = round(arr / scale)`` clipped to [-127, 127] and
    ``scale = max|arr| / 127`` (scale 1.0 for an all-zero tensor)."""
    a = np.asarray(arr, dtype=np.float32)
    amax = float(np.max(np.abs(a))) if a.size else 0.0
    scale = amax / 127.0 if amax > 0.0 else 1.0
    q = np.clip(np.rint(a / scale), -127, 127).astype(np.int8)
    return q, scale


def dequantize_int8(q, scale):
    """Inverse of :func:`quantize_int8` (float32)."""
    return q.astype(np.float32) * np.float32(scale)


def int8_weights(arg_params, min_size=1024):
    """Post-training int8 weight quantization (fake-quant) for serving.

    Every float parameter with ndim >= 2 and at least ``min_size`` elements
    (the conv/dense weights — biases and folded-BN vectors stay exact) is
    replaced by its quantize-dequantize image, so the graph and kernels are
    unchanged while the weights carry exactly the int8 information content.
    Returns ``(new_params, report)`` where the report maps each quantized
    name to its scale — the serving stats surface.
    """
    out, report = {}, {}
    for name, arr in arg_params.items():
        a = np.asarray(arr)
        if (a.ndim >= 2 and a.size >= min_size
                and np.issubdtype(a.dtype, np.floating)):
            q, scale = quantize_int8(a)
            out[name] = dequantize_int8(q, scale).astype(a.dtype)
            report[name] = scale
        else:
            out[name] = arr
    return out, report


def _prod(xs):
    p = 1
    for x in xs:
        p *= int(x)
    return p


def _node_shape(shape_dict, nodes, node_ref):
    """Inferred output shape of graph input ``node_ref`` = (node_id, out_idx).

    Weight/data nulls are keyed by name; op outputs by ``<name>_output`` (or
    ``<name>_output<idx>`` for multi-output ops). Returns None when the
    internals listing doesn't carry the key.
    """
    node_id, out_idx = node_ref[0], node_ref[1]
    node = nodes[node_id]
    if node["op"] == "null":
        return shape_dict.get(node["name"])
    return shape_dict.get(node["name"] + "_output",
                          shape_dict.get(f"{node['name']}_output{out_idx}"))


def estimate_flops(symbol, batch=None, **shape_kwargs):
    """Analytic forward FLOPs **per sample** for ``symbol``.

    Counts Convolution, Deconvolution, FullyConnected, the fused RNN op,
    RingAttention (a causal one at half its scores, one with a ``window``
    at its band's pairs; under ``select_top_k``
    the pairs each query keeps and the pairs its indexer scores; under
    ``diffusion_block`` the T (T + block) pairs the two copies of a row
    keep), GatedDeltaRule (its
    recurrent form: read, write and query of a keys x values state a token
    and value head), CausalConv1D (``kernel`` taps a channel, times the
    channels of a group where it mixes them) and MoE (the router, where it
    is a product inside the op and not the graph's, and the ``top_k``
    routed experts, not all of them)
    in the published-table convention (one multiply-add = one FLOP, the
    convention behind the ResNet-50 = 4.1 GFLOPs/img figure that bench's
    MFU numbers have used since PR-3); the unrolled LSTM graphs decompose
    into FullyConnected nodes and are covered by the dense formula.
    Elementwise, norm and pool ops are ignored (<1% of zoo-symbol FLOPs).
    Training costs ≈ 3× the forward estimate (forward + input-grad +
    weight-grad passes).

    ``batch`` defaults to the leading dim of the first shape in
    ``shape_kwargs`` — pass it explicitly for layouts whose leading dim is
    not the batch axis (e.g. time-major RNN data).
    """
    nodes = json.loads(symbol.tojson())["nodes"]
    internals = symbol.get_internals()
    _, out_shapes, _ = internals.infer_shape(**shape_kwargs)
    if out_shapes is None:
        raise ValueError("input shapes underdetermine the graph")
    shape_dict = dict(zip(internals.list_outputs(), out_shapes))
    arg_shapes, _, _ = symbol.infer_shape(**shape_kwargs)
    arg_shape = dict(zip(symbol.list_arguments(), arg_shapes))
    if batch is None:
        batch = int(next(iter(shape_kwargs.values()))[0])

    total = 0.0
    for node_id, node in enumerate(nodes):
        op = node["op"]
        if op not in ("Convolution", "Deconvolution", "FullyConnected", "RNN",
                      "RingAttention", "MoE", "GatedDeltaRule",
                      "CausalConv1D"):
            continue
        attrs = node.get("attrs") or {}
        if op == "CausalConv1D":
            # (B, T, C): every channel reads ``kernel`` taps of itself, or
            # of each of its group's C / g channels
            data = _node_shape(shape_dict, nodes, node["inputs"][0])
            if data:
                groups = int(attrs.get("num_group", 0))
                fan_in = int(data[-1]) // groups if groups else 1
                total += _prod(data) * int(attrs["kernel"]) * fan_in / batch
            continue
        if op == "GatedDeltaRule":
            # k (B, Hk, T, Dk), v (B, Hv, T, Dv): S^T k, the rank-1 write
            # and S^T q are Dk x Dv multiply-adds each
            k = _node_shape(shape_dict, nodes, node["inputs"][1])
            v = _node_shape(shape_dict, nodes, node["inputs"][2])
            if k and v:
                total += 3.0 * _prod(v) * int(k[3]) / batch
            continue
        if op == "RingAttention":
            from ..parallel.ring_attention import kept_pairs

            # q (B, H, T, Dk) . k over Dk and p . v (B, Hkv, T, Dv) over Dv,
            # a causal row sees half the keys
            q = _node_shape(shape_dict, nodes, node["inputs"][0])
            v = _node_shape(shape_dict, nodes, node["inputs"][2])
            top_k = int(attrs.get("select_top_k", 0))
            block = int(attrs.get("diffusion_block", 0))
            if q and v and block > 0:
                # block diffusion: the batch is two copies of q[0] / 2 rows,
                # which keep T (T + block) pairs a head between them
                t = int(q[2])
                total += (int(q[0]) // 2) * int(q[1]) * t * (t + block) * (
                    int(q[3]) + int(v[3])) / batch
            elif q and v and top_k > 0:
                # a selection: query t keeps min(t + 1, top_k) keys, and the
                # indexer (index_query (B, J, T, Di), one key head) scores
                # every earlier one
                t = int(q[2])
                iq = _node_shape(shape_dict, nodes, node["inputs"][3])
                total += _prod(q[:2]) * kept_pairs(t, top_k) * (
                    int(q[3]) + int(v[3])) / batch
                if iq:
                    total += _prod(iq[:2]) * (t * (t + 1) // 2) * int(
                        iq[3]) / batch
            elif q and v and 0 < int(attrs.get("window", 0)) < int(q[2]):
                # a band: query t reads its min(t + 1, window) keys
                total += _prod(q[:2]) * kept_pairs(
                    int(q[2]), int(attrs["window"])) * (
                        int(q[3]) + int(v[3])) / batch
            elif q and v:
                seen = 0.5 if parse_bool(attrs.get("causal", False)) else 1.0
                total += seen * _prod(q[:3]) * int(q[2]) * (
                    int(q[3]) + int(v[3])) / batch
            continue
        if op == "MoE":
            # every row through the router, and through gate, up and down
            # of the top_k experts it is routed to (not of all of them)
            data = _node_shape(shape_dict, nodes, node["inputs"][0])
            if data:
                h, f = int(data[-1]), int(attrs["num_hidden"])
                # a router of the graph's is counted by its own nodes
                router = int(attrs["num_experts"]) * (
                    attrs.get("router", "weight") == "weight")
                total += (_prod(data[:-1]) / batch) * h * (
                    router + int(attrs["top_k"]) * 3 * f)
            continue
        if op == "RNN":
            # data (T, N, C); per layer/dir: gates × h × (in + h) MACs/step
            data_shape = _node_shape(shape_dict, nodes, node["inputs"][0])
            if not data_shape:
                continue
            seq_len, _, in_dim = (int(d) for d in data_shape[:3])
            h = int(attrs["state_size"])
            layers = int(attrs["num_layers"])
            dirs = 2 if attrs.get("bidirectional", "False") == "True" else 1
            gates = {"lstm": 4, "gru": 3}.get(attrs.get("mode"), 1)
            macs = 0
            for layer in range(layers):
                in_l = in_dim if layer == 0 else h * dirs
                macs += dirs * gates * h * (in_l + h)
            total += 1.0 * seq_len * macs
            continue
        w = arg_shape.get(nodes[node["inputs"][1][0]]["name"])
        if op == "FullyConnected":
            if not w:
                continue
            # MACs = rows × num_hidden × in_dim; rows may exceed batch when
            # the graph folds time into the leading axis (seq-major heads)
            in_shape = _node_shape(shape_dict, nodes, node["inputs"][0])
            rows = int(in_shape[0]) if in_shape else batch
            if in_shape and not parse_bool(attrs.get("flatten", True)):
                rows = _prod(in_shape[:-1])  # FC over the last axis
            total += 1.0 * (rows / batch) * _prod(w)
        elif op == "Convolution":
            out = _node_shape(shape_dict, nodes, (node_id, 0))
            in_shape = _node_shape(shape_dict, nodes, node["inputs"][0])
            if not out:
                continue
            # per output position × per filter: in_ch/num_group × kh × kw
            # MACs — from the node attrs + input shape, so grouped/depthwise
            # convs (ResNeXt, MobileNet-style) and convs whose weight input
            # is not a plain null arg are both counted correctly (the old
            # weight-shape lookup silently skipped the latter)
            kernel = parse_shape(attrs.get("kernel", "()"))
            groups = int(attrs.get("num_group", 1))
            if in_shape and kernel:
                macs_per_pos = (
                    int(attrs["num_filter"]) * (int(in_shape[1]) // groups)
                    * _prod(kernel)
                )
            elif w:
                macs_per_pos = _prod(w)  # weight is (nf, in_ch/g, *k)
            else:
                continue
            total += 1.0 * _prod(out[2:]) * macs_per_pos
        else:  # Deconvolution: each input pixel scatters a full kernel
            if not w:
                continue
            in_shape = _node_shape(shape_dict, nodes, node["inputs"][0])
            if not in_shape:
                continue
            total += 1.0 * _prod(in_shape[2:]) * _prod(w)
    return total
