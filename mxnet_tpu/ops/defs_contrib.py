"""Contrib / detection operators.

Reference: ``src/operator/contrib/`` — the SSD triple ``multibox_prior`` /
``multibox_target`` / ``multibox_detection`` (multibox_*.{cc,cu,-inl.h}),
RCNN ``proposal``, ``count_sketch``, ``fft``/``ifft``. These are the ops the
reference wrote as genuinely custom CUDA kernels; here they are composed-jax
(batched IOU matrices + masked top-k NMS — shapes static, so XLA compiles
them into the same fused graph as the network; a Pallas kernel is only
warranted if profiling shows the NMS loop dominating).

All box math follows the reference conventions: corner format
(xmin, ymin, xmax, ymax) normalized to [0,1], encode/decode with variances.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..base import (
    MXNetError,
    parse_bool,
    parse_float,
    parse_int,
    parse_shape,
    parse_str,
)
from .registry import Param, register


def _parse_floats(v):
    if v is None:
        return ()
    if isinstance(v, (tuple, list)):
        return tuple(float(x) for x in v)
    import ast

    val = ast.literal_eval(str(v))
    if isinstance(val, (int, float)):
        return (float(val),)
    return tuple(float(x) for x in val)


# --- multibox_prior --------------------------------------------------------
def _multibox_prior(ins, params, mode):
    (data,) = ins
    in_h, in_w = data.shape[2], data.shape[3]
    sizes = params["sizes"]
    ratios = params["ratios"]
    steps = params["steps"] or (-1.0, -1.0)
    offsets = params["offsets"]
    step_y = steps[0] if steps[0] > 0 else 1.0 / in_h
    step_x = steps[1] if steps[1] > 0 else 1.0 / in_w
    num_anchors = len(sizes) + len(ratios) - 1

    cy = (jnp.arange(in_h, dtype=jnp.float32) + offsets[0]) * step_y
    cx = (jnp.arange(in_w, dtype=jnp.float32) + offsets[1]) * step_x
    cyg, cxg = jnp.meshgrid(cy, cx, indexing="ij")  # (h, w)

    # reference ordering: (size_k, ratio_0) for all k, then (size_0, ratio_k>0)
    ws, hs = [], []
    for k, s in enumerate(sizes):
        r = ratios[0]
        ws.append(s * math.sqrt(r) / 2.0)
        hs.append(s / math.sqrt(r) / 2.0)
    for r in ratios[1:]:
        s = sizes[0]
        ws.append(s * math.sqrt(r) / 2.0)
        hs.append(s / math.sqrt(r) / 2.0)
    ws = jnp.asarray(ws, jnp.float32)  # (A,)
    hs = jnp.asarray(hs, jnp.float32)

    cxg = cxg[:, :, None]
    cyg = cyg[:, :, None]
    boxes = jnp.stack(
        [cxg - ws, cyg - hs, cxg + ws, cyg + hs], axis=-1
    )  # (h, w, A, 4)
    out = boxes.reshape(1, in_h * in_w * num_anchors, 4)
    if params["clip"]:
        out = jnp.clip(out, 0.0, 1.0)
    return out


register(
    "MultiBoxPrior",
    _multibox_prior,
    arg_names=["data"],
    param_schema={
        "sizes": Param(_parse_floats, (1.0,)),
        "ratios": Param(_parse_floats, (1.0,)),
        "clip": Param(parse_bool, False),
        "steps": Param(_parse_floats, None),
        "offsets": Param(_parse_floats, (0.5, 0.5)),
    },
    aliases=("_contrib_MultiBoxPrior", "multibox_prior"),
)


# --- box helpers -----------------------------------------------------------
def _iou_matrix(anchors, gt):
    """anchors (A, 4) x gt (G, 4) → IOU (A, G), corner format."""
    ax1, ay1, ax2, ay2 = [anchors[:, i, None] for i in range(4)]
    gx1, gy1, gx2, gy2 = [gt[None, :, i] for i in range(4)]
    iw = jnp.maximum(0.0, jnp.minimum(ax2, gx2) - jnp.maximum(ax1, gx1))
    ih = jnp.maximum(0.0, jnp.minimum(ay2, gy2) - jnp.maximum(ay1, gy1))
    inter = iw * ih
    area_a = jnp.maximum(0.0, ax2 - ax1) * jnp.maximum(0.0, ay2 - ay1)
    area_g = jnp.maximum(0.0, gx2 - gx1) * jnp.maximum(0.0, gy2 - gy1)
    union = area_a + area_g - inter
    return jnp.where(union > 0, inter / union, 0.0)


def _encode_boxes(matched_gt, anchors, variances):
    """Corner→center offset encoding (reference multibox_target TransformLocations)."""
    aw = anchors[:, 2] - anchors[:, 0]
    ah = anchors[:, 3] - anchors[:, 1]
    acx = (anchors[:, 0] + anchors[:, 2]) / 2
    acy = (anchors[:, 1] + anchors[:, 3]) / 2
    gw = matched_gt[:, 2] - matched_gt[:, 0]
    gh = matched_gt[:, 3] - matched_gt[:, 1]
    gcx = (matched_gt[:, 0] + matched_gt[:, 2]) / 2
    gcy = (matched_gt[:, 1] + matched_gt[:, 3]) / 2
    eps = 1e-8
    tx = (gcx - acx) / jnp.maximum(aw, eps) / variances[0]
    ty = (gcy - acy) / jnp.maximum(ah, eps) / variances[1]
    tw = jnp.log(jnp.maximum(gw / jnp.maximum(aw, eps), eps)) / variances[2]
    th = jnp.log(jnp.maximum(gh / jnp.maximum(ah, eps), eps)) / variances[3]
    return jnp.stack([tx, ty, tw, th], axis=1)


def _decode_boxes(loc, anchors, variances, clip):
    aw = anchors[:, 2] - anchors[:, 0]
    ah = anchors[:, 3] - anchors[:, 1]
    acx = (anchors[:, 0] + anchors[:, 2]) / 2
    acy = (anchors[:, 1] + anchors[:, 3]) / 2
    cx = loc[:, 0] * variances[0] * aw + acx
    cy = loc[:, 1] * variances[1] * ah + acy
    w = jnp.exp(loc[:, 2] * variances[2]) * aw / 2
    h = jnp.exp(loc[:, 3] * variances[3]) * ah / 2
    out = jnp.stack([cx - w, cy - h, cx + w, cy + h], axis=1)
    if clip:
        out = jnp.clip(out, 0.0, 1.0)
    return out


# --- multibox_target -------------------------------------------------------
def _multibox_target(ins, params, mode):
    anchors, label, cls_pred = ins
    # anchors (1, A, 4); label (n, G, 5+) [cls, x1, y1, x2, y2]; cls_pred
    # (n, num_cls+1, A)
    A = anchors.shape[1]
    anc = anchors[0]
    thr = params["overlap_threshold"]
    ignore = params["ignore_label"]
    neg_ratio = params["negative_mining_ratio"]
    neg_thresh = params["negative_mining_thresh"]
    min_neg = params["minimum_negative_samples"]
    var = params["variances"]

    def one_sample(lbl, cpred):
        valid_gt = lbl[:, 0] >= 0  # (G,)
        gt_boxes = lbl[:, 1:5]
        iou = _iou_matrix(anc, gt_boxes)  # (A, G)
        iou = jnp.where(valid_gt[None, :], iou, -1.0)

        best_gt = jnp.argmax(iou, axis=1)  # (A,)
        best_iou = jnp.max(iou, axis=1)
        # force-match: each gt's best anchor
        best_anchor_per_gt = jnp.argmax(iou, axis=0)  # (G,)
        forced = jnp.zeros((A,), bool).at[best_anchor_per_gt].set(valid_gt)
        matched = forced | (best_iou >= thr)

        matched_gt_idx = jnp.where(
            forced,
            jnp.argmax(
                jnp.where(
                    (jnp.arange(A)[:, None] == best_anchor_per_gt[None, :])
                    & valid_gt[None, :],
                    iou + 2.0, iou,
                ), axis=1,
            ),
            best_gt,
        )
        matched_boxes = gt_boxes[matched_gt_idx]
        matched_cls = lbl[matched_gt_idx, 0]

        loc_t = _encode_boxes(matched_boxes, anc, var)
        loc_t = jnp.where(matched[:, None], loc_t, 0.0)
        loc_mask = jnp.where(matched[:, None], 1.0, 0.0)
        loc_mask = jnp.tile(loc_mask, (1, 4))[:, :4] * jnp.ones((A, 4))

        cls_t = jnp.where(matched, matched_cls + 1.0, 0.0)
        if neg_ratio > 0:
            # hard negative mining by background confidence deficit
            num_pos = jnp.sum(matched)
            max_neg = jnp.maximum(
                (neg_ratio * num_pos).astype(jnp.int32), min_neg
            )
            bg_prob = cpred[0]  # (A,) background scores (post-softmax upstream)
            neg_score = -bg_prob  # less background-confident = harder negative
            neg_cand = (~matched) & (best_iou < neg_thresh)
            score = jnp.where(neg_cand, neg_score, -jnp.inf)
            order = jnp.argsort(-score)
            rank = jnp.zeros((A,), jnp.int32).at[order].set(jnp.arange(A, dtype=jnp.int32))
            keep_neg = neg_cand & (rank < max_neg)
            cls_t = jnp.where(matched, cls_t, jnp.where(keep_neg, 0.0, ignore))
        return loc_t.reshape(-1), loc_mask.reshape(-1), cls_t

    loc_target, loc_mask, cls_target = jax.vmap(one_sample)(label, cls_pred)
    return [loc_target, loc_mask, cls_target]


register(
    "MultiBoxTarget",
    _multibox_target,
    arg_names=["anchor", "label", "cls_pred"],
    param_schema={
        "overlap_threshold": Param(parse_float, 0.5),
        "ignore_label": Param(parse_float, -1.0),
        "negative_mining_ratio": Param(parse_float, -1.0),
        "negative_mining_thresh": Param(parse_float, 0.5),
        "minimum_negative_samples": Param(parse_int, 0),
        "variances": Param(_parse_floats, (0.1, 0.1, 0.2, 0.2)),
    },
    num_outputs=3,
    aliases=("_contrib_MultiBoxTarget", "multibox_target"),
)


# --- multibox_detection ----------------------------------------------------
def _nms_keep(boxes, scores, valid, nms_threshold, force, cls_ids):
    """Masked O(k^2) NMS over statically-shaped arrays. Returns keep mask."""
    A = boxes.shape[0]
    order = jnp.argsort(-scores)
    boxes_o = boxes[order]
    valid_o = valid[order]
    cls_o = cls_ids[order]
    iou = _iou_matrix(boxes_o, boxes_o)  # (A, A)
    same_cls = (cls_o[:, None] == cls_o[None, :]) | force
    sup_matrix = (iou > nms_threshold) & same_cls
    tri = jnp.tril(jnp.ones((A, A), bool), k=-1)  # j < i suppresses i

    def body(i, keep):
        suppressed = jnp.any(sup_matrix[i] & tri[i] & keep & valid_o)
        return keep.at[i].set(keep[i] & ~suppressed)

    keep = jax.lax.fori_loop(0, A, body, valid_o)
    # scatter back to original order
    inv = jnp.zeros((A,), jnp.int32).at[order].set(jnp.arange(A, dtype=jnp.int32))
    return keep[inv]


def _multibox_detection(ins, params, mode):
    cls_prob, loc_pred, anchors = ins
    # cls_prob (n, num_cls+1, A); loc_pred (n, A*4); anchors (1, A, 4)
    n, num_cls_p1, A = cls_prob.shape
    anc = anchors[0]
    var = params["variances"]
    thr = params["threshold"]

    def one(cp, lp):
        boxes = _decode_boxes(lp.reshape(A, 4), anc, var, params["clip"])
        fg = cp[1:]  # (C, A)
        cls_id = jnp.argmax(fg, axis=0)  # (A,)
        score = jnp.max(fg, axis=0)
        valid = score > thr
        keep = _nms_keep(
            boxes, score, valid, params["nms_threshold"],
            params["force_suppress"], cls_id,
        )
        out_id = jnp.where(keep, cls_id.astype(jnp.float32), -1.0)
        return jnp.concatenate(
            [out_id[:, None], score[:, None], boxes], axis=1
        )  # (A, 6)

    return jax.vmap(one)(cls_prob, loc_pred)


register(
    "MultiBoxDetection",
    _multibox_detection,
    arg_names=["cls_prob", "loc_pred", "anchor"],
    param_schema={
        "clip": Param(parse_bool, True),
        "threshold": Param(parse_float, 0.01),
        "background_id": Param(parse_int, 0),
        "nms_threshold": Param(parse_float, 0.5),
        "force_suppress": Param(parse_bool, False),
        "variances": Param(_parse_floats, (0.1, 0.1, 0.2, 0.2)),
        "nms_topk": Param(parse_int, -1),
    },
    aliases=("_contrib_MultiBoxDetection", "multibox_detection"),
)


# --- ROIPooling ------------------------------------------------------------
def _roi_pooling(ins, params, mode):
    data, rois = ins
    # data (n, c, h, w); rois (R, 5) [batch_idx, x1, y1, x2, y2] in image coords
    ph, pw = params["pooled_size"]
    scale = params["spatial_scale"]
    n, c, h, w = data.shape

    def one_roi(roi):
        bidx = roi[0].astype(jnp.int32)
        x1 = jnp.round(roi[1] * scale).astype(jnp.int32)
        y1 = jnp.round(roi[2] * scale).astype(jnp.int32)
        x2 = jnp.round(roi[3] * scale).astype(jnp.int32)
        y2 = jnp.round(roi[4] * scale).astype(jnp.int32)
        rh = jnp.maximum(y2 - y1 + 1, 1)
        rw = jnp.maximum(x2 - x1 + 1, 1)
        img = data[bidx]  # (c, h, w)

        ys = jnp.arange(h)
        xs = jnp.arange(w)

        def pool_cell(py, px):
            hstart = y1 + (py * rh) // ph
            hend = y1 + -(-((py + 1) * rh) // ph)
            wstart = x1 + (px * rw) // pw
            wend = x1 + -(-((px + 1) * rw) // pw)
            mask = (
                (ys[:, None] >= hstart) & (ys[:, None] < jnp.minimum(hend, h))
                & (xs[None, :] >= wstart) & (xs[None, :] < jnp.minimum(wend, w))
            )
            empty = ~jnp.any(mask)
            vals = jnp.where(mask[None], img, -jnp.inf)
            out = jnp.max(vals, axis=(1, 2))
            return jnp.where(empty, 0.0, out)

        grid = jax.vmap(
            lambda py: jax.vmap(lambda px: pool_cell(py, px))(jnp.arange(pw))
        )(jnp.arange(ph))  # (ph, pw, c)
        return jnp.transpose(grid, (2, 0, 1))  # (c, ph, pw)

    return jax.vmap(one_roi)(rois)


register(
    "ROIPooling",
    _roi_pooling,
    arg_names=["data", "rois"],
    param_schema={
        "pooled_size": Param(parse_shape),
        "spatial_scale": Param(parse_float),
    },
)


# --- box_nms (generic NMS used by detection examples) ----------------------
def _fft(ins, params, mode):
    (x,) = ins
    out = jnp.fft.fft(x.astype(jnp.complex64), axis=-1)
    return jnp.concatenate([out.real, out.imag], axis=-1).astype(jnp.float32)


register(
    "fft",
    _fft,
    arg_names=["data"],
    param_schema={"compute_size": Param(parse_int, 128)},
    aliases=("_contrib_fft",),
)


def _ifft(ins, params, mode):
    (x,) = ins
    n = x.shape[-1] // 2
    comp = x[..., :n] + 1j * x[..., n:]
    return jnp.fft.ifft(comp, axis=-1).real.astype(jnp.float32)


register(
    "ifft",
    _ifft,
    arg_names=["data"],
    param_schema={"compute_size": Param(parse_int, 128)},
    aliases=("_contrib_ifft",),
)


def _count_sketch(ins, params, mode):
    data, h, s = ins
    out_dim = params["out_dim"]
    idx = h.astype(jnp.int32).reshape(-1)
    sign = s.reshape(-1)
    contrib = data * sign[None, :]
    out = jnp.zeros((data.shape[0], out_dim), data.dtype)
    return out.at[:, idx].add(contrib)


register(
    "count_sketch",
    _count_sketch,
    arg_names=["data", "h", "s"],
    param_schema={
        "out_dim": Param(parse_int),
        "processing_batch_size": Param(parse_int, 32),
    },
    aliases=("_contrib_count_sketch",),
)


# --- Proposal (RPN, reference src/operator/contrib/proposal-inl.h) ----------
def _generate_anchors(base_size, ratios, scales):
    """py-faster-rcnn anchor enumeration with the reference's rounding
    (proposal-inl.h utils::GenerateAnchors): ratios first, then scales."""
    base = np.array([0, 0, base_size - 1, base_size - 1], np.float32)
    w = base[2] - base[0] + 1
    h = base[3] - base[1] + 1
    x_ctr = base[0] + 0.5 * (w - 1)
    y_ctr = base[1] + 0.5 * (h - 1)
    anchors = []
    for r in ratios:
        size = w * h
        size_ratio = size / r
        ws = round(math.sqrt(size_ratio))
        hs = round(ws * r)
        for s in scales:
            sw, sh = ws * s, hs * s
            anchors.append([
                x_ctr - 0.5 * (sw - 1), y_ctr - 0.5 * (sh - 1),
                x_ctr + 0.5 * (sw - 1), y_ctr + 0.5 * (sh - 1),
            ])
    return np.array(anchors, np.float32)


def _proposal(ins, params, mode):
    """RPN proposal layer: anchors + deltas → clip → min-size filter →
    pre-NMS top-k → greedy NMS → post-NMS top-k. One fused XLA program —
    the sort/IOU-matrix NMS replaces the reference's CUDA workspace kernels.
    """
    cls_prob, bbox_pred, im_info = ins
    B, twoA, H, W = cls_prob.shape
    if B != 1:
        raise MXNetError("Proposal: only batch size 1 supported (reference parity)")
    A = twoA // 2
    stride = params["feature_stride"]
    anchors = jnp.asarray(
        _generate_anchors(stride, params["ratios"], params["scales"])
    )  # (A, 4)
    # all shifted anchors, row-major over (H, W, A) like the reference
    shift_x = jnp.arange(W, dtype=jnp.float32) * stride
    shift_y = jnp.arange(H, dtype=jnp.float32) * stride
    sx, sy = jnp.meshgrid(shift_x, shift_y)
    shifts = jnp.stack([sx, sy, sx, sy], axis=-1).reshape(-1, 1, 4)  # (H*W,1,4)
    all_anchors = (anchors[None] + shifts).reshape(-1, 4)  # (H*W*A, 4)

    scores = cls_prob[0, A:].transpose(1, 2, 0).reshape(-1)  # fg scores (H*W*A)
    deltas = bbox_pred[0].transpose(1, 2, 0).reshape(-1, 4)

    # BBoxTransformInv (proposal-inl.h): deltas → proposals
    ws = all_anchors[:, 2] - all_anchors[:, 0] + 1.0
    hs = all_anchors[:, 3] - all_anchors[:, 1] + 1.0
    ctr_x = all_anchors[:, 0] + 0.5 * (ws - 1.0)
    ctr_y = all_anchors[:, 1] + 0.5 * (hs - 1.0)
    if params["iou_loss"]:
        x1 = all_anchors[:, 0] + deltas[:, 0]
        y1 = all_anchors[:, 1] + deltas[:, 1]
        x2 = all_anchors[:, 2] + deltas[:, 2]
        y2 = all_anchors[:, 3] + deltas[:, 3]
    else:
        pred_ctr_x = deltas[:, 0] * ws + ctr_x
        pred_ctr_y = deltas[:, 1] * hs + ctr_y
        pred_w = jnp.exp(deltas[:, 2]) * ws
        pred_h = jnp.exp(deltas[:, 3]) * hs
        x1 = pred_ctr_x - 0.5 * (pred_w - 1.0)
        y1 = pred_ctr_y - 0.5 * (pred_h - 1.0)
        x2 = pred_ctr_x + 0.5 * (pred_w - 1.0)
        y2 = pred_ctr_y + 0.5 * (pred_h - 1.0)
    im_h, im_w = im_info[0, 0], im_info[0, 1]
    x1 = jnp.clip(x1, 0, im_w - 1.0)
    y1 = jnp.clip(y1, 0, im_h - 1.0)
    x2 = jnp.clip(x2, 0, im_w - 1.0)
    y2 = jnp.clip(y2, 0, im_h - 1.0)
    boxes = jnp.stack([x1, y1, x2, y2], axis=1)

    # min-size filter scaled by im_info scale (FilterBox)
    min_size = params["rpn_min_size"] * im_info[0, 2]
    keep_size = ((x2 - x1 + 1.0) >= min_size) & ((y2 - y1 + 1.0) >= min_size)
    scores = jnp.where(keep_size, scores, -jnp.inf)

    pre_nms = min(params["rpn_pre_nms_top_n"], boxes.shape[0])
    post_nms = params["rpn_post_nms_top_n"]
    top_scores, top_idx = jax.lax.top_k(scores, pre_nms)
    top_boxes = boxes[top_idx]

    # greedy NMS over score-sorted boxes (reference NonMaximumSuppression)
    iou = _iou_matrix_corner_pixel(top_boxes)
    sup = iou >= params["threshold"]
    tri = jnp.tril(jnp.ones((pre_nms, pre_nms), bool), k=-1)
    valid = top_scores > -jnp.inf

    def body(i, keep):
        suppressed = jnp.any(sup[i] & tri[i] & keep)
        return keep.at[i].set(keep[i] & ~suppressed)

    keep = jax.lax.fori_loop(0, pre_nms, body, valid)
    # kept boxes first (stable), pad by repeating the top proposal like the
    # reference pads its fixed-size output workspace; small feature maps can
    # have fewer than post_nms candidates
    order = jnp.argsort(~keep, stable=True)
    take = min(post_nms, pre_nms)
    sel = order[:take]
    n_keep = jnp.sum(keep)
    sel = jnp.where(jnp.arange(take) < n_keep, sel, sel[0])
    if take < post_nms:
        sel = jnp.concatenate(
            [sel, jnp.broadcast_to(sel[:1], (post_nms - take,))]
        )
    out_boxes = top_boxes[sel]
    out_scores = top_scores[sel].reshape(-1, 1)
    rois = jnp.concatenate(
        [jnp.zeros((post_nms, 1), boxes.dtype), out_boxes], axis=1
    )
    return [rois, out_scores]


def _iou_matrix_corner_pixel(boxes):
    """Pairwise IOU with the +1 pixel convention the RPN uses."""
    x1, y1, x2, y2 = boxes[:, 0], boxes[:, 1], boxes[:, 2], boxes[:, 3]
    area = (x2 - x1 + 1.0) * (y2 - y1 + 1.0)
    ix1 = jnp.maximum(x1[:, None], x1[None, :])
    iy1 = jnp.maximum(y1[:, None], y1[None, :])
    ix2 = jnp.minimum(x2[:, None], x2[None, :])
    iy2 = jnp.minimum(y2[:, None], y2[None, :])
    iw = jnp.maximum(ix2 - ix1 + 1.0, 0.0)
    ih = jnp.maximum(iy2 - iy1 + 1.0, 0.0)
    inter = iw * ih
    return inter / (area[:, None] + area[None, :] - inter)


register(
    "Proposal",
    _proposal,
    arg_names=["cls_prob", "bbox_pred", "im_info"],
    param_schema={
        "rpn_pre_nms_top_n": Param(parse_int, 6000),
        "rpn_post_nms_top_n": Param(parse_int, 300),
        "threshold": Param(parse_float, 0.7),
        "rpn_min_size": Param(parse_int, 16),
        "scales": Param(_parse_floats, (4.0, 8.0, 16.0, 32.0)),
        "ratios": Param(_parse_floats, (0.5, 1.0, 2.0)),
        "feature_stride": Param(parse_int, 16),
        "output_score": Param(parse_bool, False),
        "iou_loss": Param(parse_bool, False),
    },
    num_outputs=2,
    num_visible_outputs=lambda p: 2 if p["output_score"] else 1,
    aliases=("_contrib_Proposal", "proposal"),
)


# --- RingAttention (sequence/context parallelism as a graph op) ------------
def _ring_attention_op(ins, params, mode):
    """Sequence-parallel attention as a first-class symbol op.

    NEW surface beyond the reference (its only long-sequence tool is
    bucketing, SURVEY.md §2.5): q/k/v are (B, H, T, D); when a mesh with
    the configured sequence axis is installed (``mx.parallel.with_mesh``)
    at trace time, attention runs as blockwise ring attention — K/V blocks
    rotate over ICI via ppermute inside the caller's jitted program
    (parallel/ring_attention.py); without one it is the same online
    softmax over blocks of queries on one device (``blockwise_attention``:
    exact, and linear in T where the whole score matrix is quadratic), so
    the same symbol serves single-chip and sequence-parallel runs. Query
    and key are (B, H, T, Dk), value (B, H, T, Dv) and the output takes the
    value's width: Dv may differ from Dk (a latent-attention head scores
    over 192 and weighs values of 128), on every path. On one TPU with
    bfloat16 operands, head widths the kernels take (``flash_attention.plan``)
    and T a multiple of a block, that one-device path is the fused Pallas kernels of
    ``ops/flash_attention.py`` (forward and backward; no score tile in
    HBM); the rule is ``ring_attention.kernel_plan``, from what is observed
    at trace time (``mode.platform``: the executor's), and everything else
    takes ``jax.numpy`` blocks.

    The one-device path alone has ``window`` (causal: a query reads the
    ``window`` keys ending at itself; key blocks outside the band are
    skipped, not masked) and grouped heads: key and value (B, Hkv, T, D)
    with Hkv dividing H, query head n reading key/value head
    ``n // (H / Hkv)`` with no repeated copy. The ring path refuses both
    by name.

    ``select_top_k`` K > 0 (causal, no window, one device): three further
    inputs, ``index_query`` (B, J, T, Di), ``index_key`` (B, 1, T, Di) and
    ``index_weight`` (B, J, T), an indexer (DeepSeek-V3.2's): query ``t``
    keeps the ``min(K, t + 1)`` earlier positions of largest ``I[t, s] =
    sum_j index_weight[j, t] relu(index_query[j, t] . index_key[s])``
    (float32; scores equal to the K-th are all kept, so a row whose K-th and
    next scores are equal keeps more than K) and the softmax runs
    over those alone (``ring_attention.selected_attention`` is the
    specification and the ``jax.numpy`` blocks; where the kernels' rule
    answers a plan, one TPU with a bfloat16 trunk and indexer, it runs as
    ``ring_attention.selected_kernels``: the thresholds found in VMEM, the
    kept pairs handed to the fused kernels as int8 tiles, no float32 tile
    of queries by keys in HBM; the ring refuses it by name). The choice
    passes no gradient. The
    index inputs learn from a term of their own that backward attaches, as
    ``MoE`` attaches its router's: ``index_loss_coef`` x the sum over rows
    of ``KL(P || softmax_S(I))``, ``P`` the mean over the query heads of
    the kept keys' probabilities, a constant; query, key and value never
    see it. Where K >= T the output is the causal attention's bit for bit.

    ``diffusion_block`` Bd > 0 (causal, one device, neither ``window`` nor
    ``select_top_k``: refused by name, as the ring path refuses the mode):
    the attention of a block-diffusion training step (Arriola et al. 2025,
    arXiv:2503.09573). The batch axis holds two copies of every row, the
    NOISED ones first and the CLEAN ones after, (2B, H, T, D), row r and
    row r + B the same text at the same positions; with b(i) = i // Bd a
    noised query sees the noised keys of its own block (both directions)
    and the clean keys of the blocks before it, a clean query the clean
    keys of its own block and before, and each row's softmax runs over all
    it sees (``ring_attention.diffusion_attention``: the table, the
    ``jax.numpy`` specification, and the fused kernels with the block-cut
    diagonal where their rule engages; tiles the mask empties are not
    visited on either path). 0: off, and the operator traces to what it was.
    """
    from ..parallel.mesh import current_mesh
    from ..parallel.ring_attention import ring_attention_traced

    q, k, v = ins[:3]
    scale = params["scale"] if params["scale"] > 0 else None
    select = None
    if params["select_top_k"] > 0:
        select = tuple(ins[3:6]) + (params["select_top_k"],
                                    params["index_loss_coef"])
    return ring_attention_traced(
        q, k, v, current_mesh(), axis=params["axis_name"],
        causal=params["causal"], scale=scale,
        batch_axis=params["batch_axis"] or None, window=params["window"],
        platform=mode.platform, select=select,
        **({"diffusion_block": params["diffusion_block"]}
           if params["diffusion_block"] > 0 else {}),
    ).astype(q.dtype)


def _ring_attention_counts(ins, outs, params, platform):
    """A launch's counts for one node, from the one-device path's own ask
    of its rule (``ring_attention.kernel_plan``, as ``_on_one_device`` asks
    it): whether it has a ``window``, and then the exact pairs its band
    keeps (query ``t`` its ``min(t + 1, window)``, x heads x batch) beside
    the pairs its tiles score, so that their ratio is what the band's edges
    cost: the blocks a band cuts are scored whole
    (``executor.attention_band_kept_pairs`` / ``_scored_pairs``; a full
    layer adds to neither); whether a train program runs it in the
    fused Pallas kernels; the query-key pairs of the tiles it visits,
    forward (backward recomputes the same): the kernels' visit list at the
    plan's tiles where they engage, else the ``jax.numpy`` blocks'
    (``ring_attention.scored_pairs``), x heads x batch; whether its values
    are narrower or wider than its keys (a latent-attention head: 192 over
    128); and the lanes a pair is computed over: the width its score
    contracts over plus the width ``p.v`` writes, as either path is handed
    them (neither pads a width: 192 over 128 are 320; a model that padded
    its keys to 256 would hand over 384). Under a selection
    (``select_top_k``) also: the pairs the softmax keeps, query ``t`` its
    ``min(t + 1, K)``, x heads x batch; the pairs the indexer scores, its
    heads x the causal triangle x batch; and the scored pairs are those of
    the kernels' causal visit list at the plan's tiles where they engage,
    else of the selected walk's (``ring_attention.selected_scored_pairs``),
    so the gap between scored and selected pairs is what is computed and
    masked away. Under ``diffusion_block`` the batch is the two copies of
    ``batch / 2`` rows: the rule is asked for one copy's rows, the scored
    pairs are those of the two block-cut causal walks (the kernels' visit
    list twice, or ``ring_attention.diffusion_scored_pairs``) and of the
    noised copy's own blocks: where the kernels engage a query block's own
    tile inside the strict walk's kernels (``T x bq`` a head, most of it
    masked; ``executor.attention_own_tile_layers`` counts the layer), else
    the ``jax.numpy`` squares (``T x Bd``); the kept pairs are the exact ``T (T +
    Bd)`` a head and row (``ring_attention.diffusion_kept_pairs``), and the
    trunk rows are the rows x positions of the queries the node is handed,
    both copies (over ``executor.diffusion_noised_rows`` and the layers:
    the trunk rows a token costs, whatever the model's builder made of it)."""
    from ..parallel.ring_attention import (block_q_of, diffusion_kept_pairs,
                                           diffusion_scored_pairs,
                                           kept_pairs, kernel_plan,
                                           scored_pairs, select_block_q,
                                           selected_scored_pairs)
    from . import flash_attention

    q, k, v = ins[:3]
    causal, window = params["causal"], params["window"]
    top_k = params["select_top_k"]
    batch, heads, T, key_dim = q.shape
    block = params.get("diffusion_block", 0)
    if block > 0:
        trunk_rows = batch * T
        batch //= 2     # the rows: each is there twice
    kernels = kernel_plan(q.dtype, (batch,) + tuple(q.shape[1:]), k.shape[1],
                          causal, window, platform, v.shape[-1], top_k,
                          ins[3] if top_k > 0 else None, block)
    if block > 0:
        pairs = diffusion_scored_pairs(T, block, block_q_of(batch, heads, T)) \
            if kernels is None else T * kernels.bq + 2 * \
            flash_attention.scored_pairs(T, kernels.bq, kernels.bk, True)
    elif kernels is not None:
        pairs = flash_attention.scored_pairs(T, kernels.bq, kernels.bk,
                                             causal, window)
    elif top_k > 0:
        pairs = selected_scored_pairs(T, select_block_q(batch, heads, T),
                                      top_k)
    else:
        pairs = scored_pairs(T, causal, window,
                             block_q_of(batch, heads, T, window))

    band = {} if window <= 0 else {
        "executor.attention_band_kept_pairs":
            batch * heads * kept_pairs(T, window),
        "executor.attention_band_scored_pairs": batch * heads * pairs}
    selected = {} if top_k <= 0 else {
        "executor.attention_selected_layers": 1,
        "executor.attention_selected_pairs":
            batch * heads * kept_pairs(T, top_k),
        "executor.attention_index_pairs":
            batch * ins[3].shape[1] * (T * (T + 1) // 2)}
    diffusion = {} if block <= 0 else {
        "executor.attention_diffusion_layers": 1,
        "executor.attention_own_tile_layers": int(kernels is not None),
        "executor.attention_kept_pairs":
            batch * heads * diffusion_kept_pairs(T, block),
        "executor.diffusion_trunk_rows": trunk_rows}
    return {**band, **selected, **diffusion,
            "executor.attention_layers": 1,
            "executor.attention_window_layers": int(bool(window)),
            "executor.attention_kernel_layers": int(kernels is not None),
            "executor.attention_scored_pairs": batch * heads * pairs,
            "executor.attention_latent_layers":
                int(key_dim != v.shape[-1]),
            "executor.attention_pair_lanes": key_dim + v.shape[-1]}


register(
    "RingAttention",
    _ring_attention_op,
    arg_names=lambda p: ["query", "key", "value"] + [
        "index_query", "index_key", "index_weight"
    ] * (p["select_top_k"] > 0),
    param_schema={
        "causal": Param(parse_bool, False),
        "axis_name": Param(parse_str, "sp"),
        "batch_axis": Param(parse_str, ""),  # dp axis on combined meshes
        "scale": Param(parse_float, -1.0),  # <=0: 1/sqrt(head_dim)
        "window": Param(parse_int, 0),  # keys a query reads; 0: all before
        # keys a query keeps of those before it, chosen by the indexer's
        # three further inputs; 0: no selection and no such inputs
        "select_top_k": Param(parse_int, 0),
        # weighs the indexer's own term, attached in backward
        "index_loss_coef": Param(parse_float, 0.0),
        # positions a block of the block-diffusion mask over a batch of
        # noised copies then clean ones; 0: off
        "diffusion_block": Param(parse_int, 0),
    },
    aliases=("_contrib_RingAttention",),
    launch_counts=_ring_attention_counts,
    launch_instruments=("executor.attention_layers",
                        "executor.attention_window_layers",
                        "executor.attention_kernel_layers",
                        "executor.attention_scored_pairs",
                        "executor.attention_band_kept_pairs",
                        "executor.attention_band_scored_pairs",
                        "executor.attention_latent_layers",
                        "executor.attention_pair_lanes",
                        "executor.attention_selected_layers",
                        "executor.attention_selected_pairs",
                        "executor.attention_index_pairs",
                        "executor.attention_diffusion_layers",
                        "executor.attention_own_tile_layers",
                        "executor.attention_kept_pairs",
                        "executor.diffusion_trunk_rows"),
)
