"""contrib ops: SSD MultiBox family, Faster R-CNN Proposal, FFT/IFFT,
count_sketch (reference src/operator/contrib/, 4.4k LoC CUDA/C++).

TPU-native re-design: anchor generation / target matching / NMS are dense
fixed-shape computations (masking instead of dynamic lists) so they stay
inside XLA programs; the reference's CUDA NMS loops become a
``lax.fori_loop`` over score-sorted candidates with a suppression mask.
Detection-style outputs are gradient-free (wrapped in stop_gradient), like
the reference layers that declare no backward.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .nn import rms_norm
from .registry import register
from ..base import MXNetError


def _flist(v, default):
    if v is None:
        return tuple(default)
    if isinstance(v, (int, float)):
        return (float(v),)
    return tuple(float(x) for x in v)


# ---------------------------------------------------------------------------
# MultiBoxPrior — contrib/multibox_prior-inl.h
# ---------------------------------------------------------------------------

def _mbp_infer(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return list(in_shapes), [None], []
    sizes = _flist(attrs.get("sizes"), (1.0,))
    ratios = _flist(attrs.get("ratios"), (1.0,))
    na = len(sizes) + len(ratios) - 1
    h, w = data[2], data[3]
    return [tuple(data)], [(1, h * w * na, 4)], []


@register("_contrib_MultiBoxPrior", aliases=("MultiBoxPrior",),
          infer_shape=_mbp_infer)
def multibox_prior(data, sizes=None, ratios=None, clip=False, steps=None,
                   offsets=None):
    """Generate SSD prior (anchor) boxes for each feature-map cell
    (multibox_prior-inl.h MultiBoxPriorForward).  Output (1, H*W*A, 4) with
    corners (x1,y1,x2,y2) normalized to [0,1]."""
    sizes = _flist(sizes, (1.0,))
    ratios = _flist(ratios, (1.0,))
    offsets = _flist(offsets, (0.5, 0.5))
    h, w = data.shape[2], data.shape[3]
    steps = _flist(steps, (-1.0, -1.0))
    step_y = steps[0] if steps[0] > 0 else 1.0 / h
    step_x = steps[1] if steps[1] > 0 else 1.0 / w

    cy = (jnp.arange(h, dtype=jnp.float32) + offsets[0]) * step_y
    cx = (jnp.arange(w, dtype=jnp.float32) + offsets[1]) * step_x
    cyg, cxg = jnp.meshgrid(cy, cx, indexing="ij")     # [h, w]

    # anchors: square (s, s) boxes for every size (the reference's
    # multibox_prior.cc uses w=h=size/2 half-extents for all size anchors,
    # ignoring ratios), then size[0] stretched by sqrt(ratio) for ratios[1:]
    whs = [(s, s) for s in sizes]
    whs += [(sizes[0] * np.sqrt(r), sizes[0] / np.sqrt(r))
            for r in ratios[1:]]
    boxes = []
    for bw, bh in whs:
        x1 = cxg - bw / 2
        y1 = cyg - bh / 2
        x2 = cxg + bw / 2
        y2 = cyg + bh / 2
        boxes.append(jnp.stack([x1, y1, x2, y2], axis=-1))  # [h, w, 4]
    out = jnp.stack(boxes, axis=2).reshape(1, -1, 4)        # [1, h*w*A, 4]
    if clip:
        out = jnp.clip(out, 0.0, 1.0)
    return lax.stop_gradient(out)


# ---------------------------------------------------------------------------
# box utilities
# ---------------------------------------------------------------------------

def _iou(a, b):
    """IoU between [A,4] and [B,4] corner boxes -> [A,B]."""
    ax1, ay1, ax2, ay2 = a[:, 0:1], a[:, 1:2], a[:, 2:3], a[:, 3:4]
    bx1, by1, bx2, by2 = b[None, :, 0], b[None, :, 1], b[None, :, 2], \
        b[None, :, 3]
    iw = jnp.maximum(jnp.minimum(ax2, bx2) - jnp.maximum(ax1, bx1), 0)
    ih = jnp.maximum(jnp.minimum(ay2, by2) - jnp.maximum(ay1, by1), 0)
    inter = iw * ih
    area_a = jnp.maximum(ax2 - ax1, 0) * jnp.maximum(ay2 - ay1, 0)
    area_b = jnp.maximum(bx2 - bx1, 0) * jnp.maximum(by2 - by1, 0)
    return inter / jnp.maximum(area_a + area_b - inter, 1e-12)


def _encode(anchors, gt, variances):
    """Corner gt vs corner anchors -> center-form regression targets
    (multibox_target-inl.h encoding)."""
    aw = anchors[:, 2] - anchors[:, 0]
    ah = anchors[:, 3] - anchors[:, 1]
    acx = (anchors[:, 0] + anchors[:, 2]) / 2
    acy = (anchors[:, 1] + anchors[:, 3]) / 2
    gw = gt[:, 2] - gt[:, 0]
    gh = gt[:, 3] - gt[:, 1]
    gcx = (gt[:, 0] + gt[:, 2]) / 2
    gcy = (gt[:, 1] + gt[:, 3]) / 2
    tx = (gcx - acx) / jnp.maximum(aw, 1e-12) / variances[0]
    ty = (gcy - acy) / jnp.maximum(ah, 1e-12) / variances[1]
    tw = jnp.log(jnp.maximum(gw / jnp.maximum(aw, 1e-12), 1e-12)) / \
        variances[2]
    th = jnp.log(jnp.maximum(gh / jnp.maximum(ah, 1e-12), 1e-12)) / \
        variances[3]
    return jnp.stack([tx, ty, tw, th], axis=-1)


def _decode(anchors, deltas, variances):
    aw = anchors[:, 2] - anchors[:, 0]
    ah = anchors[:, 3] - anchors[:, 1]
    acx = (anchors[:, 0] + anchors[:, 2]) / 2
    acy = (anchors[:, 1] + anchors[:, 3]) / 2
    cx = deltas[:, 0] * variances[0] * aw + acx
    cy = deltas[:, 1] * variances[1] * ah + acy
    w = jnp.exp(deltas[:, 2] * variances[2]) * aw
    h = jnp.exp(deltas[:, 3] * variances[3]) * ah
    return jnp.stack([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2],
                     axis=-1)


# ---------------------------------------------------------------------------
# MultiBoxTarget — contrib/multibox_target-inl.h
# ---------------------------------------------------------------------------

def _mbt_infer(attrs, in_shapes):
    anchor, label, cls_pred = in_shapes[:3]
    if anchor is None or label is None or cls_pred is None:
        return list(in_shapes), [None, None, None], []
    a = anchor[1]
    n = label[0]
    return ([tuple(anchor), tuple(label), tuple(cls_pred)],
            [(n, a * 4), (n, a * 4), (n, a)], [])


@register("_contrib_MultiBoxTarget", aliases=("MultiBoxTarget",),
          input_names=("anchor", "label", "cls_pred"), num_outputs=3,
          output_names=("loc_target", "loc_mask", "cls_target"),
          infer_shape=_mbt_infer)
def multibox_target(anchor, label, cls_pred, overlap_threshold=0.5,
                    ignore_label=-1.0, negative_mining_ratio=-1.0,
                    negative_mining_thresh=0.5, minimum_negative_samples=0,
                    variances=(0.1, 0.1, 0.2, 0.2)):
    """SSD training-target assignment (multibox_target-inl.h): match each
    anchor to ground truth (best-anchor-per-gt plus IoU>threshold), emit
    localization targets/masks and classification targets."""
    variances = _flist(variances, (0.1, 0.1, 0.2, 0.2))
    anchors = anchor[0]                                # [A, 4]
    a = anchors.shape[0]

    mine = float(negative_mining_ratio) > 0

    def per_sample(lbl, pred):
        # lbl: [O, 5] rows (cls, x1, y1, x2, y2), cls<0 = padding
        valid = lbl[:, 0] >= 0                         # [O]
        gt = lbl[:, 1:5]
        iou = _iou(anchors, gt)                        # [A, O]
        iou = jnp.where(valid[None, :], iou, -1.0)
        best_gt = jnp.argmax(iou, axis=1)              # [A]
        best_iou = jnp.max(iou, axis=1)
        # force-match: iterative bipartite matching, one distinct anchor
        # per valid gt (multibox_target-inl.h greedy matching): each round
        # takes the globally-best remaining (anchor, gt) pair, then masks
        # that anchor row and gt column so no anchor or gt matches twice.
        n_gt = gt.shape[0]

        def match_round(_, state):
            iou_m, forced, forced_gt = state
            flat = iou_m.reshape(-1)
            idx = jnp.argmax(flat)
            ai = idx // n_gt
            gi = (idx % n_gt).astype(jnp.int32)
            ok = flat[idx] >= 0.0          # invalid/exhausted entries < 0
            forced = forced.at[ai].set(forced[ai] | ok)
            forced_gt = forced_gt.at[ai].set(
                jnp.where(ok, gi, forced_gt[ai]))
            iou_m = iou_m.at[ai, :].set(-2.0)
            iou_m = iou_m.at[:, gi].set(-2.0)
            return iou_m, forced, forced_gt

        _, forced, forced_gt = lax.fori_loop(
            0, n_gt, match_round,
            (iou, jnp.zeros((a,), bool), jnp.zeros((a,), jnp.int32)))
        pos = forced | (best_iou >= overlap_threshold)
        match = jnp.where(forced, forced_gt, best_gt)
        matched_gt = gt[match]                         # [A, 4]
        loc_t = _encode(anchors, matched_gt, variances)
        loc_t = jnp.where(pos[:, None], loc_t, 0.0).reshape(-1)
        loc_m = jnp.broadcast_to(pos[:, None],
                                 (a, 4)).astype(jnp.float32).reshape(-1)
        cls_t = jnp.where(pos, lbl[match, 0] + 1, 0.0)  # 0 = background
        if mine:
            # hard negative mining (multibox_target-inl.h NegativeMining):
            # candidates = anchors below the mining IoU threshold, ranked by
            # background cross-entropy (-log p_bg from cls_pred softmax);
            # keep ratio*num_pos (>= minimum_negative_samples), rest ignored
            p = jax.nn.softmax(pred, axis=0)           # [cls, A]
            neg_score = -jnp.log(jnp.maximum(p[0], 1e-12))
            cand = (~pos) & (best_iou < negative_mining_thresh)
            num_pos = pos.sum()
            num_neg = jnp.maximum(
                (num_pos * negative_mining_ratio).astype(jnp.int32),
                int(minimum_negative_samples))
            score = jnp.where(cand, neg_score, -jnp.inf)
            order = jnp.argsort(-score)
            rank = jnp.argsort(order)
            selected = cand & (rank < num_neg)
            cls_t = jnp.where(pos, cls_t,
                              jnp.where(selected, 0.0, ignore_label))
        return loc_t, loc_m, cls_t

    loc_t, loc_m, cls_t = jax.vmap(per_sample)(label, cls_pred)
    return (lax.stop_gradient(loc_t), lax.stop_gradient(loc_m),
            lax.stop_gradient(cls_t))


# ---------------------------------------------------------------------------
# MultiBoxDetection — contrib/multibox_detection-inl.h
# ---------------------------------------------------------------------------

def _mbd_infer(attrs, in_shapes):
    cls_prob, loc_pred, anchor = in_shapes[:3]
    if cls_prob is None or anchor is None:
        return list(in_shapes), [None], []
    return ([tuple(cls_prob), tuple(loc_pred), tuple(anchor)],
            [(cls_prob[0], anchor[1], 6)], [])


def _nms_mask(boxes, scores, valid, nms_threshold, topk):
    """Greedy NMS via fori_loop over the topk score-sorted candidates;
    returns keep mask [A]."""
    order = jnp.argsort(-scores)
    keep = valid

    rank = jnp.argsort(order)                          # score rank per box

    def body(i, keep):
        idx = order[i]
        alive = keep[idx]
        ious = _iou(boxes[idx][None, :], boxes)[0]     # [A]
        # suppress strictly-lower-ranked boxes overlapping idx
        suppress = (ious > nms_threshold) & (rank > rank[idx])
        return jnp.where(alive, keep & ~suppress, keep)

    return lax.fori_loop(0, topk, body, keep)


@register("_contrib_MultiBoxDetection", aliases=("MultiBoxDetection",),
          input_names=("cls_prob", "loc_pred", "anchor"),
          infer_shape=_mbd_infer)
def multibox_detection(cls_prob, loc_pred, anchor, clip=True, threshold=0.01,
                       background_id=0, nms_threshold=0.5,
                       force_suppress=False, variances=(0.1, 0.1, 0.2, 0.2),
                       nms_topk=-1):
    """SSD detection output (multibox_detection-inl.h): decode loc
    predictions against anchors, take per-anchor best non-background class,
    score-threshold, per-class greedy NMS.  Output [N, A, 6] rows
    (class_id, score, x1, y1, x2, y2); suppressed rows have class_id=-1."""
    variances = _flist(variances, (0.1, 0.1, 0.2, 0.2))
    anchors = anchor[0]
    a = anchors.shape[0]
    topk = a if nms_topk is None or int(nms_topk) <= 0 else \
        min(int(nms_topk), a)

    def per_sample(probs, deltas):
        # probs [cls, A]; deltas [A*4]
        boxes = _decode(anchors, deltas.reshape(-1, 4), variances)
        if clip:
            boxes = jnp.clip(boxes, 0.0, 1.0)
        mask = jnp.ones(probs.shape[0], bool).at[background_id].set(False)
        fg = jnp.where(mask[:, None], probs, -1.0)
        cls_id = jnp.argmax(fg, axis=0)                # [A]
        score = jnp.max(fg, axis=0)
        valid = score > threshold
        if force_suppress:
            keep = _nms_mask(boxes, jnp.where(valid, score, -1.0), valid,
                             nms_threshold, topk)
        else:
            keep = valid
            n_cls = probs.shape[0]
            for c in range(n_cls):
                if c == background_id:
                    continue
                sel = valid & (cls_id == c)
                k = _nms_mask(boxes, jnp.where(sel, score, -1.0), sel,
                              nms_threshold, topk)
                keep = jnp.where(sel, k, keep)
        # class ids in output are 0-based foreground ids: classes above
        # background_id shift down by one (reference drops background)
        fg_id = jnp.where(cls_id > background_id, cls_id - 1, cls_id)
        out_id = jnp.where(keep, fg_id.astype(jnp.float32), -1.0)
        rows = jnp.concatenate([out_id[:, None], score[:, None], boxes],
                               axis=1)
        # compact: valid detections first, sorted by confidence descending
        # (multibox_detection.cc sorts kept rows by score before writing,
        # so consumers can read the first k rows)
        order = jnp.argsort(-jnp.where(keep, score, -jnp.inf))
        return rows[order]

    out = jax.vmap(per_sample)(cls_prob, loc_pred)
    return lax.stop_gradient(out)


# ---------------------------------------------------------------------------
# Proposal — contrib/proposal-inl.h (Faster R-CNN RPN proposals)
# ---------------------------------------------------------------------------

def _gen_base_anchors(base_size, scales, ratios):
    """Standard RPN base anchors around (0,0) (proposal-inl.h
    GenerateAnchor)."""
    px = (base_size - 1) * 0.5
    py = (base_size - 1) * 0.5
    anchors = []
    area = base_size * base_size
    for r in ratios:
        size_r = area / r
        ws = int(round(np.sqrt(size_r)))
        hs = int(round(ws * r))
        for s in scales:
            w = ws * s
            h = hs * s
            anchors.append([px - (w - 1) * 0.5, py - (h - 1) * 0.5,
                            px + (w - 1) * 0.5, py + (h - 1) * 0.5])
    return np.array(anchors, np.float32)


def _proposal_infer(attrs, in_shapes):
    cls_prob = in_shapes[0]
    if cls_prob is None:
        return list(in_shapes), [None], []
    post = int(attrs.get("rpn_post_nms_top_n", 300))
    n = cls_prob[0]
    outs = [(n * post, 5)]
    if attrs.get("output_score"):
        outs.append((n * post, 1))
    return list(in_shapes), outs, []


def _proposal_num_outputs(attrs):
    return 2 if attrs.get("output_score") else 1


@register("_contrib_Proposal", aliases=("Proposal",),
          input_names=("cls_prob", "bbox_pred", "im_info"),
          num_outputs=_proposal_num_outputs, infer_shape=_proposal_infer)
def proposal(cls_prob, bbox_pred, im_info, rpn_pre_nms_top_n=6000,
             rpn_post_nms_top_n=300, threshold=0.7, rpn_min_size=16,
             scales=(4, 8, 16, 32), ratios=(0.5, 1, 2), feature_stride=16,
             output_score=False, iou_loss=False):
    """RPN proposal generation (proposal-inl.h ProposalOp): slide base
    anchors over the feature map, decode bbox_pred, clip to image, drop
    small boxes, take pre-NMS top-N by score, NMS, pad to post-NMS top-N."""
    n, two_a, h, w = cls_prob.shape
    scales = tuple(float(s) for s in (scales if isinstance(scales, (list, tuple)) else (scales,)))
    ratios = tuple(float(r) for r in (ratios if isinstance(ratios, (list, tuple)) else (ratios,)))
    base = _gen_base_anchors(int(feature_stride), scales, ratios)  # [A0, 4]
    a0 = base.shape[0]
    sy = jnp.arange(h, dtype=jnp.float32) * feature_stride
    sx = jnp.arange(w, dtype=jnp.float32) * feature_stride
    syg, sxg = jnp.meshgrid(sy, sx, indexing="ij")
    shift = jnp.stack([sxg, syg, sxg, syg], axis=-1)   # [h, w, 4]
    anchors = (shift[:, :, None, :] + base[None, None]).reshape(-1, 4)

    post = int(rpn_post_nms_top_n)
    pre = min(int(rpn_pre_nms_top_n), anchors.shape[0])

    def per_sample(probs, deltas, info):
        # probs [2*A0, h, w] (bg scores first A0 channels, fg last);
        # deltas [4*A0, h, w]
        fg = probs[a0:].transpose(1, 2, 0).reshape(-1)         # [h*w*A0]
        d = deltas.reshape(a0, 4, h, w).transpose(2, 3, 0, 1).reshape(-1, 4)
        # decode (unnormalized RPN parameterization: variances = 1)
        aw = anchors[:, 2] - anchors[:, 0] + 1
        ah = anchors[:, 3] - anchors[:, 1] + 1
        acx = anchors[:, 0] + aw * 0.5
        acy = anchors[:, 1] + ah * 0.5
        cx = d[:, 0] * aw + acx
        cy = d[:, 1] * ah + acy
        ww = jnp.exp(jnp.clip(d[:, 2], -10, 10)) * aw
        hh = jnp.exp(jnp.clip(d[:, 3], -10, 10)) * ah
        boxes = jnp.stack([cx - (ww - 1) * 0.5, cy - (hh - 1) * 0.5,
                           cx + (ww - 1) * 0.5, cy + (hh - 1) * 0.5],
                          axis=-1)
        im_h, im_w = info[0], info[1]
        boxes = jnp.stack([jnp.clip(boxes[:, 0], 0, im_w - 1),
                           jnp.clip(boxes[:, 1], 0, im_h - 1),
                           jnp.clip(boxes[:, 2], 0, im_w - 1),
                           jnp.clip(boxes[:, 3], 0, im_h - 1)], axis=-1)
        min_size = rpn_min_size * info[2]
        keep_size = ((boxes[:, 2] - boxes[:, 0] + 1) >= min_size) & \
                    ((boxes[:, 3] - boxes[:, 1] + 1) >= min_size)
        score = jnp.where(keep_size, fg, -1.0)
        top_score, top_idx = lax.top_k(score, pre)
        top_boxes = boxes[top_idx]
        valid = top_score > 0
        keep = _nms_mask(top_boxes, top_score, valid, threshold, pre)
        # order survivors by score, take post
        rank_score = jnp.where(keep, top_score, -1.0)
        sel_score, sel = lax.top_k(rank_score, post)
        out_boxes = jnp.where((sel_score > 0)[:, None], top_boxes[sel], 0.0)
        out_score = jnp.maximum(sel_score, 0.0)
        return out_boxes, out_score

    boxes, scores = jax.vmap(per_sample)(cls_prob, bbox_pred, im_info)
    batch_idx = jnp.repeat(jnp.arange(n, dtype=jnp.float32), post)
    rois = jnp.concatenate([batch_idx[:, None],
                            boxes.reshape(n * post, 4)], axis=1)
    rois = lax.stop_gradient(rois)
    if output_score:
        return rois, lax.stop_gradient(scores.reshape(n * post, 1))
    return rois


# ---------------------------------------------------------------------------
# FFT / IFFT — contrib/fft-inl.h (cuFFT): real input -> interleaved
# real/imag output of length 2d
# ---------------------------------------------------------------------------

def _fft_infer(attrs, in_shapes):
    d = in_shapes[0]
    if d is None:
        return list(in_shapes), [None], []
    return [tuple(d)], [d[:-1] + (d[-1] * 2,)], []


@register("_contrib_fft", aliases=("fft",), infer_shape=_fft_infer)
def fft(data, compute_size=128):
    """FFT along the last dim; complex output interleaved [re, im, re, im...]
    (fft-inl.h output layout, 2*d)."""
    f = jnp.fft.fft(data.astype(jnp.float32), axis=-1)
    out = jnp.stack([f.real, f.imag], axis=-1)
    return out.reshape(data.shape[:-1] + (data.shape[-1] * 2,)) \
        .astype(jnp.float32)


def _ifft_infer(attrs, in_shapes):
    d = in_shapes[0]
    if d is None:
        return list(in_shapes), [None], []
    return [tuple(d)], [d[:-1] + (d[-1] // 2,)], []


@register("_contrib_ifft", aliases=("ifft",), infer_shape=_ifft_infer)
def ifft(data, compute_size=128):
    """Inverse of _contrib_fft: interleaved complex -> real (the reference
    scales by n like cuFFT's unnormalized inverse divided in python)."""
    d = data.shape[-1] // 2
    c = data.reshape(data.shape[:-1] + (d, 2))
    z = c[..., 0] + 1j * c[..., 1]
    return jnp.fft.ifft(z, axis=-1).real.astype(jnp.float32) * d


# ---------------------------------------------------------------------------
# count_sketch — contrib/count_sketch-inl.h
# ---------------------------------------------------------------------------

def _cs_infer(attrs, in_shapes):
    d = in_shapes[0]
    out_dim = int(attrs["out_dim"])
    if d is None:
        return list(in_shapes), [None], []
    return list(in_shapes), [(d[0], out_dim)], []


@register("_contrib_count_sketch", aliases=("count_sketch",),
          input_names=("data", "h", "s"), infer_shape=_cs_infer)
def count_sketch(data, h, s, out_dim=0, processing_batch_size=32):
    """Count-sketch projection (count_sketch-inl.h): out[:, h[j]] +=
    s[j] * data[:, j].  h in [0, out_dim), s in {+1, -1}.  Linear, so the
    gradient falls out of autodiff through the scatter-add."""
    out_dim = int(out_dim)
    hj = h.reshape(-1).astype(jnp.int32)
    sj = s.reshape(-1).astype(data.dtype)
    vals = data * sj[None, :]
    out = jnp.zeros(data.shape[:-1] + (out_dim,), data.dtype)
    return out.at[..., hj].add(vals)


# ---------------------------------------------------------------------------
# Attention — new capability beyond the reference (2017 had none).  The
# symbol-level entry to the flash-style attention in parallel/
# ring_attention.py: under a GSPMD-sharded trainer the sequence axis
# partitions automatically; for the explicit ring schedule over 'sp' use
# parallel.ring_attention directly.
# ---------------------------------------------------------------------------

def _attention_infer(attrs, in_shapes):
    q = in_shapes[0]
    if q is None:
        return list(in_shapes), [None], []
    return [tuple(s) if s is not None else None for s in in_shapes], \
        [tuple(q)], []


@register("_contrib_Attention", aliases=("Attention", "attention"),
          input_names=("query", "key", "value"),
          infer_shape=_attention_infer)
def contrib_attention(query, key, value, num_heads=1, causal=False,
                      scale=-1.0):
    """Multi-head scaled-dot-product attention (numerically-stable
    softmax; materializes the (Tq, Tk) score matrix — for long-context
    O(T/sp) memory use parallel.ring_attention over an 'sp' mesh axis).
    query/key/value: (batch, seq, d_model); heads split from d_model.
    Output: (batch, seq_q, d_model)."""
    from ..parallel.ring_attention import full_attention
    num_heads = int(num_heads)
    B, T, D = query.shape
    Tk = key.shape[1]
    if D % num_heads != 0:
        raise MXNetError("d_model %d not divisible by num_heads %d"
                         % (D, num_heads))
    if causal and T > Tk:
        raise MXNetError(
            "causal attention needs seq_q (%d) <= seq_k (%d): earlier "
            "query positions would have no visible keys" % (T, Tk))
    hd = D // num_heads
    q = query.reshape(B, T, num_heads, hd)
    k = key.reshape(B, Tk, num_heads, hd)
    v = value.reshape(B, Tk, num_heads, hd)
    s = None if float(scale) <= 0 else float(scale)
    out = full_attention(q, k, v, causal=bool(causal), scale=s)
    return out.reshape(B, T, D)


# ---------------------------------------------------------------------------
# Sequence mixers and the routed-expert layer of today's hybrid language
# models (no 2017 counterpart).  The arithmetic is pure lax: kernels/
# flash_attention.gqa_attention, kernels/delta_rule.gated_delta_rule,
# lax.ragged_dot.
# ---------------------------------------------------------------------------

def _gq_attention_infer(attrs, in_shapes):
    """The output has the query's positions and heads and the value's head
    size; every input's shape is the caller's to give (the model builder
    declares its variables' shapes)."""
    query, value = in_shapes[0], in_shapes[2]
    out = None if query is None or value is None \
        else tuple(query[:-1]) + (value[-1],)
    return list(in_shapes), [out], []


@register("_contrib_GQAttention", aliases=("GQAttention",),
          input_names=lambda attrs: ("query", "key", "value", "gate")
          if attrs.get("gated", False) else ("query", "key", "value"),
          infer_shape=_gq_attention_infer)
def gq_attention(query, key, value, gate=None, scale=-1.0, block_q=512,
                 gated=False, window=0):
    """Causal grouped-query softmax attention that never holds a
    (positions x positions) matrix.  query (batch, positions, query_heads,
    head_dim); key (batch, positions, kv_heads, head_dim) and value (batch,
    positions, kv_heads, value_dim), each key/value head serving
    ``query_heads // kv_heads`` consecutive query heads.  ``value_dim`` need
    not be ``head_dim`` (latent attention: 192-wide keys, 128-wide values);
    the output is (batch, positions, query_heads, value_dim).  ``scale``
    <= 0 means head_dim^-0.5.  With ``gated`` the result is multiplied by
    ``sigmoid(gate)`` (gate shaped like the output).  ``window`` > 0 is
    sliding-window attention: position ``p`` sees the keys ``p - window <
    j <= p``, ``window`` of them with its own; 0 (or all the positions and
    more) is none, the whole causal prefix."""
    from ..kernels.flash_attention import gqa_attention
    out = gqa_attention(query, key, value,
                        scale=None if float(scale) <= 0 else float(scale),
                        block_q=int(block_q), window=int(window))
    if gate is not None:
        out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
    return out


def _delta_rule_infer(attrs, in_shapes):
    value = in_shapes[2]
    return list(in_shapes), [None if value is None else tuple(value)], []


@register("_contrib_GatedDeltaRule", aliases=("GatedDeltaRule",),
          input_names=("query", "key", "value", "a", "b", "A_log",
                       "dt_bias"),
          infer_shape=_delta_rule_infer)
def gated_delta_rule_op(query, key, value, a, b, A_log, dt_bias, chunk=64,
                        eps=1e-6):
    """Gated delta rule, computed in chunks of ``chunk`` positions.  query,
    key (batch, positions, key_heads, dk); value (batch, positions,
    value_heads, dv); b (batch, positions, value_heads); A_log
    (value_heads,).  Each key head serves ``value_heads // key_heads``
    consecutive value heads.  The rank of ``a`` says which rule: (batch,
    positions, value_heads) with dt_bias (value_heads,) is Gated DeltaNet's
    one decay a head; (batch, positions, value_heads, dk) with dt_bias
    (value_heads * dk,) is Kimi Delta Attention's decay a key channel.  In
    float32: query and key are L2-normalised per head (``eps``), query
    scaled by dk^-0.5, ``beta = sigmoid(b)``, ``g = -exp(A_log) *
    softplus(a + dt_bias)`` (``A_log`` a head's, whichever rank); per value
    head, from a zero state, position t does ``S = exp(g_t) S`` — ``S =
    diag(exp(g_t)) S``, row by row, for the vector — ``u = (v_t - S^T k_t)
    beta_t; S = S + k_t u^T; o_t = S^T q_t``.  Returns o shaped like
    value."""
    from ..kernels.delta_rule import gated_delta_net
    f32 = jnp.float32
    beta = jax.nn.sigmoid(b.astype(f32))
    rate = jnp.exp(A_log.astype(f32))
    if a.ndim == 4:
        rate = rate[:, None]
    g = -rate * jax.nn.softplus(
        a.astype(f32) + dt_bias.astype(f32).reshape(a.shape[2:]))
    return gated_delta_net(query, key, value, g, beta, chunk=int(chunk),
                           eps=float(eps))


@jax.custom_vjp
def _rows_by_pair(x, order, inverse, k):
    """``x[order // k]``: the rows of ``x`` (tokens, width), one a (token,
    expert) pair, in sorted order.  ``inverse`` is the permutation that
    takes sorted pairs back to token-major order, ``k`` pairs a token, so
    the backward is a gather and a sum, not a scatter."""
    return jnp.take(x, order // k, axis=0)


def _rows_fwd(x, order, inverse, k):
    return jnp.take(x, order // k, axis=0), (inverse, x.shape[0])


def _rows_bwd(res, g):
    inverse, tokens = res
    back = jnp.take(g, inverse, axis=0).astype(jnp.float32)
    return (back.reshape(tokens, -1, g.shape[-1]).sum(axis=1)
            .astype(g.dtype), None, None, None)


_rows_by_pair.defvjp(_rows_fwd, _rows_bwd)


@jax.custom_vjp
def _permuted(x, perm, inverse):
    """``x[perm]`` for a permutation and its inverse: gathers both ways."""
    return jnp.take(x, perm, axis=0)


_permuted.defvjp(
    lambda x, perm, inverse: (jnp.take(x, perm, axis=0), inverse),
    lambda inverse, g: (jnp.take(g, inverse, axis=0), None, None))


def _grouped_dot(rows, weight, sizes, valid):
    """``lax.ragged_dot`` over the groups, with the rows past the last
    group — which it neither reads nor writes, going forward or backward —
    held at zero on both sides of it."""
    rows = jnp.where(valid, rows, 0)
    return jnp.where(valid, lax.ragged_dot(rows, weight, sizes), 0)


def _expert_products(rows, gate_up_weight, down_weight, sizes, valid):
    """``(silu(x @ gate_e) * (x @ up_e)) @ down_e`` for rows grouped by
    expert e: (rows, hidden) -> (rows, hidden), zeros where not ``valid``."""
    gate, up = jnp.split(_grouped_dot(rows, gate_up_weight, sizes, valid),
                         2, axis=-1)
    return _grouped_dot(jax.nn.silu(gate) * up, down_weight, sizes, valid)


@jax.custom_vjp
def _rows_at(x, token):
    """``x[token]`` for (tokens, width) ``x``, a token taken any number of
    times: the backward adds the rows' cotangents up by token in float32."""
    return jnp.take(x, token, axis=0)


def _rows_at_bwd(res, g):
    token, tokens = res
    back = jnp.zeros((tokens, g.shape[-1]), jnp.float32)
    return back.at[token].add(g.astype(jnp.float32)).astype(g.dtype), None


_rows_at.defvjp(
    lambda x, token: (jnp.take(x, token, axis=0), (token, x.shape[0])),
    _rows_at_bwd)


def _experts_full(data, weight, gate_up_weight, down_weight, order, sizes,
                  here):
    """The held experts' weighted sum over ALL tokens * k pairs in sorted
    order: whatever the router did, at the width of the worst case."""
    tokens, k = weight.shape
    inverse = jnp.argsort(order)
    valid = (jnp.arange(tokens * k) < jnp.sum(sizes))[:, None]
    rows = _rows_by_pair(data, order, inverse, k)        # (tokens * k, H)
    out = _expert_products(rows, gate_up_weight, down_weight, sizes, valid)
    # back to token-major pairs, weighted, summed over a token's k
    out = _permuted(out, inverse, order).reshape(tokens, k, -1)
    scale = jnp.where(here.reshape(tokens, k), weight, 0.0)[..., None]
    return jnp.sum(out.astype(jnp.float32) * scale,
                   axis=1).astype(data.dtype)


def _experts_block(data, weight, gate_up_weight, down_weight, order, sizes,
                   start, capacity, into):
    """``into`` (tokens, hidden) float32 plus the held experts' weighted
    sum over the sorted pairs ``start .. start + capacity - 1``: nothing
    here is tokens * k long but ``order`` and ``weight``."""
    k = weight.shape[1]
    pair = lax.dynamic_slice(order, (start,), (capacity,))
    token = pair // k
    # the part of each expert's group that lies inside this block
    ends = jnp.cumsum(sizes)
    block = (jnp.clip(ends, start, start + capacity)
             - jnp.clip(ends - sizes, start, start + capacity))
    live = (jnp.arange(capacity) < jnp.sum(block))[:, None]
    rows = _rows_at(data, token)                         # (capacity, H)
    out = _expert_products(rows, gate_up_weight, down_weight, block, live)
    scale = jnp.take(weight.reshape(-1), pair)[:, None]
    return into.at[token].add(out.astype(jnp.float32) * scale)


def _blocks(order, sizes, capacity):
    """``order`` padded to whole blocks of ``capacity`` pairs, and the
    test that block ``i`` still holds a held expert's pair."""
    order = jnp.pad(order, (0, -order.shape[0] % capacity))
    landed = jnp.sum(sizes)
    return order, lambda carry: carry[0] * capacity < landed


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _experts_blocked(data, weight, gate_up_weight, down_weight, order, sizes,
                     capacity):
    """The held experts' weighted sum, a block of ``capacity`` sorted
    pairs at a time for as many blocks as the held experts' pairs fill —
    counted on the device: one on a step whose pairs fit ``capacity``,
    none when no pair landed.  The backward is the same loop over each
    block's own vjp, the gradients summed in float32."""
    return _experts_blocked_fwd(data, weight, gate_up_weight, down_weight,
                                order, sizes, capacity)[0]


def _experts_blocked_fwd(data, weight, gate_up_weight, down_weight, order,
                         sizes, capacity):
    padded, more = _blocks(order, sizes, capacity)

    def body(carry):
        i, out = carry
        return i + 1, _experts_block(
            data, weight, gate_up_weight, down_weight, padded, sizes,
            i * capacity, capacity, out)
    _, out = lax.while_loop(
        more, body, (0, jnp.zeros(data.shape, jnp.float32)))
    return out.astype(data.dtype), (data, weight, gate_up_weight,
                                    down_weight, order, sizes)


def _experts_blocked_bwd(capacity, res, g):
    leaves, (order, sizes) = res[:4], res[4:]
    padded, more = _blocks(order, sizes, capacity)
    g = g.astype(jnp.float32)
    nothing = jnp.zeros(g.shape, jnp.float32)

    def body(carry):
        i, total = carry
        grads = jax.vjp(
            lambda *a: _experts_block(*a, padded, sizes, i * capacity,
                                      capacity, nothing), *leaves)[1](g)
        return i + 1, tuple(t + x.astype(jnp.float32)
                            for t, x in zip(total, grads))
    _, total = lax.while_loop(more, body, (0, tuple(
        jnp.zeros(x.shape, jnp.float32) for x in leaves)))
    # the weights' gradients leave in the weights' dtype: without the
    # barrier XLA fuses this narrowing with the optimizer's widening, and
    # a step that holds every gradient until its all-finite check holds
    # the float32 sums, at twice the size
    grads = tuple(t.astype(x.dtype) for t, x in zip(total, leaves))
    return tuple(lax.optimization_barrier(grads)) + (None, None)


_experts_blocked.defvjp(_experts_blocked_fwd, _experts_blocked_bwd)


@jax.custom_vjp
def _pushing(out, bias, push):
    """``out`` as it is; going backward ``bias``, which no gradient
    reaches, is handed ``push`` as its gradient."""
    return out


_pushing.defvjp(
    lambda out, bias, push: (out, push.astype(bias.dtype)),
    lambda push, g: (g, push, jnp.zeros_like(push)))


def _capacity(pairs, held, num_experts):
    """Rows of one block of the blocked path: twice the share of the
    ``pairs`` (tokens * k) that a uniform router lands on ``held`` of
    ``num_experts`` experts, rounded up to a multiple of 1,024; at
    ``pairs`` there is no blocked path."""
    share = -(-2 * pairs * held // num_experts)
    return min(pairs, -(-share // 1024) * 1024)


def _routed_infer(attrs, in_shapes):
    data = in_shapes[0]
    return list(in_shapes), \
        [None if data is None else tuple(data), (6,)], []


@register("_contrib_RoutedExperts", aliases=("RoutedExperts",),
          input_names=lambda attrs: (
              "data", "scores" if attrs.get("scores_given", False)
              else "router_weight", "gate_up_weight", "down_weight")
          + (("select_bias",) if attrs.get("use_select_bias", False)
             else ()),
          num_outputs=2, output_names=("output", "stats"),
          infer_shape=_routed_infer)
def routed_experts(data, router_weight, gate_up_weight, down_weight,
                   select_bias=None, top_k=1, expert_offset=0,
                   norm_topk_prob=True, score_func="softmax",
                   routed_scaling_factor=1.0, use_select_bias=False,
                   scores_given=False, balance_rate=0.0):
    """Dropless top-``top_k`` routed experts, told which experts it holds.
    data (tokens, hidden); router_weight (num_experts, hidden) over ALL the
    experts of the layer; gate_up_weight (held, hidden, 2 x width) and
    down_weight (held, width, hidden) of the experts ``expert_offset ..
    expert_offset + held - 1`` that live here.  In float32
    ``p = score_func(data @ router_weight.T)`` over all experts
    (``softmax`` or ``sigmoid``); each token takes the ``top_k`` experts
    whose ``p`` is largest — whose ``p + select_bias`` is, with
    ``use_select_bias`` and that (num_experts,) input, which enters the
    choice alone and so gets no gradient — and weighs them by ``p``
    (divided by their sum with ``norm_topk_prob``) times
    ``routed_scaling_factor``; expert e gives ``(silu(x @ gate_e) * (x @
    up_e)) @ down_e``.  Output 0 is the part of the weighted sum that the
    held experts give — a token none of whose experts is held gets zeros; what
    the other experts would add is their chips' to compute.  Output 1 (no
    gradient) is six float32 counts of this call: token-expert pairs
    routed, those that landed on held experts, the fullest held expert's
    pairs, the mean over held experts, 1, and 1 if the blocked path ran
    and one block held all the pairs that landed (``n <= C``).

    With ``scores_given`` the second input is not a router's weight but
    ``scores`` (tokens, num_experts), the ``p`` itself as the graph
    computed it (a router that is more than one matrix product, say an MLP
    that carries state from layer to layer: ``DepthRouter``); ``score_func``
    is then unused, the choice, the weights and the counts are as above,
    and the gradient of the weighted sum reaches ``scores`` through the
    chosen experts' weights.  ``top_k=1, norm_topk_prob=False`` weighs a
    token's one expert by its ``p`` as it is.

    With ``balance_rate`` above 0 the load moves ``select_bias``: going
    backward it is handed ``balance_rate x (pairs that chose expert e -
    pairs / num_experts)`` as its gradient, over all the experts, held
    here or not, so whatever rule the optimizer applies to every leaf
    lowers the bias of an expert that took more than an even share and
    raises the others' (the bias-balancing of arXiv:2408.15664 with the
    load's error itself in the place of its sign).  The sum over a batch's
    rows is the batch's, as a gradient's is.

    Pairs are sorted by expert, so each held expert's tokens are one
    contiguous group of rows, the held experts' pairs are the sorted
    order's first ``n`` and the expert products are two ``lax.ragged_dot``
    calls over those groups.  Two paths compute the same sum and neither
    drops a pair; the shapes alone choose between them.  Where half or
    more of the experts are held (or there are under ~1,024 pairs) the
    full path works at tokens x ``top_k`` rows, whatever ``n``.  Where
    fewer are held, the blocked path works on ``C`` sorted pairs at a
    time (``_capacity``: twice a uniform router's share) in a loop whose
    trip count ``ceil(n / C)`` is counted on the device: one block on a
    step whose held pairs fit ``C``, more on a step that overflows it —
    that step is slower, never different."""
    if score_func not in ("softmax", "sigmoid"):
        raise MXNetError("RoutedExperts: score_func %r is neither softmax "
                         "nor sigmoid" % (score_func,))
    if balance_rate and select_bias is None:
        raise MXNetError("RoutedExperts: balance_rate moves select_bias, "
                         "which use_select_bias brings")
    return _routed(data, router_weight, gate_up_weight, down_weight,
                   int(top_k), int(expert_offset), norm_topk_prob,
                   _capacity(data.shape[0] * int(top_k),
                             gate_up_weight.shape[0],
                             router_weight.shape[1 if scores_given else 0]),
                   score_func, select_bias, float(routed_scaling_factor),
                   bool(scores_given), float(balance_rate))


def _routed(data, router_weight, gate_up_weight, down_weight, k, offset,
            norm_topk_prob, capacity, score_func="softmax", select_bias=None,
            scaling=1.0, scores_given=False, balance_rate=0.0):
    """``routed_experts`` with the blocked path's rows a block,
    ``capacity``, as an argument (tokens * k: the full path)."""
    f32 = jnp.float32
    tokens = data.shape[0]
    held = gate_up_weight.shape[0]
    if scores_given:
        score = router_weight.astype(f32)
    else:
        logits = jnp.dot(data, router_weight.T, preferred_element_type=f32)
        score = jax.nn.softmax(logits, axis=-1) if score_func == "softmax" \
            else jax.nn.sigmoid(logits)
    if select_bias is None:
        weight, expert = lax.top_k(score, k)
    else:
        _, expert = lax.top_k(score + select_bias.astype(f32), k)
        weight = jnp.take_along_axis(score, expert, axis=-1)
    if norm_topk_prob:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    if scaling != 1.0:
        weight = weight * scaling
    local = expert.reshape(-1) - offset                  # (tokens * k,)
    here = (local >= 0) & (local < held)
    # held pairs first, grouped by expert; absent ones behind them
    order = jnp.argsort(jnp.where(here, local, held), stable=True)
    sizes = jnp.sum(jax.nn.one_hot(jnp.where(here, local, held), held,
                                   dtype=jnp.int32), axis=0)
    if capacity < tokens * k:
        out = _experts_blocked(data, weight, gate_up_weight, down_weight,
                               order, sizes, capacity)
        compact = (jnp.sum(sizes) <= capacity).astype(f32)
    else:
        out = _experts_full(data, weight, gate_up_weight, down_weight,
                            order, sizes, here)
        compact = jnp.zeros((), f32)
    if balance_rate:
        chosen = jnp.sum(jax.nn.one_hot(expert.reshape(-1), score.shape[1],
                                        dtype=f32), axis=0)
        out = _pushing(out, select_bias, lax.stop_gradient(
            balance_rate * (chosen - tokens * k / score.shape[1])))
    load = sizes.astype(f32)
    stats = lax.stop_gradient(jnp.stack([
        jnp.asarray(tokens * k, f32), jnp.sum(load), jnp.max(load),
        jnp.mean(load), jnp.ones((), f32), compact]))
    return out, stats


def _depth_router_infer(attrs, in_shapes):
    data, down, fc3 = in_shapes[0], in_shapes[1], in_shapes[5]
    if data is None or down is None or fc3 is None:
        return list(in_shapes), [None, None], []
    return list(in_shapes), [(data[0], down[0]), (data[0], fc3[0])], []


@register("_contrib_DepthRouter", aliases=("DepthRouter",),
          input_names=lambda attrs: (
              "data", "down_weight", "norm_gamma", "fc1_weight",
              "fc2_weight", "fc3_weight")
          + (("state", "carry") if attrs.get("carried", False) else ()),
          num_outputs=2, output_names=("state", "scores"),
          infer_shape=_depth_router_infer)
def depth_router(data, down_weight, norm_gamma, fc1_weight, fc2_weight,
                 fc3_weight, state=None, carry=None, eps=1e-6,
                 carried=False):
    """A router that is a small MLP over a state carried from layer to
    layer (ZAYA1, arXiv:2511.17127), all of it in float32 whatever the
    data's dtype: ``r = data @ down_weight.T`` (tokens, width), plus
    ``carry * state`` with ``carried`` — ``state`` (tokens, width) the
    previous layer's ``r``, ``carry`` (width,) learned —; ``s =
    fc3(gelu(fc2(gelu(fc1(rmsnorm(r; norm_gamma, eps))))))`` with the exact
    ``gelu`` and no bias.  Output 0 is ``r`` (float32), what the next
    layer's router is handed; output 1 is ``softmax(s)`` over all the
    experts (float32), what ``RoutedExperts`` takes with ``scores_given``.
    Contractions under ``highest`` precision: a choice of one expert in
    sixteen turns on the fourth digit of ``s``."""
    f32 = jnp.float32
    hi = lax.Precision.HIGHEST

    def fc(x, w):
        return jnp.dot(x, w.astype(f32).T, precision=hi,
                       preferred_element_type=f32)
    r = jnp.dot(data, down_weight.T, precision=hi,
                preferred_element_type=f32)
    if state is not None:
        r = r + carry.astype(f32) * state.astype(f32)
    x = jax.nn.gelu(fc(rms_norm(r, norm_gamma, eps=eps), fc1_weight),
                    approximate=False)
    x = jax.nn.gelu(fc(x, fc2_weight), approximate=False)
    return r, jax.nn.softmax(fc(x, fc3_weight), axis=-1)


@register("_contrib_RoutedExpertsStats", aliases=("RoutedExpertsStats",),
          variable_inputs=True,
          input_names=lambda attrs: tuple(
              "arg%d" % i for i in range(int(attrs.get("num_args", 1)))),
          infer_shape=lambda attrs, in_shapes: (list(in_shapes), [(6,)], []))
def routed_experts_stats(*stats, num_args=1):
    """One step's counts over several routed-expert layers, from each
    layer's ``stats`` output: pairs routed and pairs that landed here
    summed over the layers; the fullest held expert's pairs and the mean
    over held experts, both of the layer whose fullest expert is
    fullest; the calls and those that needed one block at most, summed."""
    s = jnp.stack(stats)                                 # (layers, 6)
    worst = jnp.argmax(s[:, 2])
    return jnp.stack([jnp.sum(s[:, 0]), jnp.sum(s[:, 1]), s[worst, 2],
                      s[worst, 3], jnp.sum(s[:, 4]), jnp.sum(s[:, 5])])
