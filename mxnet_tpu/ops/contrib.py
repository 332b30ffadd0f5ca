"""Contrib ops: SSD multibox family, bounding-box utilities, CTC loss,
count_sketch, FFT, proposal.

Parity: reference `src/operator/contrib/` (multibox_prior.cc,
multibox_target.cc:72, multibox_detection.cc, bounding_box.cc,
ctc_loss-inl.h, count_sketch, fft, proposal).

TPU-native redesign: everything is static-shape, branch-free jnp/lax — NMS
and matching are formulated as masked top-k/argmax sweeps (lax.scan / sort
tricks) instead of the reference's data-dependent CUDA loops, so they compile
once and run on the MXU/VPU.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .registry import register

# ---------------------------------------------------------------------------
# SSD: MultiBoxPrior / MultiBoxTarget / MultiBoxDetection
# ---------------------------------------------------------------------------


@register("_contrib_MultiBoxPrior", aliases=("MultiBoxPrior",),
          differentiable=False)
def MultiBoxPrior(data, sizes=(1.0,), ratios=(1.0,), clip=False, steps=(-1.0, -1.0),
                  offsets=(0.5, 0.5)):
    """Generate anchor boxes per feature-map cell.

    Parity: src/operator/contrib/multibox_prior.cc — anchors are
    (sizes[0],ratios[0]), (sizes[1:],ratios[0]), (sizes[0],ratios[1:]).
    Output [1, H*W*num_anchors, 4] in corner format, normalized coords.
    """
    H, W = data.shape[2], data.shape[3]
    sizes = tuple(float(s) for s in sizes)
    ratios = tuple(float(r) for r in ratios)
    step_y = steps[0] if steps[0] > 0 else 1.0 / H
    step_x = steps[1] if steps[1] > 0 else 1.0 / W
    cy = (jnp.arange(H) + offsets[0]) * step_y
    cx = (jnp.arange(W) + offsets[1]) * step_x
    anchors = []
    for i, s in enumerate(sizes):
        r = ratios[0]
        anchors.append((s * np.sqrt(r), s / np.sqrt(r)))
    for r in ratios[1:]:
        s = sizes[0]
        anchors.append((s * np.sqrt(r), s / np.sqrt(r)))
    aw = jnp.asarray([a[0] for a in anchors]) / 2.0
    ah = jnp.asarray([a[1] for a in anchors]) / 2.0
    gy, gx = jnp.meshgrid(cy, cx, indexing="ij")  # [H, W]
    gy = gy[:, :, None]; gx = gx[:, :, None]
    boxes = jnp.stack([gx - aw, gy - ah, gx + aw, gy + ah], axis=-1)  # [H,W,A,4]
    out = boxes.reshape(1, -1, 4)
    if clip:
        out = jnp.clip(out, 0.0, 1.0)
    return out.astype(data.dtype)


def _iou_corner(a, b):
    """a: [M,4], b: [N,4] corner boxes -> [M,N] IoU."""
    tl = jnp.maximum(a[:, None, :2], b[None, :, :2])
    br = jnp.minimum(a[:, None, 2:], b[None, :, 2:])
    wh = jnp.maximum(br - tl, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    area_a = jnp.maximum((a[:, 2] - a[:, 0]) * (a[:, 3] - a[:, 1]), 0.0)
    area_b = jnp.maximum((b[:, 2] - b[:, 0]) * (b[:, 3] - b[:, 1]), 0.0)
    union = area_a[:, None] + area_b[None, :] - inter
    return jnp.where(union > 0, inter / union, 0.0)


@register("_contrib_MultiBoxTarget", aliases=("MultiBoxTarget",),
          num_outputs=3, differentiable=False)
def MultiBoxTarget(anchor, label, cls_pred, overlap_threshold=0.5,
                   ignore_label=-1.0, negative_mining_ratio=-1.0,
                   negative_mining_thresh=0.5, minimum_negative_samples=0,
                   variances=(0.1, 0.1, 0.2, 0.2)):
    """Match anchors to ground-truth; emit (loc_target, loc_mask, cls_target).

    Parity: src/operator/contrib/multibox_target.cc:72. Static-shape matching:
    per-anchor argmax IoU + bipartite best-anchor-per-gt override, vectorized
    over the batch with vmap instead of per-sample CPU loops.
    """
    A = anchor.shape[1]
    anchors = anchor.reshape(A, 4)
    v = jnp.asarray(variances)

    def one_sample(lab):
        # lab: [M, >=5] rows (cls, x1, y1, x2, y2); cls<0 = padding
        gt_cls = lab[:, 0]
        gt_box = lab[:, 1:5]
        valid = gt_cls >= 0
        iou = _iou_corner(anchors, gt_box)  # [A, M]
        iou = jnp.where(valid[None, :], iou, -1.0)
        best_gt = jnp.argmax(iou, axis=1)            # per-anchor best gt
        best_iou = jnp.max(iou, axis=1)
        # bipartite: each gt claims its best anchor
        best_anchor = jnp.argmax(iou, axis=0)        # [M]
        claimed = jnp.zeros(A, dtype=bool).at[best_anchor].set(valid)
        claimed_gt = jnp.zeros(A, dtype=jnp.int32).at[best_anchor].set(
            jnp.where(valid, jnp.arange(lab.shape[0], dtype=jnp.int32), 0))
        pos = claimed | (best_iou >= overlap_threshold)
        match = jnp.where(claimed, claimed_gt, best_gt)
        mcls = gt_cls[match]
        mbox = gt_box[match]
        cls_t = jnp.where(pos, mcls + 1.0, 0.0)
        # encode loc targets (center form, variance-scaled)
        acx = (anchors[:, 0] + anchors[:, 2]) / 2
        acy = (anchors[:, 1] + anchors[:, 3]) / 2
        aw = jnp.maximum(anchors[:, 2] - anchors[:, 0], 1e-8)
        ah = jnp.maximum(anchors[:, 3] - anchors[:, 1], 1e-8)
        gcx = (mbox[:, 0] + mbox[:, 2]) / 2
        gcy = (mbox[:, 1] + mbox[:, 3]) / 2
        gw = jnp.maximum(mbox[:, 2] - mbox[:, 0], 1e-8)
        gh = jnp.maximum(mbox[:, 3] - mbox[:, 1], 1e-8)
        tx = (gcx - acx) / aw / v[0]
        ty = (gcy - acy) / ah / v[1]
        tw = jnp.log(gw / aw) / v[2]
        th = jnp.log(gh / ah) / v[3]
        loc_t = jnp.stack([tx, ty, tw, th], axis=-1)
        loc_t = jnp.where(pos[:, None], loc_t, 0.0)
        # per-coordinate mask [A, 4] (reference loc_mask is length 4A)
        loc_m = jnp.broadcast_to(pos[:, None], loc_t.shape).astype(loc_t.dtype)
        return loc_t.reshape(-1), loc_m.reshape(-1), cls_t, pos

    loc_t, loc_m, cls_t, pos = jax.vmap(one_sample)(label)

    if negative_mining_ratio > 0:
        # hard-negative mining on background confidence (cls_pred: [N, C, A])
        prob = jax.nn.softmax(cls_pred, axis=1)
        bg = prob[:, 0, :]  # background prob per anchor
        neg_cand = (~pos) & (bg < 1.0)
        npos = jnp.sum(pos, axis=1, keepdims=True)
        k = jnp.minimum(npos * negative_mining_ratio + minimum_negative_samples, A)
        score = jnp.where(neg_cand, 1.0 - bg, -1.0)  # higher = harder negative
        order = jnp.argsort(-score, axis=1)
        rank = jnp.argsort(order, axis=1)
        keep_neg = (rank < k) & neg_cand
        cls_t = jnp.where(pos, cls_t, jnp.where(keep_neg, 0.0, ignore_label))
    return loc_t, loc_m, cls_t


@register("_contrib_MultiBoxDetection", aliases=("MultiBoxDetection",),
          differentiable=False)
def MultiBoxDetection(cls_prob, loc_pred, anchor, clip=True, threshold=0.01,
                      background_id=0, nms_threshold=0.5, force_suppress=False,
                      variances=(0.1, 0.1, 0.2, 0.2), nms_topk=-1):
    """Decode + per-class NMS. Output [N, A, 6] rows (cls, score, x1,y1,x2,y2).

    Parity: src/operator/contrib/multibox_detection.cc. NMS is a fixed-length
    masked sweep (O(A^2) IoU matrix + greedy scan) — static shapes for XLA.
    """
    N, C, A = cls_prob.shape
    anchors = anchor.reshape(A, 4)
    v = jnp.asarray(variances)

    def one(probs, locs):
        acx = (anchors[:, 0] + anchors[:, 2]) / 2
        acy = (anchors[:, 1] + anchors[:, 3]) / 2
        aw = anchors[:, 2] - anchors[:, 0]
        ah = anchors[:, 3] - anchors[:, 1]
        l = locs.reshape(A, 4)
        cx = l[:, 0] * v[0] * aw + acx
        cy = l[:, 1] * v[1] * ah + acy
        w = jnp.exp(l[:, 2] * v[2]) * aw / 2
        h = jnp.exp(l[:, 3] * v[3]) * ah / 2
        boxes = jnp.stack([cx - w, cy - h, cx + w, cy + h], axis=-1)
        if clip:
            boxes = jnp.clip(boxes, 0.0, 1.0)
        cls_id = jnp.argmax(probs, axis=0).astype(jnp.float32)  # over C
        score = jnp.max(probs, axis=0)
        keep = (cls_id != background_id) & (score > threshold)
        cls_out = jnp.where(keep, cls_id - 1.0, -1.0)
        score = jnp.where(keep, score, 0.0)
        # greedy NMS via scan over score-sorted anchors
        order = jnp.argsort(-score)
        sboxes = boxes[order]
        scls = cls_out[order]
        sscore = score[order]
        iou = _iou_corner(sboxes, sboxes)
        same = (scls[:, None] == scls[None, :]) | force_suppress
        suppress_mat = (iou > nms_threshold) & same

        def body(alive, i):
            keep_i = alive[i] & (scls[i] >= 0)
            kill = suppress_mat[i] & keep_i
            kill = kill.at[i].set(False)
            return alive & ~kill, keep_i

        alive0 = jnp.ones(A, dtype=bool)
        alive, kept = lax.scan(body, alive0, jnp.arange(A))
        final_cls = jnp.where(kept, scls, -1.0)
        out = jnp.concatenate([final_cls[:, None], sscore[:, None], sboxes], axis=1)
        return out

    return jax.vmap(one)(cls_prob, loc_pred)


# ---------------------------------------------------------------------------
# bounding-box ops (parity: src/operator/contrib/bounding_box.cc)
# ---------------------------------------------------------------------------


@register("_contrib_box_iou", differentiable=False)
def box_iou(lhs, rhs, format="corner"):
    a = lhs.reshape(-1, 4)
    b = rhs.reshape(-1, 4)
    if format == "center":
        def c2c(x):
            return jnp.stack([x[:, 0] - x[:, 2] / 2, x[:, 1] - x[:, 3] / 2,
                              x[:, 0] + x[:, 2] / 2, x[:, 1] + x[:, 3] / 2], axis=-1)
        a, b = c2c(a), c2c(b)
    return _iou_corner(a, b).reshape(lhs.shape[:-1] + rhs.shape[:-1])


@register("_contrib_box_nms", aliases=("_contrib_nms",), differentiable=False)
def box_nms(data, overlap_thresh=0.5, valid_thresh=0.0, topk=-1, coord_start=2,
            score_index=1, id_index=-1, background_id=-1, force_suppress=False,
            in_format="corner", out_format="corner"):
    """data: [..., N, K] rows with score at score_index, boxes at coord_start."""
    shape = data.shape
    flat = data.reshape(-1, shape[-2], shape[-1])

    def one(rows):
        score = rows[:, score_index]
        boxes = lax.dynamic_slice_in_dim(rows, coord_start, 4, axis=1)
        if in_format == "center":
            boxes = jnp.stack([boxes[:, 0] - boxes[:, 2] / 2,
                               boxes[:, 1] - boxes[:, 3] / 2,
                               boxes[:, 0] + boxes[:, 2] / 2,
                               boxes[:, 1] + boxes[:, 3] / 2], axis=-1)
        valid = score > valid_thresh
        if id_index >= 0:
            ids = rows[:, id_index]
            valid = valid & (ids != background_id)
        else:
            ids = jnp.zeros_like(score)
        order = jnp.argsort(-jnp.where(valid, score, -jnp.inf))
        sb, sid = boxes[order], ids[order]
        svalid = valid[order]
        if topk > 0:
            svalid = svalid & (jnp.arange(rows.shape[0]) < topk)
        iou = _iou_corner(sb, sb)
        same = (sid[:, None] == sid[None, :]) | force_suppress
        sup = (iou > overlap_thresh) & same

        def body(alive, i):
            keep_i = alive[i] & svalid[i]
            kill = sup[i] & keep_i
            kill = kill.at[i].set(False)
            return alive & ~kill, keep_i

        alive, kept = lax.scan(body, jnp.ones(rows.shape[0], bool),
                               jnp.arange(rows.shape[0]))
        out_rows = rows[order]
        out_rows = jnp.where(kept[:, None], out_rows, -1.0)
        return out_rows

    return jax.vmap(one)(flat).reshape(shape)


# ---------------------------------------------------------------------------
# CTC loss (parity: src/operator/contrib/ctc_loss-inl.h — here a log-domain
# forward recursion with lax.scan instead of the bundled warp-ctc kernels)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def ctc_loss_ref(logits, labels, input_lengths, label_lengths, blank=0):
    """logits: [T, N, C] (pre-softmax); labels: [N, L] (0 = reference blank
    convention handled by caller). Returns per-sample negative log likelihood.
    """
    T, N, C = logits.shape
    L = labels.shape[1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    # extended label seq: blank, l1, blank, l2, ..., blank — length 2L+1
    S = 2 * L + 1
    ext = jnp.full((N, S), blank, dtype=jnp.int32)
    ext = ext.at[:, 1::2].set(labels.astype(jnp.int32))
    ext_valid = jnp.arange(S)[None, :] < (2 * label_lengths[:, None] + 1)

    # repeat mask: alpha can skip s-2 only if ext[s] != ext[s-2]
    can_skip = jnp.concatenate(
        [jnp.zeros((N, 2), bool), ext[:, 2:] != ext[:, :-2]], axis=1)

    def get_logp(t):
        return jnp.take_along_axis(logp[t], ext, axis=1)  # [N, S]

    alpha0 = jnp.full((N, S), NEG_INF)
    alpha0 = alpha0.at[:, 0].set(logp[0, :, blank])
    alpha0 = alpha0.at[:, 1].set(
        jnp.where(label_lengths > 0,
                  jnp.take_along_axis(logp[0], ext[:, 1:2], axis=1)[:, 0],
                  NEG_INF))

    def step(alpha, t):
        prev1 = jnp.concatenate([jnp.full((N, 1), NEG_INF), alpha[:, :-1]], axis=1)
        prev2 = jnp.concatenate([jnp.full((N, 2), NEG_INF), alpha[:, :-2]], axis=1)
        prev2 = jnp.where(can_skip, prev2, NEG_INF)
        merged = jnp.logaddexp(jnp.logaddexp(alpha, prev1), prev2)
        new = merged + get_logp(t)
        new = jnp.where(ext_valid, new, NEG_INF)
        # frozen past input length
        active = (t < input_lengths)[:, None]
        new = jnp.where(active, new, alpha)
        return new, None

    alpha, _ = lax.scan(step, alpha0, jnp.arange(1, T))
    last = 2 * label_lengths  # index of final blank
    a_last = jnp.take_along_axis(alpha, last[:, None].astype(jnp.int32), axis=1)[:, 0]
    a_prev = jnp.take_along_axis(
        alpha, jnp.maximum(last - 1, 0)[:, None].astype(jnp.int32), axis=1)[:, 0]
    a_prev = jnp.where(label_lengths > 0, a_prev, NEG_INF)
    return -jnp.logaddexp(a_last, a_prev)


@register("_contrib_ctc_loss", aliases=("ctc_loss", "CTCLoss",
                                        "_contrib_CTCLoss"))
def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             use_data_lengths=False, use_label_lengths=False,
             blank_label="first"):
    """data: [T, N, C] activations; label: [N, L] classes.

    Parity: src/operator/contrib/ctc_loss-inl.h. blank_label='first' means
    label values are 1..C-1 with 0 reserved (reference semantics: 'first'
    reserves index 0 for blank and actual labels are 0..C-2 shifted by +1 in
    the alphabet... the reference uses padding value 0/-1); 'last' reserves
    C-1 and uses -1 padding.
    """
    T, N, C = data.shape
    L = label.shape[1]
    if blank_label == "first":
        blank = 0
        lab = label.astype(jnp.int32)
        lab_len = (label_lengths if use_label_lengths
                   else jnp.sum((lab > 0).astype(jnp.int32), axis=1))
    else:
        blank = C - 1
        lab = label.astype(jnp.int32)
        lab_len = (label_lengths if use_label_lengths
                   else jnp.sum((lab >= 0).astype(jnp.int32), axis=1))
        lab = jnp.where(lab < 0, 0, lab)
    in_len = (data_lengths if use_data_lengths
              else jnp.full((N,), T))
    return ctc_loss_ref(data, lab, in_len.astype(jnp.int32),
                        lab_len.astype(jnp.int32), blank=blank)


# ---------------------------------------------------------------------------
# count_sketch / fft (parity: contrib count_sketch.cc, fft.cc)
# ---------------------------------------------------------------------------


@register("_contrib_count_sketch", differentiable=False)
def count_sketch(data, h, s, out_dim=0, processing_batch_size=32):
    """Random-hash feature sketch: out[j] = sum_i s[i]*data[i] where h[i]==j."""
    n, d = data.shape
    hj = h.reshape(-1).astype(jnp.int32)[:d]
    sj = s.reshape(-1)[:d]
    vals = data * sj[None, :]
    out = jnp.zeros((n, int(out_dim)), dtype=data.dtype)
    return out.at[:, hj].add(vals)


@register("_contrib_fft", differentiable=False)
def fft(data, compute_size=128):
    out = jnp.fft.fft(data, axis=-1)
    return jnp.stack([out.real, out.imag], axis=-1).reshape(
        data.shape[:-1] + (2 * data.shape[-1],))


@register("_contrib_ifft", differentiable=False)
def ifft(data, compute_size=128):
    c = data.reshape(data.shape[:-1] + (data.shape[-1] // 2, 2))
    comp = c[..., 0] + 1j * c[..., 1]
    return jnp.fft.ifft(comp, axis=-1).real.astype(jnp.float32)


# ---------------------------------------------------------------------------
# RCNN proposal (parity: contrib proposal.cc) — static-shape decode + NMS
# ---------------------------------------------------------------------------


@register("_contrib_Proposal", aliases=("Proposal",), differentiable=False)
def Proposal(cls_prob, bbox_pred, im_info, rpn_pre_nms_top_n=6000,
             rpn_post_nms_top_n=300, threshold=0.7, rpn_min_size=16,
             scales=(4, 8, 16, 32), ratios=(0.5, 1, 2), feature_stride=16,
             output_score=False, iou_loss=False):
    N, _, H, W = cls_prob.shape
    A = len(scales) * len(ratios)
    base = float(feature_stride)
    anchors = []
    for r in ratios:
        for s in scales:
            ws = base * s * np.sqrt(1.0 / r)
            hs = base * s * np.sqrt(r)
            anchors.append([-(ws - 1) / 2, -(hs - 1) / 2, (ws - 1) / 2, (hs - 1) / 2])
    anc = jnp.asarray(anchors)  # [A, 4]
    ys = jnp.arange(H) * feature_stride
    xs = jnp.arange(W) * feature_stride
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    shifts = jnp.stack([gx, gy, gx, gy], axis=-1).reshape(-1, 1, 4)
    all_anchors = (shifts + anc[None]).reshape(-1, 4)  # [H*W*A, 4]

    def one(score_map, deltas, info):
        scores = score_map[A:].transpose(1, 2, 0).reshape(-1)  # fg scores
        d = deltas.reshape(A, 4, H, W).transpose(2, 3, 0, 1).reshape(-1, 4)
        widths = all_anchors[:, 2] - all_anchors[:, 0] + 1
        heights = all_anchors[:, 3] - all_anchors[:, 1] + 1
        cx = all_anchors[:, 0] + widths / 2
        cy = all_anchors[:, 1] + heights / 2
        pcx = d[:, 0] * widths + cx
        pcy = d[:, 1] * heights + cy
        pw = jnp.exp(d[:, 2]) * widths
        ph = jnp.exp(d[:, 3]) * heights
        boxes = jnp.stack([pcx - pw / 2, pcy - ph / 2,
                           pcx + pw / 2, pcy + ph / 2], axis=-1)
        boxes = jnp.clip(boxes, 0, jnp.asarray([info[1] - 1, info[0] - 1,
                                                info[1] - 1, info[0] - 1]))
        keep = ((boxes[:, 2] - boxes[:, 0]) >= rpn_min_size) & \
               ((boxes[:, 3] - boxes[:, 1]) >= rpn_min_size)
        scores = jnp.where(keep, scores, -jnp.inf)
        k = min(rpn_pre_nms_top_n, boxes.shape[0])
        top_scores, idx = lax.top_k(scores, k)
        top_boxes = boxes[idx]
        iou = _iou_corner(top_boxes, top_boxes)
        sup = iou > threshold

        def body(alive, i):
            keep_i = alive[i] & jnp.isfinite(top_scores[i])
            kill = sup[i] & keep_i
            kill = kill.at[i].set(False)
            return alive & ~kill, keep_i

        alive, kept = lax.scan(body, jnp.ones(k, bool), jnp.arange(k))
        rank = jnp.cumsum(kept.astype(jnp.int32)) - 1
        final = jnp.zeros((rpn_post_nms_top_n, 4), dtype=boxes.dtype)
        sel = kept & (rank < rpn_post_nms_top_n)
        final = final.at[jnp.where(sel, rank, rpn_post_nms_top_n - 1)].set(
            jnp.where(sel[:, None], top_boxes, 0.0)[:k])
        fscore = jnp.zeros((rpn_post_nms_top_n,), dtype=scores.dtype)
        fscore = fscore.at[jnp.where(sel, rank, rpn_post_nms_top_n - 1)].set(
            jnp.where(sel, top_scores, 0.0)[:k])
        rois = jnp.concatenate([jnp.zeros((rpn_post_nms_top_n, 1)), final], axis=1)
        return rois, fscore[:, None]

    rois, scores = jax.vmap(one)(cls_prob, bbox_pred, im_info)
    rois = rois.reshape(-1, 5)
    if output_score:
        return rois, scores.reshape(-1, 1)
    return rois


@register("_contrib_MultiProposal", aliases=("MultiProposal",),
          differentiable=False)
def MultiProposal(cls_prob, bbox_pred, im_info, rpn_pre_nms_top_n=6000,
                  rpn_post_nms_top_n=300, threshold=0.7, rpn_min_size=16,
                  scales=(4, 8, 16, 32), ratios=(0.5, 1, 2),
                  feature_stride=16, output_score=False, iou_loss=False):
    """Batched RPN proposals: Proposal over every image in the batch, with
    rois[:, 0] carrying the source image index.

    Parity: src/operator/contrib/multi_proposal.cc (the batched variant of
    proposal.cc). Same anchor/delta/NMS pipeline; output
    [N*rpn_post_nms_top_n, 5].
    """
    N = cls_prob.shape[0]
    out = Proposal(cls_prob, bbox_pred, im_info,
                   rpn_pre_nms_top_n=rpn_pre_nms_top_n,
                   rpn_post_nms_top_n=rpn_post_nms_top_n,
                   threshold=threshold, rpn_min_size=rpn_min_size,
                   scales=scales, ratios=ratios,
                   feature_stride=feature_stride,
                   output_score=True, iou_loss=iou_loss)
    rois, scores = out
    batch_idx = jnp.repeat(jnp.arange(N, dtype=rois.dtype),
                           rpn_post_nms_top_n)
    rois = rois.at[:, 0].set(batch_idx)
    if output_score:
        return rois, scores
    return rois


# ---------------------------------------------------------------------------
# Deformable ops (R-FCN / Deformable ConvNets family) + PSROI pooling
# ---------------------------------------------------------------------------


def _bilinear_gather(img, y, x):
    """Bilinear sample `img` [C, H, W] at float positions y/x [...] with
    zero padding outside. Returns [C, ...]. Pure gathers + fma — XLA lowers
    this to vectorized dynamic-gathers, the TPU-friendly formulation of the
    reference's per-thread `bilinear_interp` (deformable_psroi_pooling.cu)."""
    H, W = img.shape[-2:]
    y0 = jnp.floor(y)
    x0 = jnp.floor(x)
    out = jnp.zeros(img.shape[:1] + y.shape, dtype=img.dtype)
    for yy, wy in ((y0, 1.0 - (y - y0)), (y0 + 1.0, y - y0)):
        for xx, wx in ((x0, 1.0 - (x - x0)), (x0 + 1.0, x - x0)):
            inside = (yy >= 0) & (yy < H) & (xx >= 0) & (xx < W)
            yi = jnp.clip(yy, 0, H - 1).astype(jnp.int32)
            xi = jnp.clip(xx, 0, W - 1).astype(jnp.int32)
            w = (wy * wx * inside).astype(img.dtype)
            out = out + img[:, yi, xi] * w
    return out


@register("_contrib_DeformableConvolution", aliases=("DeformableConvolution",))
def DeformableConvolution(data, offset, weight, bias=None, kernel=None,
                          stride=(1, 1), dilate=(1, 1), pad=(0, 0),
                          num_filter=0, num_group=1, num_deformable_group=1,
                          no_bias=False, workspace=1024, layout=None):
    """Deformable convolution (Dai et al. 2017).

    Parity: src/operator/contrib/deformable_convolution.cc — sampling
    positions of a regular conv are displaced by a learned `offset` input
    [N, 2*num_deformable_group*kh*kw, Ho, Wo] (y-offset then x-offset per
    kernel tap, per deformable group), values fetched by bilinear
    interpolation with zero padding.

    TPU-native redesign: instead of the reference's deformable-im2col CUDA
    kernel, the sampled patch tensor is built with vectorized bilinear
    gathers and contracted with the weights in one grouped einsum on the
    MXU. Differentiable in data, offset, and weight via jax autodiff (the
    reference hand-writes col2im backward kernels).
    """
    N, C, H, W = data.shape
    F = weight.shape[0]
    kh, kw = weight.shape[2], weight.shape[3]
    sh, sw = (stride, stride) if isinstance(stride, int) else stride
    dh, dw = (dilate, dilate) if isinstance(dilate, int) else dilate
    ph, pw = (pad, pad) if isinstance(pad, int) else pad
    Ho = (H + 2 * ph - (dh * (kh - 1) + 1)) // sh + 1
    Wo = (W + 2 * pw - (dw * (kw - 1) + 1)) // sw + 1
    K = kh * kw
    G, Gd = num_group, num_deformable_group

    # base sampling grid per kernel tap: [K, Ho, Wo]
    ky, kx = jnp.meshgrid(jnp.arange(kh) * dh, jnp.arange(kw) * dw,
                          indexing="ij")
    base_y = (jnp.arange(Ho) * sh - ph)[None, :, None] + \
        ky.reshape(K, 1, 1)
    base_x = (jnp.arange(Wo) * sw - pw)[None, None, :] + \
        kx.reshape(K, 1, 1)

    def one(img, off):
        # off: [2*Gd*K, Ho, Wo] -> [Gd, K, 2, Ho, Wo] (y first, then x)
        o = off.reshape(Gd, K, 2, Ho, Wo)
        y = base_y[None] + o[:, :, 0]                       # [Gd, K, Ho, Wo]
        x = base_x[None] + o[:, :, 1]
        img_g = img.reshape(Gd, C // Gd, H, W)
        cols = jax.vmap(_bilinear_gather)(img_g, y, x)      # [Gd, C/Gd, K, Ho, Wo]
        cols = cols.reshape(G, C // G, K, Ho, Wo)
        wg = weight.reshape(G, F // G, C // G, K)
        out = jnp.einsum("gfck,gckhw->gfhw", wg, cols,
                         preferred_element_type=jnp.float32)
        return out.reshape(F, Ho, Wo).astype(data.dtype)

    out = jax.vmap(one)(data, offset)
    if bias is not None and not no_bias:
        out = out + bias.reshape(1, F, 1, 1)
    return out


@register("_contrib_PSROIPooling", aliases=("PSROIPooling",))
def PSROIPooling(data, rois, spatial_scale=1.0, output_dim=1, pooled_size=1,
                 group_size=0):
    """Position-sensitive ROI pooling (R-FCN).

    Parity: src/operator/contrib/psroi_pooling.cu PSROIPoolForwardKernel —
    rois are [R, 5] (batch_index, x1, y1, x2, y2); coordinates are rounded,
    scaled by spatial_scale, each of pooled_size^2 bins averages the integer
    pixels of its sub-window from channel (ctop*gs + gh)*gs + gw.

    TPU-native redesign: the data-dependent bin loops become masked
    einsum reductions, so every ROI is one dense contraction — no dynamic
    shapes. The bin→channel assignment is static, so only the output_dim
    channels each bin actually reads are gathered (not all C = od*gs^2).
    Differentiable in data via autodiff.
    """
    P = int(pooled_size)
    gs = int(group_size) if group_size else P
    C, H, W = data.shape[1], data.shape[2], data.shape[3]
    assert C == output_dim * gs * gs, \
        "data channels (%d) != output_dim*group_size^2 (%d)" % (
            C, output_dim * gs * gs)
    gh = np.clip((np.arange(P) * gs) // P, 0, gs - 1)
    gw = gh
    # channel read by bin (ctop, ph, pw): (ctop*gs + gh)*gs + gw — static
    chan = ((np.arange(output_dim)[:, None, None] * gs + gh[None, :, None])
            * gs + gw[None, None, :])                        # [od, P, P]
    chan = jnp.asarray(chan)

    def one(roi):
        b = roi[0].astype(jnp.int32)
        d = jnp.take(data, b, axis=0)                        # [C, H, W]
        start_w = jnp.round(roi[1]) * spatial_scale
        start_h = jnp.round(roi[2]) * spatial_scale
        end_w = (jnp.round(roi[3]) + 1.0) * spatial_scale
        end_h = (jnp.round(roi[4]) + 1.0) * spatial_scale
        rw = jnp.maximum(end_w - start_w, 0.1)
        rh = jnp.maximum(end_h - start_h, 0.1)
        bin_h, bin_w = rh / P, rw / P
        hs = jnp.clip(jnp.floor(jnp.arange(P) * bin_h + start_h), 0, H)
        he = jnp.clip(jnp.ceil((jnp.arange(P) + 1) * bin_h + start_h), 0, H)
        ws = jnp.clip(jnp.floor(jnp.arange(P) * bin_w + start_w), 0, W)
        we = jnp.clip(jnp.ceil((jnp.arange(P) + 1) * bin_w + start_w), 0, W)
        hidx = jnp.arange(H)[None, :]
        widx = jnp.arange(W)[None, :]
        mask_h = ((hidx >= hs[:, None]) & (hidx < he[:, None])).astype(d.dtype)
        mask_w = ((widx >= ws[:, None]) & (widx < we[:, None])).astype(d.dtype)
        d_sel = d[chan]                                      # [od, P, P, H, W]
        binsum = jnp.einsum("oabhw,ah,bw->oab", d_sel, mask_h, mask_w)
        area = (he - hs)[None, :, None] * (we - ws)[None, None, :]
        return jnp.where(area > 0, binsum / jnp.maximum(area, 1.0), 0.0)

    return jax.vmap(one)(rois).astype(data.dtype)


@register("_contrib_DeformablePSROIPooling",
          aliases=("DeformablePSROIPooling",), num_outputs=2)
def DeformablePSROIPooling(data, rois, trans=None, spatial_scale=1.0,
                           output_dim=1, group_size=1, pooled_size=1,
                           part_size=0, sample_per_part=1, trans_std=0.0,
                           no_trans=False):
    """Deformable position-sensitive ROI pooling.

    Parity: src/operator/contrib/deformable_psroi_pooling.cu
    DeformablePSROIPoolForwardKernel — each bin takes sample_per_part^2
    bilinear samples at positions displaced by `trans`
    [R, 2*num_classes, part_size, part_size] (scaled by trans_std and the
    roi extent); samples falling outside (-0.5, dim-0.5) are dropped from
    the average. Outputs (pooled [R, output_dim, P, P], top_count).

    TPU-native redesign: all samples for all bins gather in one vectorized
    bilinear pass per ROI; the valid-sample count becomes a mask sum. The
    bin→channel assignment is static, so only the channel each bin actually
    reads is sampled (not all C = od*gs^2).
    """
    P = int(pooled_size)
    gs = int(group_size)
    sp = int(sample_per_part)
    part = int(part_size) if part_size else P
    C, H, W = data.shape[1], data.shape[2], data.shape[3]
    assert C == output_dim * gs * gs, \
        "data channels (%d) != output_dim*group_size^2 (%d)" % (
            C, output_dim * gs * gs)
    ncls = 1 if (no_trans or trans is None) else trans.shape[1] // 2
    assert ncls >= 1 and output_dim % ncls == 0, \
        "output_dim (%d) must be a positive multiple of num_classes (%d) " \
        "derived from trans channels" % (output_dim, ncls)
    cec = output_dim // ncls  # channels_each_class
    gh = np.clip((np.arange(P) * gs) // P, 0, gs - 1)
    gw = gh
    part_h = np.floor(np.arange(P) / P * part).astype(np.int32)
    part_w = part_h
    # channel read by bin (ctop, ph, pw) and its trans class — both static
    chan = ((np.arange(output_dim)[:, None, None] * gs + gh[None, :, None])
            * gs + gw[None, None, :])                        # [od, P, P]
    chan = jnp.asarray(chan)
    cls_of = jnp.asarray(np.arange(output_dim) // cec)       # [od]

    def one(roi, tr):
        b = roi[0].astype(jnp.int32)
        d = jnp.take(data, b, axis=0)                        # [C, H, W]
        start_w = jnp.round(roi[1]) * spatial_scale - 0.5
        start_h = jnp.round(roi[2]) * spatial_scale - 0.5
        end_w = (jnp.round(roi[3]) + 1.0) * spatial_scale - 0.5
        end_h = (jnp.round(roi[4]) + 1.0) * spatial_scale - 0.5
        rw = jnp.maximum(end_w - start_w, 0.1)
        rh = jnp.maximum(end_h - start_h, 0.1)
        bin_h, bin_w = rh / P, rw / P
        sub_h, sub_w = bin_h / sp, bin_w / sp
        if tr is None:
            tx = ty = jnp.zeros((1, P, P))
        else:
            t = tr.reshape(ncls, 2, part, part)
            tx = t[:, 0][:, part_h[:, None], part_w[None, :]] * trans_std
            ty = t[:, 1][:, part_h[:, None], part_w[None, :]] * trans_std
        # sample positions [ncls, P, P, sp, sp]
        hstart = jnp.arange(P)[:, None] * bin_h + start_h + ty * rh
        wstart = jnp.arange(P)[None, :] * bin_w + start_w + tx * rw
        y = hstart[..., None, None] + \
            (jnp.arange(sp) * sub_h)[None, None, None, :, None]
        x = wstart[..., None, None] + \
            (jnp.arange(sp) * sub_w)[None, None, None, None, :]
        # boundary samples at exactly -0.5 / dim-0.5 are kept (the reference
        # skips only strictly-outside samples)
        valid = (x >= -0.5) & (x <= W - 0.5) & (y >= -0.5) & (y <= H - 0.5)
        yc = jnp.clip(y, 0.0, H - 1.0)
        xc = jnp.clip(x, 0.0, W - 1.0)
        # sample only the channel each bin reads: [od*P*P] single-channel
        # bilinear gathers instead of all C channels at every position
        imgs = d[chan].reshape(-1, H, W)                     # [od*P*P, H, W]
        yc, xc = jnp.broadcast_arrays(yc, xc)  # [ncls, P, P, sp, sp]
        yb = yc[cls_of].reshape(-1, sp, sp)
        xb = xc[cls_of].reshape(-1, sp, sp)
        vb = jax.vmap(lambda im, yy, xx:
                      _bilinear_gather(im[None], yy, xx)[0])(imgs, yb, xb)
        validb = valid[cls_of].reshape(-1, sp, sp).astype(d.dtype)
        s = (vb * validb).sum(axis=(-1, -2)).reshape(output_dim, P, P)
        cnt_sel = valid.sum(axis=(-1, -2)).astype(d.dtype)[cls_of]  # [od,P,P]
        pooled = jnp.where(cnt_sel > 0, s / jnp.maximum(cnt_sel, 1.0), 0.0)
        return pooled.astype(data.dtype), cnt_sel.astype(data.dtype)

    if trans is None or no_trans:
        out, cnt = jax.vmap(lambda r: one(r, None))(rois)
    else:
        out, cnt = jax.vmap(one)(rois, trans)
    return out, cnt


@register("_contrib_div_sqrt_dim")
def div_sqrt_dim(data):
    """data / sqrt(last-dim size) — the transformer attention scaler
    (parity: src/operator/contrib/transformer-inl.h _contrib_div_sqrt_dim)."""
    return data / jnp.sqrt(jnp.asarray(data.shape[-1], dtype=data.dtype))


@register("_contrib_bipartite_matching", num_outputs=2,
          differentiable=False)
def bipartite_matching(dist, is_ascend=False, threshold=0.5, topk=-1):
    """Greedy bipartite matching over pairwise scores (parity:
    src/operator/contrib/bounding_box.cc `_contrib_bipartite_matching`).

    dist: [..., N, M] score matrix. Repeatedly takes the globally best
    still-unmatched (row, col) pair whose score beats `threshold`
    (better = larger unless is_ascend), marking both as used; at most
    `topk` matches per matrix when topk > 0. Returns (row_match[..., N]
    giving the matched col or -1, col_match[..., M] giving the matched
    row or -1). Data-dependent greedy loop expressed as lax.fori_loop so
    the whole op stays jittable on TPU.
    """
    batch_shape = dist.shape[:-2]
    n, m = dist.shape[-2], dist.shape[-1]
    flat = dist.reshape((-1, n, m)).astype(jnp.float32)
    sign = -1.0 if is_ascend else 1.0
    thr = jnp.float32(threshold) * sign
    iters = min(n, m) if topk is None or topk <= 0 else min(topk, min(n, m))

    def one(d):
        d = d * sign  # larger-is-better canonical form

        def body(_, st):
            dd, rmatch, cmatch = st
            best = jnp.argmax(dd)
            r, c = best // m, best % m
            ok = dd[r, c] >= thr
            rmatch = jnp.where(ok, rmatch.at[r].set(c), rmatch)
            cmatch = jnp.where(ok, cmatch.at[c].set(r), cmatch)
            dd = jnp.where(ok, dd.at[r, :].set(-jnp.inf), dd)
            dd = jnp.where(ok, dd.at[:, c].set(-jnp.inf), dd)
            return dd, rmatch, cmatch

        init = (d, jnp.full((n,), -1, jnp.float32),
                jnp.full((m,), -1, jnp.float32))
        _, rmatch, cmatch = lax.fori_loop(0, iters, body, init)
        return rmatch, cmatch

    rm, cm = jax.vmap(one)(flat)
    return rm.reshape(batch_shape + (n,)), cm.reshape(batch_shape + (m,))


@register("khatri_rao")
def khatri_rao(*args):
    """Column-wise Khatri-Rao product of 2-D matrices.

    Parity: src/operator/contrib/krprod.cc `khatri_rao` — inputs
    A_i [M_i, N] share the column count N; output [prod(M_i), N] whose kth
    column is the Kronecker product of the kth columns (row-major order:
    earlier matrices vary slowest, matching the reference example).
    """
    out = args[0]
    for m in args[1:]:
        out = (out[:, None, :] * m[None, :, :]).reshape(-1, out.shape[-1])
    return out


@register("_contrib_PagedAttention", aliases=("PagedAttention",),
          differentiable=False)
def PagedAttention(query, k_pool, v_pool, block_table, q_start,
                   block_size=16, scale=None):
    """Ragged paged attention over a block-pooled KV-cache — the serving
    decode/prefill read (ops/pallas_paged.py) as a public operator.

    query [B, Tq, H, Dh]; k_pool/v_pool [num_blocks, H, block_size, Dh]
    (ONE layer of serving.PagedKVCache's contiguous-per-layer pools);
    block_table [B, w] int32; q_start [B] int32 true position of each
    row's first query token. Keys past position q_start+i are masked per
    row (ragged; doubles as the causal mask for prefill chunks).

    With MXNET_PAGED_ATTENTION=1 (and Mosaic-tileable shapes on real
    TPUs) the read runs as the Pallas kernel — block-table walk in VMEM,
    online f32 softmax, no dense gather; otherwise the same math
    composes from gather-by-table + masked softmax in XLA, so the op is
    always available and the env flag only switches implementation.
    Inference-only (decode serving path), like the reference's
    data-dependent contrib kernels."""
    import math as _math
    from . import pallas_paged as _pp
    from .pallas_attention import default_interpret

    B, Tq, H, Dh = query.shape
    if scale is None:
        scale = 1.0 / _math.sqrt(Dh)
    interpret = default_interpret()
    if _pp.paged_enabled() and _pp.paged_fallback_reason(
            Dh, block_size, interpret, k_pool.dtype) is None:
        return _pp.paged_attention(query, k_pool, v_pool, block_table,
                                   q_start, block_size, scale=scale,
                                   interpret=interpret)
    w = block_table.shape[1]
    # (B, w, H, bs, Dh) -> position-ordered (B, w*bs, H, Dh)
    ks = k_pool[block_table].transpose(0, 1, 3, 2, 4).reshape(
        B, w * block_size, H, Dh)
    vs = v_pool[block_table].transpose(0, 1, 3, 2, 4).reshape(
        B, w * block_size, H, Dh)
    s = jnp.einsum("bqhd,bthd->bhqt", query.astype(jnp.float32),
                   ks.astype(jnp.float32)) * scale
    kp = jnp.arange(w * block_size)[None, None, None, :]
    qp = (q_start[:, None, None, None]
          + jnp.arange(Tq)[None, None, :, None])
    s = jnp.where(kp <= qp, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqt,bthd->bqhd", p, vs.astype(p.dtype))
    return out.astype(query.dtype)
