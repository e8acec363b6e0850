"""Detection building blocks: anchors, box coding, IoU matching, losses.

Object detection is the reference's flagship SyncBN use case ("this
performance drop is known to happen for object detection models",
reference ``README.md:3``; RetinaNet-R50-FPN at per-chip batch=2 is the
capability config in BASELINE.json). All ops are static-shape and
jit-friendly: ground truth arrives padded to a fixed ``max_boxes`` with a
validity mask, matching is a dense IoU argmax, and losses mask invalid
entries — no data-dependent shapes anywhere (XLA requirement).

Target assignment (:func:`assign_targets`) is gather-free and works on
coordinate rows: a TPU gather with one scalar index per anchor runs index
by index, and an array whose minor dimension is 2 or 4 fills that many of
a vector register's 128 lanes, so the matched label and box are picked by
a one-hot select over the (few) ground-truth boxes, coordinate by
coordinate.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


# -- anchors --------------------------------------------------------------


def generate_level_anchors(
    feat_h: int,
    feat_w: int,
    stride: int,
    sizes: Sequence[float],
    ratios: Sequence[float] = (0.5, 1.0, 2.0),
) -> jnp.ndarray:
    """Anchors for one FPN level, (H*W*A, 4) as (x1, y1, x2, y2), centered
    on the stride grid (torchvision AnchorGenerator semantics)."""
    base = []
    for size in sizes:
        area = float(size) ** 2
        for r in ratios:
            w = math.sqrt(area / r)
            h = w * r
            base.append([-w / 2, -h / 2, w / 2, h / 2])
    base_a = jnp.asarray(base, jnp.float32)  # (A, 4)

    cx = (jnp.arange(feat_w, dtype=jnp.float32) + 0.5) * stride
    cy = (jnp.arange(feat_h, dtype=jnp.float32) + 0.5) * stride
    cxg, cyg = jnp.meshgrid(cx, cy, indexing="xy")
    centers = jnp.stack([cxg, cyg, cxg, cyg], axis=-1).reshape(-1, 1, 4)
    return (centers + base_a[None]).reshape(-1, 4)


def retinanet_anchors(
    image_size: tuple[int, int],
    strides: Sequence[int] = (8, 16, 32, 64, 128),
    anchor_scale: float = 4.0,
) -> jnp.ndarray:
    """All-level RetinaNet anchors concatenated: per level, 3 octave scales
    (2^0, 2^1/3, 2^2/3) × 3 ratios, base size ``anchor_scale × stride``."""
    h, w = image_size
    out = []
    for stride in strides:
        sizes = [anchor_scale * stride * (2 ** (o / 3)) for o in range(3)]
        out.append(
            generate_level_anchors(
                math.ceil(h / stride), math.ceil(w / stride), stride, sizes
            )
        )
    return jnp.concatenate(out, axis=0)


# -- box coding -----------------------------------------------------------


def _encode_rows(b, a):
    """:func:`box_encode` on coordinate rows: ``b`` and ``a`` are
    (x1, y1, x2, y2) tuples of arrays that broadcast; returns the four
    rows (dx, dy, dw, dh)."""
    aw = a[2] - a[0]
    ah = a[3] - a[1]
    ax = a[0] + 0.5 * aw
    ay = a[1] + 0.5 * ah
    bw = jnp.maximum(b[2] - b[0], 1e-6)
    bh = jnp.maximum(b[3] - b[1], 1e-6)
    bx = b[0] + 0.5 * bw
    by = b[1] + 0.5 * bh
    return (bx - ax) / aw, (by - ay) / ah, jnp.log(bw / aw), jnp.log(bh / ah)


def box_encode(boxes: jnp.ndarray, anchors: jnp.ndarray) -> jnp.ndarray:
    """(x1y1x2y2 boxes, anchors) → (dx, dy, dw, dh) regression targets
    (Faster-R-CNN coding, weights 1)."""
    return jnp.stack(
        _encode_rows(jnp.moveaxis(boxes, -1, 0), jnp.moveaxis(anchors, -1, 0)),
        axis=-1,
    )


def box_decode(deltas: jnp.ndarray, anchors: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`box_encode`; clamps dw/dh like torchvision
    (log(1000/16) ≈ 4.135) for numerical safety."""
    aw = anchors[..., 2] - anchors[..., 0]
    ah = anchors[..., 3] - anchors[..., 1]
    ax = anchors[..., 0] + 0.5 * aw
    ay = anchors[..., 1] + 0.5 * ah
    clamp = math.log(1000.0 / 16)
    dx, dy = deltas[..., 0], deltas[..., 1]
    dw = jnp.clip(deltas[..., 2], -clamp, clamp)
    dh = jnp.clip(deltas[..., 3], -clamp, clamp)
    cx = dx * aw + ax
    cy = dy * ah + ay
    w = jnp.exp(dw) * aw
    h = jnp.exp(dh) * ah
    return jnp.stack(
        [cx - 0.5 * w, cy - 0.5 * h, cx + 0.5 * w, cy + 0.5 * h], axis=-1
    )


# -- IoU + matching -------------------------------------------------------


def box_iou(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Pairwise IoU: (N, 4) × (M, 4) → (N, M), coordinate by coordinate
    (no intermediate with a minor dimension of 2)."""
    ax1, ay1, ax2, ay2 = a.T[:, :, None]
    bx1, by1, bx2, by2 = b.T[:, None, :]
    w = jnp.clip(jnp.minimum(ax2, bx2) - jnp.maximum(ax1, bx1), 0.0)
    h = jnp.clip(jnp.minimum(ay2, by2) - jnp.maximum(ay1, by1), 0.0)
    inter = w * h
    area_a = jnp.clip(ax2 - ax1, 0) * jnp.clip(ay2 - ay1, 0)
    area_b = jnp.clip(bx2 - bx1, 0) * jnp.clip(by2 - by1, 0)
    union = area_a + area_b - inter
    return jnp.where(union > 0, inter / union, 0.0)


def match_anchors(
    anchors: jnp.ndarray,
    gt_boxes: jnp.ndarray,
    gt_valid: jnp.ndarray,
    *,
    high: float = 0.5,
    low: float = 0.4,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Max-IoU assigner (torchvision Matcher semantics with
    allow_low_quality_matches): per anchor, the best valid GT index or
    -1 (background) / -2 (ignore, between thresholds). Anchors that are the
    argmax for some GT are force-matched to it.

    Returns (matched_idx (N,), max_iou (N,)).
    """
    iou = box_iou(anchors, gt_boxes)  # (N, M)
    iou = jnp.where(gt_valid[None, :], iou, -1.0)
    # one IoU matrix for every reduction below: the promotion compares it
    # with its own column maxima for equality, and a compiler that fused
    # the arithmetic into each reduction could round the two sides apart
    # (materialized it is also the faster program on the TPU)
    iou = jax.lax.optimization_barrier(iou)
    best_gt = jnp.argmax(iou, axis=1)
    best_iou = jnp.max(iou, axis=1)
    matched = jnp.where(
        best_iou >= high, best_gt, jnp.where(best_iou < low, -1, -2)
    )
    # low-quality promotion: for each valid GT, every anchor achieving that
    # GT's best IoU is force-matched to it. Dense formulation (no scatter:
    # padded invalid GTs must not clobber valid promotions — their masked
    # IoU columns argmax to anchor 0). When an anchor ties as best for
    # several GTs, the highest GT index wins, matching torch's sequential
    # overwrite ([torch] Matcher.set_low_quality_matches_).
    gt_best_iou = jnp.max(iou, axis=0)  # (M,)
    ok = gt_valid & (gt_best_iou > 0)
    is_best = (iou >= gt_best_iou[None, :]) & ok[None, :]  # (N, M)
    gt_index = jnp.arange(gt_boxes.shape[0], dtype=jnp.int32)[None, :]
    promote_to = jnp.max(jnp.where(is_best, gt_index, -1), axis=1)
    matched = jnp.where(promote_to >= 0, promote_to, matched)
    return matched, best_iou


def assign_targets(
    anchors: jnp.ndarray,
    boxes: jnp.ndarray,
    labels: jnp.ndarray,
    valid: jnp.ndarray,
    num_classes: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One image's training targets for the (A, 4) ``anchors`` from its
    padded ground truth (``boxes`` (M, 4), ``labels`` (M,), ``valid`` (M,)).

    Returns ``(cls_t, box_t, fg, ignore)``: the one-hot class targets
    (A, ``num_classes``), all zero off the foreground; the
    :func:`box_encode` targets as four rows (4, A), meaningful on the
    foreground only; and the (A,) foreground and ignore masks of
    :func:`match_anchors`.

    The matched label and box are taken by a select against the (A, M)
    one-hot of the matched index (see the module docstring), whose rows
    are all zero for background and ignored anchors: those read label -1
    and a zero box, which encodes to finite values.
    """
    with jax.named_scope("anchors_match"):
        matched, _ = match_anchors(anchors, boxes, valid)
        fg = matched >= 0
        ignore = matched == -2
        gt_index = jnp.arange(boxes.shape[0], dtype=matched.dtype)[None, :]
        picked = matched[:, None] == gt_index  # (A, M)

    def pick(per_gt):  # (M,) → (A,), 0 where the anchor matched no GT
        return jnp.sum(jnp.where(picked, per_gt[None, :], 0), axis=1)

    with jax.named_scope("focal"):
        cls_t = jax.nn.one_hot(jnp.where(fg, pick(labels), -1), num_classes)
    with jax.named_scope("smooth_l1"):
        box_t = jnp.stack(_encode_rows(tuple(pick(r) for r in boxes.T),
                                       tuple(anchors.T)))
    return cls_t, box_t, fg, ignore


# -- losses ---------------------------------------------------------------


def sigmoid_focal_loss(
    logits: jnp.ndarray,
    targets: jnp.ndarray,
    *,
    alpha: float = 0.25,
    gamma: float = 2.0,
) -> jnp.ndarray:
    """Elementwise sigmoid focal loss (RetinaNet paper; torchvision
    ``sigmoid_focal_loss`` semantics, reduction='none')."""
    import optax

    p = jax.nn.sigmoid(logits)
    ce = optax.sigmoid_binary_cross_entropy(logits, targets)
    p_t = p * targets + (1 - p) * (1 - targets)
    loss = ce * (1 - p_t) ** gamma
    if alpha >= 0:
        alpha_t = alpha * targets + (1 - alpha) * (1 - targets)
        loss = alpha_t * loss
    return loss


def smooth_l1(pred: jnp.ndarray, target: jnp.ndarray, beta: float = 0.1111) -> jnp.ndarray:
    d = jnp.abs(pred - target)
    return jnp.where(d < beta, 0.5 * d * d / beta, d - 0.5 * beta)


# -- host-side NMS (eval post-process) ------------------------------------


def nms(boxes: np.ndarray, scores: np.ndarray, iou_threshold: float = 0.5):
    """Greedy non-maximum suppression on the host (numpy) — the eval
    post-process torchvision runs after RetinaNet decode. Returns indices
    of kept boxes in descending score order."""
    boxes = np.asarray(boxes, np.float32)
    scores = np.asarray(scores, np.float32)
    order = np.argsort(-scores)
    keep = []
    while order.size:
        i = order[0]
        keep.append(int(i))
        if order.size == 1:
            break
        rest = order[1:]
        lt = np.maximum(boxes[i, :2], boxes[rest, :2])
        rb = np.minimum(boxes[i, 2:], boxes[rest, 2:])
        wh = np.clip(rb - lt, 0, None)
        inter = wh[:, 0] * wh[:, 1]
        area_i = max((boxes[i, 2] - boxes[i, 0]) * (boxes[i, 3] - boxes[i, 1]), 0)
        area_r = np.clip(boxes[rest, 2] - boxes[rest, 0], 0, None) * np.clip(
            boxes[rest, 3] - boxes[rest, 1], 0, None
        )
        union = area_i + area_r - inter
        iou = np.where(union > 0, inter / union, 0.0)
        order = rest[iou <= iou_threshold]
    return keep


def batched_nms(boxes, scores, classes, iou_threshold: float = 0.5):
    """Per-class NMS (boxes of different classes never suppress each
    other), torchvision.ops.batched_nms semantics."""
    boxes = np.asarray(boxes, np.float32)
    classes = np.asarray(classes)
    if boxes.size == 0:
        return []
    # offset trick: shift each class into a disjoint coordinate region.
    # Normalize to a non-negative origin first — decoded boxes can have
    # negative coordinates near image edges, which would otherwise leak
    # across class regions.
    boxes = boxes - float(boxes.min())
    span = float(boxes.max()) + 1.0
    offsets = classes.astype(np.float32)[:, None] * span
    return nms(boxes + offsets, scores, iou_threshold)
