"""RetinaNet + detection ops tests: box coding roundtrip, IoU/matcher,
focal loss values, FPN shapes, end-to-end SyncBN DP train step at
per-chip batch=2 (the BASELINE.json capability config)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
import torch
from flax import nnx

from tpu_syncbn import compat
from tpu_syncbn import nn as tnn, parallel
from tpu_syncbn.models import detection as det
from tpu_syncbn.models import retinanet as rn
from tpu_syncbn.models.resnet import ResNet, BasicBlock


def test_box_encode_decode_roundtrip():
    rng = np.random.RandomState(0)
    anchors = jnp.asarray(
        np.stack([
            rng.uniform(0, 100, 50), rng.uniform(0, 100, 50),
            rng.uniform(110, 200, 50), rng.uniform(110, 200, 50),
        ], -1), jnp.float32,
    )
    boxes = anchors + jnp.asarray(rng.uniform(-5, 5, (50, 4)), jnp.float32)
    deltas = det.box_encode(boxes, anchors)
    back = det.box_decode(deltas, anchors)
    np.testing.assert_allclose(np.asarray(back), np.asarray(boxes), rtol=1e-4, atol=1e-3)


def test_box_iou_known_values():
    a = jnp.asarray([[0, 0, 10, 10]], jnp.float32)
    b = jnp.asarray([[0, 0, 10, 10], [5, 5, 15, 15], [20, 20, 30, 30]], jnp.float32)
    iou = np.asarray(det.box_iou(a, b))[0]
    np.testing.assert_allclose(iou, [1.0, 25 / 175, 0.0], rtol=1e-5)


def test_matcher_thresholds_and_promotion():
    anchors = jnp.asarray([
        [0, 0, 10, 10],     # IoU 1.0 with gt0 -> fg
        [0, 0, 12, 10],     # high IoU with gt0 -> fg
        [4, 4, 18, 18],     # mid IoU -> ignore band or bg
        [40, 40, 50, 50],   # best anchor for gt1 (low IoU) -> promoted
        [100, 100, 110, 110],  # background
    ], jnp.float32)
    gt = jnp.asarray([[0, 0, 10, 10], [39, 39, 52, 55]], jnp.float32)
    valid = jnp.asarray([True, True])
    matched, _ = det.match_anchors(anchors, gt, valid)
    m = np.asarray(matched)
    assert m[0] == 0 and m[1] == 0
    assert m[3] == 1      # promoted low-quality match
    assert m[4] == -1     # background


def test_matcher_no_valid_gt():
    anchors = jnp.asarray([[0, 0, 10, 10]], jnp.float32)
    gt = jnp.zeros((3, 4), jnp.float32)
    valid = jnp.asarray([False, False, False])
    matched, _ = det.match_anchors(anchors, gt, valid)
    assert int(matched[0]) == -1


def test_focal_loss_matches_torchvision_formula():
    """Check against torchvision.ops.sigmoid_focal_loss reference formula
    computed with torch."""
    rng = np.random.RandomState(1)
    logits = rng.randn(32).astype(np.float32)
    targets = (rng.rand(32) > 0.7).astype(np.float32)

    ours = np.asarray(det.sigmoid_focal_loss(jnp.asarray(logits), jnp.asarray(targets)))

    lt = torch.from_numpy(logits)
    tt = torch.from_numpy(targets)
    p = torch.sigmoid(lt)
    ce = torch.nn.functional.binary_cross_entropy_with_logits(lt, tt, reduction="none")
    p_t = p * tt + (1 - p) * (1 - tt)
    ref = ce * ((1 - p_t) ** 2.0)
    ref = (0.25 * tt + 0.75 * (1 - tt)) * ref
    np.testing.assert_allclose(ours, ref.numpy(), rtol=1e-5, atol=1e-6)


def test_anchor_count_matches_feature_grid():
    anchors = det.retinanet_anchors((64, 64))
    expected = sum(
        -(-64 // s) * -(-64 // s) * 9 for s in (8, 16, 32, 64, 128)
    )
    assert anchors.shape == (expected, 4)


def _small_retinanet(image_size=(64, 64), num_classes=5):
    backbone = ResNet(BasicBlock, (1, 1, 1, 1), num_classes=1,
                      width=16, rngs=nnx.Rngs(0))
    return rn.RetinaNet(
        num_classes=num_classes, image_size=image_size,
        fpn_channels=32, backbone=backbone, rngs=nnx.Rngs(0),
    )


def test_retinanet_forward_shapes():
    model = _small_retinanet()
    cls, box = model(jnp.zeros((2, 64, 64, 3)))
    n_anchors = det.retinanet_anchors((64, 64)).shape[0]
    assert cls.shape == (2, n_anchors, 5)
    assert box.shape == (2, n_anchors, 4)
    # focal prior init: initial foreground probability ≈ 0.01
    p = jax.nn.sigmoid(cls)
    assert 0.005 < float(p.mean()) < 0.02


@pytest.mark.slow  # spawn/compile-heavy: tier-1 runs against an 870s kill
def test_retinanet_loss_and_grad_finite():
    model = _small_retinanet()
    B, M = 2, 4
    images = jnp.asarray(np.random.RandomState(0).randn(B, 64, 64, 3), jnp.float32)
    gt_boxes = jnp.asarray([[[8, 8, 40, 40], [20, 20, 60, 56]] + [[0, 0, 0, 0]] * 2] * B, jnp.float32)
    gt_labels = jnp.asarray([[1, 3, 0, 0]] * B, jnp.int32)
    gt_valid = jnp.asarray([[True, True, False, False]] * B)

    total, aux = model.loss(images, gt_boxes, gt_labels, gt_valid)
    assert np.isfinite(float(total))
    assert float(aux["box_loss"]) > 0

    graphdef, params, rest = nnx.split(model, nnx.Param, ...)

    def loss_fn(p):
        m = compat.nnx_merge(graphdef, p, rest, copy=True)
        t, _ = m.loss(images, gt_boxes, gt_labels, gt_valid)
        return t

    grads = jax.grad(loss_fn)(params)
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in flat)
    assert any(float(jnp.abs(g).max()) > 0 for g in flat)


@pytest.mark.slow
def test_retinanet_syncbn_dp_per_chip_batch2():
    """The capability config: SyncBN-converted RetinaNet under DP with
    per-chip batch=2 (global 16 over 8 replicas) — one step runs, loss
    finite and decreases when overfitting a fixed batch."""
    model = tnn.convert_sync_batchnorm(_small_retinanet())
    n_sync = sum(1 for _, n in nnx.iter_graph(model)
                 if isinstance(n, tnn.SyncBatchNorm))
    assert n_sync > 0

    B = 16  # 2 per chip × 8
    rng = np.random.RandomState(3)
    images = jnp.asarray(rng.randn(B, 64, 64, 3), jnp.float32)
    gt_boxes = jnp.tile(jnp.asarray([[[8, 8, 48, 48], [0, 0, 0, 0]]], jnp.float32), (B, 1, 1))
    gt_labels = jnp.tile(jnp.asarray([[2, 0]], jnp.int32), (B, 1))
    gt_valid = jnp.tile(jnp.asarray([[True, False]]), (B, 1))

    def loss_fn(m, batch):
        imgs, boxes, labels, valid = batch
        return m.loss(imgs, boxes, labels, valid)

    dp = parallel.DataParallel(model, optax.adam(1e-3), loss_fn)
    batch = (images, gt_boxes, gt_labels, gt_valid)
    losses = [float(dp.train_step(batch).loss) for _ in range(8)]
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


def test_retinanet_decode_shapes():
    model = _small_retinanet()
    boxes, scores, classes, keep = model.decode(jnp.zeros((2, 64, 64, 3)), top_k=20)
    assert boxes.shape == (2, 20, 4)
    assert scores.shape == classes.shape == keep.shape == (2, 20)


def test_matcher_promotion_with_padded_invalid_gt():
    """Regression: padded invalid GT columns must not clobber a valid GT's
    low-quality promotion (the review's anchor-0 scatter-collision case)."""
    anchors = jnp.asarray([[0, 0, 10, 10], [50, 50, 60, 60]], jnp.float32)
    gt = jnp.asarray([[0, 0, 10, 22], [0, 0, 0, 0], [0, 0, 0, 0]], jnp.float32)
    valid = jnp.asarray([True, False, False])
    matched, _ = det.match_anchors(anchors, gt, valid)
    assert int(matched[0]) == 0  # promoted to its best (only) valid GT


def test_matcher_tie_highest_gt_wins():
    """Anchor tied as best for two GTs: highest GT index wins (torch's
    sequential overwrite order)."""
    anchors = jnp.asarray([[0, 0, 10, 10]], jnp.float32)
    gt = jnp.asarray([[0, 0, 10, 30], [0, 0, 30, 10]], jnp.float32)  # equal IoU
    valid = jnp.asarray([True, True])
    matched, _ = det.match_anchors(anchors, gt, valid)
    assert int(matched[0]) == 1


# -- assign_targets: gather-free target assignment -------------------------

PUBLISHED_IMAGE, PUBLISHED_M, PUBLISHED_K = (800, 1344), 100, 80


@pytest.fixture(scope="module")
def published_anchors():
    anchors = det.retinanet_anchors(PUBLISHED_IMAGE)
    assert anchors.shape == (201_600, 4)
    return anchors


def _padded_gt(rng, n_valid):
    """Boxes inside the image, at least 16 pixels a side, padded to
    ``PUBLISHED_M`` with zero boxes (the benchmark's recipe for the
    detection cell)."""
    (h, w), m = PUBLISHED_IMAGE, PUBLISHED_M
    x1 = rng.uniform(0, w - 16, m)
    y1 = rng.uniform(0, h - 16, m)
    x2 = x1 + rng.uniform(16, np.maximum(w - x1, 16))
    y2 = y1 + rng.uniform(16, np.maximum(h - y1, 16))
    valid = np.arange(m) < n_valid
    boxes = np.stack([x1, y1, x2, y2], -1).astype(np.float32) * valid[:, None]
    labels = rng.integers(0, PUBLISHED_K, m, dtype=np.int32)
    return jnp.asarray(boxes), jnp.asarray(labels), jnp.asarray(valid)


def _plain_box_iou(a, b):
    lt = jnp.maximum(a[:, None, :2], b[None, :, :2])
    rb = jnp.minimum(a[:, None, 2:], b[None, :, 2:])
    wh = jnp.clip(rb - lt, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    area_a = jnp.clip(a[:, 2] - a[:, 0], 0) * jnp.clip(a[:, 3] - a[:, 1], 0)
    area_b = jnp.clip(b[:, 2] - b[:, 0], 0) * jnp.clip(b[:, 3] - b[:, 1], 0)
    union = area_a[:, None] + area_b[None, :] - inter
    return jnp.where(union > 0, inter / union, 0.0)


def _plain_match(anchors, gt_boxes, gt_valid, high=0.5, low=0.4):
    iou = jnp.where(gt_valid[None, :], _plain_box_iou(anchors, gt_boxes), -1.0)
    best_gt = jnp.argmax(iou, axis=1)
    best_iou = jnp.max(iou, axis=1)
    matched = jnp.where(
        best_iou >= high, best_gt, jnp.where(best_iou < low, -1, -2))
    gt_best_iou = jnp.max(iou, axis=0)
    is_best = ((iou >= gt_best_iou[None, :])
               & (gt_valid & (gt_best_iou > 0))[None, :])
    promote_to = gt_boxes.shape[0] - 1 - jnp.argmax(is_best[:, ::-1], axis=1)
    return jnp.where(jnp.any(is_best, axis=1), promote_to, matched)


def _plain_box_encode(boxes, anchors):
    aw = anchors[..., 2] - anchors[..., 0]
    ah = anchors[..., 3] - anchors[..., 1]
    ax = anchors[..., 0] + 0.5 * aw
    ay = anchors[..., 1] + 0.5 * ah
    bw = jnp.maximum(boxes[..., 2] - boxes[..., 0], 1e-6)
    bh = jnp.maximum(boxes[..., 3] - boxes[..., 1], 1e-6)
    bx = boxes[..., 0] + 0.5 * bw
    by = boxes[..., 1] + 0.5 * bh
    return jnp.stack(
        [(bx - ax) / aw, (by - ay) / ah, jnp.log(bw / aw), jnp.log(bh / ah)],
        axis=-1)


def _gather_losses(anchors, logits, deltas, boxes, labels, valid, k):
    """The plain oracle: the formulation ``RetinaNet.loss`` had before
    ``assign_targets``, two gathers with one index per anchor."""
    matched = _plain_match(anchors, boxes, valid)
    fg = matched >= 0
    ignore = matched == -2
    safe = jnp.clip(matched, 0)
    cls_t = jax.nn.one_hot(labels[safe], k) * fg[:, None]
    box_t = _plain_box_encode(boxes[safe], anchors)
    cls_loss = det.sigmoid_focal_loss(logits, cls_t)
    cls_loss = jnp.where(ignore[:, None], 0.0, cls_loss).sum()
    box_loss = det.smooth_l1(deltas, box_t).sum(-1)
    box_loss = jnp.where(fg, box_loss, 0.0).sum()
    n_fg = jnp.maximum(fg.sum(), 1)
    return cls_loss / n_fg, box_loss / n_fg, (cls_t, box_t, fg, ignore)


def _assigned_losses(anchors, logits, deltas, boxes, labels, valid, k):
    """What ``RetinaNet.loss`` does for one image."""
    cls_t, box_t, fg, ignore = det.assign_targets(
        anchors, boxes, labels, valid, k)
    cls_loss = det.sigmoid_focal_loss(logits, cls_t)
    cls_loss = jnp.where(ignore[:, None], 0.0, cls_loss).sum()
    box_loss = jnp.where(fg, det.smooth_l1(deltas.T, box_t), 0.0).sum()
    n_fg = jnp.maximum(fg.sum(), 1)
    return cls_loss / n_fg, box_loss / n_fg, (cls_t, box_t.T, fg, ignore)


def _losses_and_grads(losses):
    def both(anchors, logits, deltas, boxes, labels, valid):
        def total(logits, deltas):
            c, b, targets = losses(anchors, logits, deltas, boxes, labels,
                                   valid, PUBLISHED_K)
            return c + b, (c, b, targets)

        (_, aux), grads = jax.value_and_grad(
            total, argnums=(0, 1), has_aux=True)(logits, deltas)
        return aux, grads

    return jax.jit(both)


@pytest.mark.parametrize("n_valid,seed", [(0, 0), (1, 1), (7, 39), (20, 36)])
def test_assign_targets_equals_gather_formulation(published_anchors, n_valid,
                                                  seed):
    """At the published shape (201,600 anchors, 100 padded boxes, 80
    classes) the dense select gives the targets, losses and gradients of
    the gather formulation. Seeds 39 and 36 hold many-way ties for a box's
    best IoU (see the next test)."""
    rng = np.random.default_rng(seed)
    boxes, labels, valid = _padded_gt(rng, n_valid)
    a = published_anchors.shape[0]
    logits = jnp.asarray(rng.normal(-4, 1, (a, PUBLISHED_K)), jnp.float32)
    deltas = jnp.asarray(rng.normal(0, 0.5, (a, 4)), jnp.float32)
    args = (published_anchors, logits, deltas, boxes, labels, valid)
    (c0, b0, (cls0, box0, fg0, ig0)), (gl0, gd0) = _losses_and_grads(
        _gather_losses)(*args)
    (c1, b1, (cls1, box1, fg1, ig1)), (gl1, gd1) = _losses_and_grads(
        _assigned_losses)(*args)

    fg = np.asarray(fg0)
    assert int(fg.sum()) >= n_valid  # every valid box holds an anchor
    np.testing.assert_array_equal(np.asarray(fg1), fg)
    np.testing.assert_array_equal(np.asarray(ig1), np.asarray(ig0))
    np.testing.assert_array_equal(np.asarray(cls1), np.asarray(cls0))
    np.testing.assert_array_equal(np.asarray(box1)[fg], np.asarray(box0)[fg])
    assert np.isfinite(np.asarray(box1)).all()  # a zero box off the foreground
    # same arithmetic an element; the box loss sums its four coordinates
    # in another order
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c0))
    np.testing.assert_allclose(np.asarray(b1), np.asarray(b0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gl1), np.asarray(gl0),
                               rtol=1e-6, atol=0)
    np.testing.assert_allclose(np.asarray(gd1), np.asarray(gd0),
                               rtol=1e-6, atol=0)
    if n_valid == 0:
        assert not fg.any() and float(b1) == 0.0 and np.isfinite(float(c1))


def test_matcher_keeps_exact_ties_at_published_shape(published_anchors):
    """A pool in which dozens of anchors tie for one box's best IoU (one
    pool in three does): every tied anchor is promoted, as by the plain
    matcher. An IoU matrix laid out (M, A) rounds 51 of them apart on the
    CPU."""
    boxes, _, valid = _padded_gt(np.random.default_rng(39), 7)
    plain = jax.jit(_plain_match)(published_anchors, boxes, valid)
    ours, _ = jax.jit(det.match_anchors)(published_anchors, boxes, valid)
    np.testing.assert_array_equal(np.asarray(ours), np.asarray(plain))


def test_assign_targets_lowers_without_gather_or_scatter(published_anchors):
    boxes, labels, valid = _padded_gt(np.random.default_rng(3), 7)
    a = published_anchors.shape[0]
    logits = jnp.zeros((a, PUBLISHED_K))
    deltas = jnp.zeros((a, 4))
    programs = {
        "assign_targets": jax.jit(
            det.assign_targets, static_argnums=4).lower(
                published_anchors, boxes, labels, valid, PUBLISHED_K),
        "grad of the losses": _losses_and_grads(_assigned_losses).lower(
            published_anchors, logits, deltas, boxes, labels, valid),
    }
    for name, lowered in programs.items():
        text = lowered.as_text()
        assert "stablehlo." in text
        for op in ("gather", "scatter"):
            assert op not in text, (name, op)
    # the oracle is what the assertion would catch
    assert "gather" in _losses_and_grads(_gather_losses).lower(
        published_anchors, logits, deltas, boxes, labels, valid).as_text()


@pytest.mark.parametrize("case", ["padded_invalid_gt", "tie_highest_gt_wins"])
def test_assign_targets_matcher_regressions(case):
    """``test_matcher_promotion_with_padded_invalid_gt`` and
    ``test_matcher_tie_highest_gt_wins`` through ``assign_targets``: the
    promoted anchor carries its GT's class and box."""
    if case == "padded_invalid_gt":
        anchors = jnp.asarray([[0, 0, 10, 10], [50, 50, 60, 60]], jnp.float32)
        gt = jnp.asarray([[0, 0, 10, 22], [0, 0, 0, 0], [0, 0, 0, 0]],
                         jnp.float32)
        valid = jnp.asarray([True, False, False])
        labels = jnp.asarray([3, 1, 2], jnp.int32)
        want_gt, want_fg = 0, [True, False]
    else:
        anchors = jnp.asarray([[0, 0, 10, 10]], jnp.float32)
        gt = jnp.asarray([[0, 0, 10, 30], [0, 0, 30, 10]], jnp.float32)
        valid = jnp.asarray([True, True])
        labels = jnp.asarray([3, 1], jnp.int32)
        want_gt, want_fg = 1, [True]
    cls_t, box_t, fg, ignore = det.assign_targets(anchors, gt, labels, valid, 5)
    assert np.asarray(fg).tolist() == want_fg
    assert not np.asarray(ignore).any()
    want_cls = np.zeros((len(want_fg), 5), np.float32)
    want_cls[0, int(labels[want_gt])] = 1.0
    np.testing.assert_array_equal(np.asarray(cls_t), want_cls)
    np.testing.assert_array_equal(
        np.asarray(box_t)[:, 0],
        np.asarray(det.box_encode(gt[want_gt], anchors[0])))


def test_retinanet_loss_background_only_image():
    """No valid box in either image: every anchor is background, the
    losses are finite and the box loss is zero."""
    model = _small_retinanet()
    images = jnp.asarray(
        np.random.RandomState(0).randn(2, 64, 64, 3), jnp.float32)
    total, aux = model.loss(
        images, jnp.zeros((2, 4, 4), jnp.float32),
        jnp.zeros((2, 4), jnp.int32), jnp.zeros((2, 4), bool))
    assert np.isfinite(float(total)) and float(aux["cls_loss"]) > 0
    assert float(aux["box_loss"]) == 0.0


def test_retinanet_loss_and_its_gradient_lower_without_gather():
    """Through ``RetinaNet.loss`` itself: nothing in the model's forward
    and backward pass indexes per anchor."""
    model = _small_retinanet()
    graphdef, params, rest = nnx.split(model, nnx.Param, ...)

    def loss_fn(p, images, boxes, labels, valid):
        m = compat.nnx_merge(graphdef, p, rest, copy=True)
        return m.loss(images, boxes, labels, valid)[0]

    text = jax.jit(jax.value_and_grad(loss_fn)).lower(
        params, jnp.zeros((2, 64, 64, 3)), jnp.zeros((2, 4, 4)),
        jnp.zeros((2, 4), jnp.int32), jnp.zeros((2, 4), bool)).as_text()
    assert "stablehlo.convolution" in text
    assert "stablehlo.gather" not in text
    assert "stablehlo.scatter" not in text


def test_detection_dataset_pipeline_end_to_end():
    """Capability config 4 with the REAL data pipeline: detection dataset →
    sampler → loader → device_prefetch → SyncBN DP RetinaNet step."""
    from tpu_syncbn import data as tdata

    model = tnn.convert_sync_batchnorm(_small_retinanet())
    dp = parallel.DataParallel(
        model, optax.adam(1e-3),
        lambda m, b: m.loss(*b),
    )
    ds = tdata.SyntheticDetectionDataset(
        length=32, image_size=(64, 64), num_classes=5, max_boxes=4
    )
    sampler = tdata.DistributedSampler(len(ds), 1, 0, seed=0)
    loader = tdata.DataLoader(ds, batch_size=16, sampler=sampler,
                              num_workers=2, drop_last=True)
    for batch in tdata.device_prefetch(iter(loader), sharding=dp.batch_sharding):
        out = dp.train_step(batch)
    assert np.isfinite(float(out.loss))


def test_coco_dataset_format(tmp_path):
    import json as js

    ann = {
        "images": [{"id": 1, "file_name": "img1"}],
        "categories": [{"id": 7}, {"id": 3}],
        "annotations": [
            {"image_id": 1, "category_id": 7, "bbox": [10, 20, 30, 40]},
            {"image_id": 1, "category_id": 3, "bbox": [0, 0, 5, 5]},
        ],
    }
    (tmp_path / "ann.json").write_text(js.dumps(ann))
    np.save(tmp_path / "img1.npy", np.zeros((64, 64, 3), np.float32))

    from tpu_syncbn.data import CocoDetectionDataset

    ds = CocoDetectionDataset(str(tmp_path / "ann.json"), str(tmp_path),
                              max_boxes=4)
    assert ds.num_classes == 2
    img, boxes, labels, valid = ds[0]
    assert img.shape == (64, 64, 3)
    np.testing.assert_allclose(boxes[0], [10, 20, 40, 60])  # xywh→xyxy
    assert labels[0] == 1 and labels[1] == 0  # densified: id 7→1, id 3→0
    assert valid.tolist() == [True, True, False, False]


def test_nms_suppresses_overlaps():
    boxes = np.asarray([
        [0, 0, 10, 10],
        [1, 1, 11, 11],    # heavy overlap with 0, lower score -> suppressed
        [20, 20, 30, 30],  # disjoint -> kept
    ], np.float32)
    scores = np.asarray([0.9, 0.8, 0.7], np.float32)
    keep = det.nms(boxes, scores, iou_threshold=0.5)
    assert keep == [0, 2]


def test_batched_nms_keeps_cross_class_overlaps():
    boxes = np.asarray([[0, 0, 10, 10], [1, 1, 11, 11]], np.float32)
    scores = np.asarray([0.9, 0.8], np.float32)
    classes = np.asarray([0, 1])
    keep = det.batched_nms(boxes, scores, classes, iou_threshold=0.5)
    assert sorted(keep) == [0, 1]  # different classes: both survive
    keep_same = det.batched_nms(boxes, scores, np.asarray([0, 0]), 0.5)
    assert keep_same == [0]


def test_batched_nms_negative_coordinates():
    """Regression: negative coords must not leak across class regions."""
    boxes = np.asarray([[-40, 0, 10, 50], [-39, 1, 11, 51]], np.float32)
    scores = np.asarray([0.9, 0.8], np.float32)
    keep = det.batched_nms(boxes, scores, np.asarray([0, 1]), 0.5)
    assert sorted(keep) == [0, 1]  # different classes: both survive
