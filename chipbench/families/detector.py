"""One-stage detectors: RetinaNet-R50-FPN with SyncBN in the backbone."""

from __future__ import annotations

import functools

import numpy as np

from chipbench import flops, reference
from chipbench.families import _shared

optimizer = _shared.optimizer
transform = _shared.transform


def build_model(cfg: dict, key):
    from tpu_syncbn import models

    def make(rngs):
        return models.retinanet_r50_fpn(
            num_classes=cfg["num_classes"],
            image_size=tuple(cfg["image_size"]),
            fpn_channels=cfg["fpn_channels"],
            backbone=_shared.backbone(cfg, 1, rngs),
            rngs=rngs,
        )

    return _shared.build_on_device(make, key, cfg["sync_batchnorm"])


def loss_fn(model, batch):
    return model.loss(*batch)


def outputs(model, batch) -> dict:
    """The backbone's maps and, from them as ``RetinaNet.__call__`` does
    it, the per-anchor class logits and box deltas."""
    maps = _shared.backbone_maps(model.backbone, batch[0])
    cls_logits, box_deltas = model.head(
        model.fpn(maps["c3"], maps["c4"], maps["c5"]))
    return maps | {"cls_logits": cls_logits, "box_deltas": box_deltas}


def make_pool(cfg: dict, n: int, rng: np.random.Generator) -> tuple:
    """Decoded images with ground truth padded to ``max_boxes``: boxes
    (x1, y1, x2, y2) inside the image, at least 16 pixels a side."""
    h, w = cfg["image_size"]
    m = cfg["max_boxes"]
    lo, hi = cfg["valid_boxes"]
    side = min(16, h // 2, w // 2)
    x1 = rng.uniform(0, w - side, (n, m))
    y1 = rng.uniform(0, h - side, (n, m))
    x2 = x1 + rng.uniform(side, np.maximum(w - x1, side))
    y2 = y1 + rng.uniform(side, np.maximum(h - y1, side))
    boxes = np.stack([x1, y1, x2, y2], -1).astype(np.float32)
    labels = rng.integers(0, cfg["num_classes"], (n, m), dtype=np.int32)
    valid = np.arange(m)[None, :] < rng.integers(lo, hi + 1, (n, 1))
    boxes *= valid[..., None]
    return _shared.pixels(rng, n, cfg), boxes, labels, valid


def reference_fn(cfg: dict):
    return functools.partial(reference.detector,
                             num_classes=cfg["num_classes"],
                             dtype=_shared.dtype_of(cfg["compute_dtype"]))


def stem_running_stats(rest: dict) -> dict:
    return _shared.stem_running_stats(rest["backbone"])


def train_flops_per_image(cfg: dict) -> int:
    return flops.train_flops(sum(flops.detector_forward_macs(cfg).values()))
