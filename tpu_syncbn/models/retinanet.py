"""RetinaNet-R50-FPN (nnx, NHWC) — the small-per-chip-batch SyncBN
capability config (BASELINE.json: "RetinaNet-R50-FPN COCO, per-chip
batch=2"; the case the reference's recipe exists for, ``README.md:3``).

TPU-first choices: NHWC everywhere, static anchor tensors baked at
construction for a fixed image size (XLA static shapes), padded ground
truth with validity masks, nearest-neighbor top-down upsampling via
reshape-broadcast (cheap on VPU), and BN only in the backbone (heads use
plain convs like torchvision's retinanet_resnet50_fpn) so
``convert_sync_batchnorm`` syncs exactly the backbone stats.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from flax import nnx

from tpu_syncbn import compat

from tpu_syncbn.models import detection as det
from tpu_syncbn.models.resnet import ResNet, Bottleneck, _conv_init


def _conv3(cin, cout, rngs, *, bias_init=None):
    return nnx.Conv(
        cin, cout, (3, 3), padding="SAME", kernel_init=_conv_init,
        bias_init=bias_init or nnx.initializers.zeros_init(), rngs=rngs,
    )


def _upsample2(x: jax.Array, target_hw: tuple[int, int]) -> jax.Array:
    """Nearest-neighbor 2× upsample then crop to target (handles odd sizes)."""
    n, h, w, c = x.shape
    y = jnp.broadcast_to(x[:, :, None, :, None, :], (n, h, 2, w, 2, c))
    y = y.reshape(n, h * 2, w * 2, c)
    th, tw = target_hw
    return y[:, :th, :tw, :]


class FPN(nnx.Module):
    """Feature Pyramid Network over C3-C5 with P6/P7 extras
    (RetinaNet flavor: P6 = conv stride 2 on C5, P7 = conv stride 2 on
    relu(P6) — torchvision LastLevelP6P7)."""

    def __init__(self, in_channels: tuple[int, int, int], out_channels: int, rngs):
        self.lateral = compat.nnx_list([
            nnx.Conv(c, out_channels, (1, 1), kernel_init=_conv_init, rngs=rngs)
            for c in in_channels
        ])
        self.output = compat.nnx_list([
            _conv3(out_channels, out_channels, rngs) for _ in in_channels
        ])
        self.p6 = nnx.Conv(
            in_channels[-1], out_channels, (3, 3), strides=(2, 2),
            padding="SAME", kernel_init=_conv_init, rngs=rngs,
        )
        self.p7 = nnx.Conv(
            out_channels, out_channels, (3, 3), strides=(2, 2),
            padding="SAME", kernel_init=_conv_init, rngs=rngs,
        )

    def __call__(self, c3, c4, c5):
        laterals = [lat(c) for lat, c in zip(self.lateral, (c3, c4, c5))]
        # top-down pathway
        p5 = laterals[2]
        p4 = laterals[1] + _upsample2(p5, laterals[1].shape[1:3])
        p3 = laterals[0] + _upsample2(p4, laterals[0].shape[1:3])
        p3, p4, p5 = (out(p) for out, p in zip(self.output, (p3, p4, p5)))
        p6 = self.p6(c5)
        p7 = self.p7(nnx.relu(p6))
        return [p3, p4, p5, p6, p7]


class RetinaHead(nnx.Module):
    """Shared classification/regression subnets (4 conv256 + output)."""

    def __init__(self, channels: int, num_anchors: int, num_classes: int, rngs):
        self.cls_tower = compat.nnx_list(
            [_conv3(channels, channels, rngs) for _ in range(4)]
        )
        self.box_tower = compat.nnx_list(
            [_conv3(channels, channels, rngs) for _ in range(4)]
        )
        # focal-loss prior: bias so initial P(fg) ≈ 0.01 (RetinaNet paper)
        prior = 0.01
        bias_value = -math.log((1 - prior) / prior)
        self.cls_out = _conv3(
            channels, num_anchors * num_classes, rngs,
            bias_init=nnx.initializers.constant(bias_value),
        )
        self.box_out = _conv3(channels, num_anchors * 4, rngs)
        self.num_classes = num_classes
        self.num_anchors = num_anchors

    def __call__(self, feats):
        cls_all, box_all = [], []
        for f in feats:
            with jax.named_scope("head_cls"):
                c = f
                for conv in self.cls_tower:
                    c = nnx.relu(conv(c))
                cls = self.cls_out(c)
            with jax.named_scope("head_box"):
                b = f
                for conv in self.box_tower:
                    b = nnx.relu(conv(b))
                box = self.box_out(b)
            n = f.shape[0]
            cls_all.append(cls.reshape(n, -1, self.num_classes))
            box_all.append(box.reshape(n, -1, 4))
        return jnp.concatenate(cls_all, 1), jnp.concatenate(box_all, 1)


class RetinaNet(nnx.Module):
    """RetinaNet with a ResNet-50-FPN backbone.

    ``__call__(images)`` → (cls_logits (B, A, K), box_deltas (B, A, 4)).
    ``loss(images, gt_boxes, gt_labels, gt_valid)`` → (total, aux dict),
    with GT padded to a fixed ``max_boxes`` and masked by ``gt_valid`` —
    static shapes end to end.
    """

    def __init__(
        self,
        *,
        num_classes: int = 80,
        image_size: tuple[int, int] = (512, 512),
        fpn_channels: int = 256,
        backbone: ResNet | None = None,
        rngs: nnx.Rngs,
    ):
        if backbone is None:
            backbone = ResNet(
                Bottleneck, (3, 4, 6, 3), num_classes=1, rngs=rngs
            )
        self.backbone = backbone
        dims = (
            backbone.feature_dim // 4,   # C3
            backbone.feature_dim // 2,   # C4
            backbone.feature_dim,        # C5
        )
        self.fpn = FPN(dims, fpn_channels, rngs)
        self.head = RetinaHead(fpn_channels, num_anchors=9,
                               num_classes=num_classes, rngs=rngs)
        self.num_classes = num_classes
        self.image_size = image_size
        # static anchors for the configured image size (A, 4)
        self.anchors = nnx.Variable(det.retinanet_anchors(image_size))

    def __call__(self, images: jax.Array):
        feats = self.backbone.features(images)  # C2..C5
        with jax.named_scope("fpn"):
            p = self.fpn(feats[1], feats[2], feats[3])
        return self.head(p)

    def loss(self, images, gt_boxes, gt_labels, gt_valid):
        """Focal classification + smooth-L1 box loss, normalized by the
        number of foreground anchors (RetinaNet convention)."""
        cls_logits, box_deltas = self(images)
        anchors = self.anchors[...]

        def one_image(logits, deltas, boxes, labels, valid):
            cls_t, box_t, fg, ignore = det.assign_targets(
                anchors, boxes, labels, valid, self.num_classes)
            with jax.named_scope("focal"):
                cls_loss = det.sigmoid_focal_loss(logits, cls_t)
                cls_loss = jnp.where(ignore[:, None], 0.0, cls_loss).sum()
            with jax.named_scope("smooth_l1"):
                # anchors on the minor axis, as the (4, A) targets are
                box_loss = det.smooth_l1(deltas.T, box_t)
                box_loss = jnp.where(fg, box_loss, 0.0).sum()
            n_fg = jnp.maximum(fg.sum(), 1)
            return cls_loss / n_fg, box_loss / n_fg

        cls_l, box_l = jax.vmap(one_image)(
            cls_logits, box_deltas, gt_boxes, gt_labels, gt_valid
        )
        total = cls_l.mean() + box_l.mean()
        return total, {"cls_loss": cls_l.mean(), "box_loss": box_l.mean()}

    def decode(self, images, *, score_thresh=0.05, top_k=100):
        """Inference: decode top-k scoring boxes per image (static top-k;
        full NMS is a post-process on host for eval)."""
        cls_logits, box_deltas = self(images)
        anchors = self.anchors[...]
        scores = jax.nn.sigmoid(cls_logits)  # (B, A, K)
        best_score = scores.max(-1)
        best_class = scores.argmax(-1)
        k = min(top_k, best_score.shape[1])
        top_scores, top_idx = jax.lax.top_k(best_score, k)
        boxes = det.box_decode(
            jnp.take_along_axis(box_deltas, top_idx[..., None], axis=1),
            anchors[top_idx],
        )
        classes = jnp.take_along_axis(best_class, top_idx, axis=1)
        keep = top_scores >= score_thresh
        return boxes, top_scores, classes, keep


def retinanet_r50_fpn(**kw) -> RetinaNet:
    return RetinaNet(**kw)
