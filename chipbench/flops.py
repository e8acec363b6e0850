"""Closed-form operation counts from a configuration's shapes.

The numerator of ``mfu`` must not move with the code under test, so it
is not XLA's ``cost_analysis()`` (which counts every elementwise and
monitor op of whatever the program compiles to) but the multiply-adds
of the convolutions and matrix multiplications the architecture needs,
counted here from the sizes in the configuration file. Elementwise work
(BN, ReLU, pooling, the loss) is left out, as is usual for an MFU.

One multiply-add is two operations; a training step costs three forward
passes (forward, gradient with respect to the input, gradient with
respect to the weights). Nothing is recomputed in these programs, and a
recomputed operation would not count.
"""

from __future__ import annotations

TRAIN_PASSES = 3  # forward + two backward matmuls per forward matmul
OPS_PER_MAC = 2


def out_size(size: int, stride: int) -> int:
    """Output length of a SAME-padded (or torch k//2-padded) strided
    convolution or pool: ceil(size / stride)."""
    return -(-size // stride)


def conv_macs(h: int, w: int, k: int, cin: int, cout: int, stride: int = 1):
    """(multiply-adds, out_h, out_w) of one k x k convolution."""
    oh, ow = out_size(h, stride), out_size(w, stride)
    return oh * ow * k * k * cin * cout, oh, ow


def resnet_macs(cfg: dict, *, classifier: bool) -> tuple[int, list[tuple]]:
    """Forward multiply-adds per image of the bottleneck ResNet in
    ``cfg`` (``layers``, ``width``, ``image_size``), and the
    (h, w, channels) of C2..C5. ``classifier`` adds the final linear
    layer (``num_classes``)."""
    if cfg["block"] != "bottleneck":
        raise ValueError(f"no closed form for block {cfg['block']!r}")
    h, w = cfg["image_size"]
    width = cfg["width"]
    total, h, w = conv_macs(h, w, 7, 3, width, 2)       # stem 7x7/2
    h, w = out_size(h, 2), out_size(w, 2)                # max-pool 3x3/2
    cin = width
    feats = []
    for i, n_blocks in enumerate(cfg["layers"]):
        planes = width * 2 ** i
        for b in range(n_blocks):
            stride = 2 if (b == 0 and i > 0) else 1
            m1, _, _ = conv_macs(h, w, 1, cin, planes)             # 1x1
            m2, oh, ow = conv_macs(h, w, 3, planes, planes, stride)  # 3x3
            m3, _, _ = conv_macs(oh, ow, 1, planes, planes * 4)     # 1x1
            total += m1 + m2 + m3
            if stride != 1 or cin != planes * 4:                    # shortcut
                total += conv_macs(h, w, 1, cin, planes * 4, stride)[0]
            h, w, cin = oh, ow, planes * 4
        feats.append((h, w, cin))
    if classifier:
        total += cin * cfg["num_classes"]
    return total, feats


def fpn_macs(cfg: dict, feats: list[tuple]) -> tuple[int, list[tuple]]:
    """Forward multiply-adds per image of the FPN over C3..C5 with P6/P7
    (torchvision LastLevelP6P7 on C5), and the (h, w) of P3..P7."""
    c = cfg["fpn_channels"]
    total = 0
    levels = []
    for h, w, cin in feats[1:]:                 # C3, C4, C5
        total += conv_macs(h, w, 1, cin, c)[0]   # lateral
        total += conv_macs(h, w, 3, c, c)[0]     # output
        levels.append((h, w))
    h5, w5, c5 = feats[-1]
    m6, h6, w6 = conv_macs(h5, w5, 3, c5, c, 2)
    m7, h7, w7 = conv_macs(h6, w6, 3, c, c, 2)
    return total + m6 + m7, levels + [(h6, w6), (h7, w7)]


def head_macs(cfg: dict, levels: list[tuple]) -> int:
    """Forward multiply-adds per image of the two RetinaNet towers and
    their output convolutions, shared over the pyramid levels."""
    c, a = cfg["fpn_channels"], cfg["num_anchors"]
    per_location = (
        2 * cfg["tower_convs"] * 9 * c * c       # cls and box towers
        + 9 * c * a * cfg["num_classes"]         # cls_out
        + 9 * c * a * 4                          # box_out
    )
    return per_location * sum(h * w for h, w in levels)


def classifier_forward_macs(cfg: dict) -> int:
    return resnet_macs(cfg, classifier=True)[0]


def detector_forward_macs(cfg: dict) -> dict:
    backbone, feats = resnet_macs(cfg, classifier=False)
    fpn, levels = fpn_macs(cfg, feats)
    return {"backbone": backbone, "fpn": fpn, "head": head_macs(cfg, levels)}


def train_flops(forward_macs: int) -> int:
    """Operations of one training pass over what ``forward_macs`` covers."""
    return forward_macs * OPS_PER_MAC * TRAIN_PASSES
