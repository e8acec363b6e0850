"""Plain references: the architectures' forward passes, straight
``jax.numpy`` / ``lax.conv_general_dilated``, reading the program's
parameters by path and sharing no code with ``tpu_syncbn/``.

Arithmetic is float32 at HIGHEST precision (on a TPU an f32 matmul
otherwise runs in bf16 passes) except where the configuration states a
lower compute type (``compute_dtype``, bfloat16 for the backbones):
there the operands of a convolution or matmul and the activations it
stores are rounded to that type, as the configuration says they are,
with products accumulated and BN statistics taken in float32. A
reference in pure float32 cannot hold such a program to anything tight:
at random initialization the 53 BN layers of ResNet-50 amplify bf16
rounding to 0.11 of the logits and 0.3-0.4 of per-location features
(measured on the chip, PERF.md), which would hide a dropped layer.
With ``compute_dtype`` float32 (the CPU tests) this is the pure float32
reference.

Even so the whole network cannot be held to anything tight from the
pixels on: a difference in the last bit of one early activation grows
through the BN layers to 0.07 of ResNet-50's logits and 0.2-0.3 of
RetinaNet's at two images (measured on the chip against this reference,
PR 24). So the comparison is made in pieces (``compared``): the
backbone's C2..C5 from the pixels, where C2 is still tight and the
deeper maps are a coarse check for a wrong or missing layer, and the
layers after the backbone *given the program's own backbone maps*,
which no BN layer amplifies and which are tight again.

Training-mode batch normalization: statistics of the batch, over
everything but channels, biased variance for normalizing. The caller
jits these over the *global* batch, so the stem statistics returned are
those of the global batch however the arrays are laid out over chips.

Departures from the published descriptions, each following the program
under test (``models/resnet.py``, ``models/retinanet.py``) so that the
comparison is of arithmetic and not of conventions:

* strided convolutions and the max-pool pad as XLA ``SAME`` (for an even
  input the one pixel of padding is at the end, torchvision's is at the
  start; shapes and operation counts are the same);
* the FPN's P6 is a stride-2 convolution on C5 (torchvision
  ``LastLevelP6P7`` with ``in_channels=2048``), P7 one on relu(P6);
* nearest-neighbour 2x upsampling cropped to the finer level's shape.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5
BN_MOMENTUM = 0.1


def conv(x, kernel, stride=1, bias=None, dtype=jnp.float32):
    """Operands rounded to ``dtype``, products accumulated in float32,
    the result stored in ``dtype``."""
    y = lax.conv_general_dilated(
        x.astype(dtype), kernel.astype(dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    if bias is not None:
        y = y + bias
    return y.astype(dtype)


def batch_norm(x, p):
    """Training-mode BN in float32, stored in x's type; returns (y,
    batch mean, biased batch variance)."""
    xf = x.astype(jnp.float32)
    mean = xf.mean(axis=(0, 1, 2))
    var = jnp.square(xf - mean).mean(axis=(0, 1, 2))
    y = (xf - mean) * lax.rsqrt(var + BN_EPS) * p["weight"] + p["bias"]
    return y.astype(x.dtype), mean, var


def max_pool_3x3_s2(x):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )


def bottleneck(p, x, stride):
    def cbn(name, bn, y, s=1):
        return batch_norm(conv(y, p[name]["kernel"], s, dtype=x.dtype),
                          p[bn])[0]

    out = jax.nn.relu(cbn("conv1", "bn1", x))
    out = jax.nn.relu(cbn("conv2", "bn2", out, stride))
    out = cbn("conv3", "bn3", out)
    if "down_conv" in p:
        x = cbn("down_conv", "down_bn", x, stride)
    return jax.nn.relu(out + x)


def resnet_features(p, x, dtype):
    """C2..C5 of a bottleneck ResNet v1.5 computed in ``dtype``, and the
    stem BN's batch statistics (mean, biased variance, element count per
    channel)."""
    y = conv(x, p["stem_conv"]["kernel"], 2, dtype=dtype)
    count = y.shape[0] * y.shape[1] * y.shape[2]
    y, mean, var = batch_norm(y, p["stem_bn"])
    y = max_pool_3x3_s2(jax.nn.relu(y))
    feats = []
    stages = p["stages"]
    for i in range(len(stages)):
        blocks = stages[i]
        for b in range(len(blocks)):
            y = bottleneck(blocks[b], y, 2 if (b == 0 and i > 0) else 1)
        feats.append(y)
    return feats, {"mean": mean, "var": var, "count": count}


def expected_running_stats(stem):
    """What a BN layer's running statistics hold after one step from
    their initial (0, 1): torch semantics, momentum 0.1, the running
    variance updated with the unbiased batch variance."""
    n = stem["count"]
    return {
        "running_mean": BN_MOMENTUM * stem["mean"],
        "running_var": (1 - BN_MOMENTUM)
        + BN_MOMENTUM * stem["var"] * (n / (n - 1)),
    }


def rel_l2(got, want):
    """|got - want| / |want| over all elements, in float32."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.square(got - want))
                    / jnp.maximum(jnp.sum(jnp.square(want)), 1e-30))


def compared(got: dict, feats: list, after: dict) -> dict:
    """Relative L2 error of each of the program's outputs ``got``: its
    C2..C5 against ``feats`` (from the pixels), the rest against
    ``after`` (from the program's own C3..C5)."""
    want = {f"c{i + 2}": f for i, f in enumerate(feats)} | after
    return {name: rel_l2(got[name], w) for name, w in want.items()}


def classifier_head(params, c5, dtype):
    pooled = c5.astype(jnp.float32).mean(axis=(1, 2)).astype(dtype)
    logits = jnp.dot(
        pooled, params["fc"]["kernel"].astype(dtype),
        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    ).astype(dtype) + params["fc"]["bias"].astype(dtype)
    return logits.astype(jnp.float32)


def classifier(params, batch, got, *, dtype=jnp.float32):
    """ResNet classifier on ``batch`` = (images f32 NHWC, integer
    labels): the errors of the program's outputs ``got`` (c2..c5,
    logits), the mean cross-entropy (in float32) and the stem
    statistics."""
    with jax.default_matmul_precision("highest"):
        x, labels = batch
        feats, stem = resnet_features(params, x, dtype)
        logp = jax.nn.log_softmax(classifier_head(params, feats[-1], dtype))
        loss = -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
        after = {"logits": classifier_head(params, got["c5"], dtype)}
    return {"errors": compared(got, feats, after), "loss": loss, "stem": stem}


def upsample2(x, target_hw):
    y = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
    return y[:, : target_hw[0], : target_hw[1], :]


def fpn(p, c3, c4, c5):
    def cv(q, x, stride=1):
        return conv(x, q["kernel"], stride, q["bias"])

    lat = [cv(p["lateral"][i], c) for i, c in enumerate((c3, c4, c5))]
    p5 = lat[2]
    p4 = lat[1] + upsample2(p5, lat[1].shape[1:3])
    p3 = lat[0] + upsample2(p4, lat[0].shape[1:3])
    outs = [cv(p["output"][i], x) for i, x in enumerate((p3, p4, p5))]
    p6 = cv(p["p6"], c5, 2)
    p7 = cv(p["p7"], jax.nn.relu(p6), 2)
    return outs + [p6, p7]


def retina_head(p, feats, num_classes):
    def tower(convs, x):
        for i in range(len(convs)):
            x = jax.nn.relu(conv(x, convs[i]["kernel"], 1, convs[i]["bias"]))
        return x

    cls_all, box_all = [], []
    for f in feats:
        n = f.shape[0]
        c = tower(p["cls_tower"], f)
        cls = conv(c, p["cls_out"]["kernel"], 1, p["cls_out"]["bias"])
        b = tower(p["box_tower"], f)
        box = conv(b, p["box_out"]["kernel"], 1, p["box_out"]["bias"])
        cls_all.append(cls.reshape(n, -1, num_classes))
        box_all.append(box.reshape(n, -1, 4))
    return jnp.concatenate(cls_all, 1), jnp.concatenate(box_all, 1)


def detector(params, batch, got, *, num_classes, dtype=jnp.float32):
    """RetinaNet-R50-FPN on ``batch`` = (images, boxes, labels, valid):
    the errors of the program's outputs ``got`` (c2..c5 of the backbone
    in ``dtype``; per-anchor class logits and box deltas from FPN and
    head in float32) and the stem statistics. The detection loss has no
    plain reference yet (PERF.md, Open questions)."""
    with jax.default_matmul_precision("highest"):
        feats, stem = resnet_features(params["backbone"], batch[0], dtype)
        pyramid = fpn(params["fpn"], *(got[c].astype(jnp.float32)
                                       for c in ("c3", "c4", "c5")))
        cls_logits, box_deltas = retina_head(params["head"], pyramid,
                                             num_classes)
        after = {"cls_logits": cls_logits, "box_deltas": box_deltas}
    return {"errors": compared(got, feats, after), "stem": stem}
