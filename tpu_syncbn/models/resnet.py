"""ResNet family (nnx, NHWC) — the recipe's model side.

The reference's capability configs (BASELINE.json) name ResNet-18 (CIFAR-10)
and ResNet-50 (ImageNet) as the DP+SyncBN workloads; torchvision's resnet is
the de-facto architecture definition. This is a TPU-first reimplementation:
channel-last layout (lane dim = channels), ``nnx.Conv`` lowering to XLA
convolutions that tile onto the MXU, and a ``norm`` factory argument so
``convert_sync_batchnorm`` (or direct ``SyncBatchNorm`` construction) slots
in without touching the architecture.

``small_input=True`` selects the CIFAR stem (3×3/1 conv, no max-pool) used
by the ResNet-18/CIFAR-10 capability config; default is the ImageNet stem
(7×7/2 + 3×3/2 max-pool).

**Row strips at a small device batch.** The TPU's convolution layout tiles
batch x channels by 8 x 128, so under a batch of 8 the compiler converts
every convolution space-to-batch (a piece of the width folded into the
batch, a halo column padded on) and back, around each convolution of the
backward pass: at 2 images of 800x1344 a device 24.0 ms of an 82.5 ms
RetinaNet step were such ``copy`` and ``pad`` operations (PERF.md, PR 37; a
device batch under 8 is the case the reference's recipe is for: detection
at 2 images a chip). So ``ResNet.features`` folds it itself where the
batch N it is called with is under 8: a stage runs on G row strips of
each image laid along the batch axis, ``(N, H, W, C) -> (N*G, H/G, W, C)``,
a reshape that moves nothing. A 1x1 convolution, BN (its sums run over N,
H and W alike, so SyncBN's statistics are those of the same elements),
ReLU and the residual add do not see the fold; a 3x3 convolution sees it
in the one row above and below a strip, which the neighbouring strip
holds (``_conv_on_strips``). The maps ``features`` returns are unfolded.
``strip_count`` is the rule, from the static shape alone: the least G
with N*G >= 8 that divides the stage's input height H, with H/G even
where the stage's first block strides (so that "SAME" pads a strip as it
pads the image); no such G, or N >= 8: the stage runs on whole images, as
it always did (there every folded variant reads worse). Both block types
go through it. Scopes ``strips`` (the fold into a stage and the unfold
out of it: reshapes, which compile to nothing) and ``halo`` (the exchange,
``layer<i>/block<b>/halo``) say in the lowered step and in a trace where it
engaged and what it costs; every other path is what it is on whole images.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from flax import nnx

from tpu_syncbn import compat

from tpu_syncbn.nn import BatchNorm2d

# torch resnet uses Kaiming/He fan-out normal for convs
_conv_init = nnx.initializers.variance_scaling(2.0, "fan_out", "truncated_normal")


def _conv(cin, cout, kernel, stride, rngs, *, padding="SAME", dtype=None):
    return nnx.Conv(
        cin, cout, (kernel, kernel), strides=(stride, stride),
        padding=padding, use_bias=False, kernel_init=_conv_init,
        dtype=dtype, param_dtype=jnp.float32, rngs=rngs,
    )


# the TPU's convolution layout tiles batch x channels by 8 x 128: a
# batch under it is what the compiler converts space-to-batch
_MIN_CONV_BATCH = 8


def strip_count(n: int, h: int, strided: bool) -> int:
    """Row strips an image that a stage entered by ``n`` images of
    height ``h`` runs on (1: whole images); ``strided``: its first block
    halves the height. The module docstring has the rule and why."""
    if n >= _MIN_CONV_BATCH:
        return 1
    for g in range(-(-_MIN_CONV_BATCH // n), h + 1):
        if h % g == 0 and not (strided and (h // g) % 2):
            return g
    return 1


def _conv_on_strips(conv: nnx.Conv, x: jax.Array, strips: int) -> jax.Array:
    """``conv`` (3x3, "SAME", stride 1 or 2, no bias) of images that lie
    as ``strips`` row strips each, consecutive along the batch axis:
    ``conv(x)`` but for each strip's edge rows, which read the
    neighbouring strip's row where "SAME" would pad zeros. At stride 2
    (an even strip height) "SAME" pads (0, 1): the row below alone."""
    if strips == 1:
        return conv(x)
    with jax.named_scope("halo"):
        strip = (jnp.arange(x.shape[0]) % strips)[:, None, None, None]
        # the wrapped-around row of the roll is an image's edge: zeroed
        rows = [x, jnp.where(strip == strips - 1, 0,
                             jnp.roll(x[:, :1], -1, axis=0))]
        if conv.strides[0] == 1:
            rows.insert(0, jnp.where(strip == 0, 0,
                                     jnp.roll(x[:, -1:], 1, axis=0)))
        x = jnp.concatenate(rows, axis=1)
    x, kernel = conv.promote_dtype((x, conv.kernel[...]), dtype=conv.dtype)
    pad_w, = jax.lax.padtype_to_pads(
        x.shape[2:3], conv.kernel_size[1:], conv.strides[1:], conv.padding)
    return jax.lax.conv_general_dilated(
        x, kernel, conv.strides, [(0, 0), pad_w],
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=conv.precision,
    )


class BasicBlock(nnx.Module):
    expansion = 1

    def __init__(self, cin, planes, stride, norm, rngs, dtype=None):
        self.stride = stride
        self.conv1 = _conv(cin, planes, 3, stride, rngs, dtype=dtype)
        self.bn1 = norm(planes)
        self.conv2 = _conv(planes, planes, 3, 1, rngs, dtype=dtype)
        self.bn2 = norm(planes)
        if stride != 1 or cin != planes * self.expansion:
            self.down_conv = _conv(cin, planes * self.expansion, 1, stride, rngs, dtype=dtype)
            self.down_bn = norm(planes * self.expansion)
        else:
            self.down_conv = None
            self.down_bn = None

    def __call__(self, x, strips: int = 1):
        """``strips``: row strips an image that ``x`` lies as
        (``ResNet.features``); 1 is whole images."""
        identity = x
        out = nnx.relu(self.bn1(_conv_on_strips(self.conv1, x, strips)))
        out = self.bn2(_conv_on_strips(self.conv2, out, strips))
        if self.down_conv is not None:
            identity = self.down_bn(self.down_conv(x))
        return nnx.relu(out + identity)


class Bottleneck(nnx.Module):
    expansion = 4

    def __init__(self, cin, planes, stride, norm, rngs, dtype=None):
        self.stride = stride
        self.conv1 = _conv(cin, planes, 1, 1, rngs, dtype=dtype)
        self.bn1 = norm(planes)
        # torchvision places the stride on the 3x3 (resnet v1.5)
        self.conv2 = _conv(planes, planes, 3, stride, rngs, dtype=dtype)
        self.bn2 = norm(planes)
        self.conv3 = _conv(planes, planes * self.expansion, 1, 1, rngs, dtype=dtype)
        self.bn3 = norm(planes * self.expansion)
        if stride != 1 or cin != planes * self.expansion:
            self.down_conv = _conv(cin, planes * self.expansion, 1, stride, rngs, dtype=dtype)
            self.down_bn = norm(planes * self.expansion)
        else:
            self.down_conv = None
            self.down_bn = None

    def __call__(self, x, strips: int = 1):
        """``strips``: row strips an image that ``x`` lies as
        (``ResNet.features``); 1 is whole images."""
        identity = x
        out = nnx.relu(self.bn1(self.conv1(x)))
        out = nnx.relu(self.bn2(_conv_on_strips(self.conv2, out, strips)))
        out = self.bn3(self.conv3(out))
        if self.down_conv is not None:
            identity = self.down_bn(self.down_conv(x))
        return nnx.relu(out + identity)


def _run_stage(stage, x: jax.Array) -> jax.Array:
    """The stage's blocks on ``x``: on row strips folded into the batch
    where ``strip_count`` says so, the result unfolded. The scope
    ``strips`` holds the fold and the unfold alone, so that a block's
    path is ``layer<i>/block<b>/...`` folded or not."""
    n, h = x.shape[:2]
    strips = strip_count(n, h, stage[0].stride == 2)
    if strips > 1:
        with jax.named_scope("strips"):
            x = x.reshape(n * strips, h // strips, *x.shape[2:])
    for b, blk in enumerate(stage):
        with jax.named_scope(f"block{b}"):
            x = blk(x, strips)
    if strips > 1:
        with jax.named_scope("strips"):
            x = x.reshape(n, -1, *x.shape[2:])
    return x


class ResNet(nnx.Module):
    """Feature extractor + classifier head.

    ``norm`` is any ``Callable[[int], nnx.Module]`` — the extension point
    the SyncBN conversion relies on (default plain :class:`BatchNorm2d`;
    after ``convert_sync_batchnorm`` every instance is a SyncBatchNorm).
    """

    def __init__(
        self,
        block: type,
        layers: tuple[int, ...],
        *,
        num_classes: int = 1000,
        small_input: bool = False,
        norm: Callable[[int], nnx.Module] | None = None,
        width: int = 64,
        dtype: jnp.dtype | None = None,
        rngs: nnx.Rngs,
    ):
        """``dtype``: compute dtype for convs/matmuls (e.g. jnp.bfloat16
        for the TPU MXU fast path); params stay float32 and BN accumulates
        in float32 regardless."""
        norm = norm if norm is not None else BatchNorm2d
        self.small_input = small_input
        self.dtype = dtype
        if small_input:
            self.stem_conv = _conv(3, width, 3, 1, rngs, dtype=dtype)
        else:
            self.stem_conv = _conv(3, width, 7, 2, rngs, dtype=dtype)
        self.stem_bn = norm(width)

        cin = width
        stages = []
        for i, n_blocks in enumerate(layers):
            planes = width * (2**i)
            stride = 1 if i == 0 else 2
            blocks = []
            for b in range(n_blocks):
                blocks.append(
                    block(cin, planes, stride if b == 0 else 1, norm, rngs,
                          dtype=dtype)
                )
                cin = planes * block.expansion
            stages.append(compat.nnx_list(blocks))
        self.stages = compat.nnx_list(stages)
        self.fc = nnx.Linear(
            cin, num_classes,
            kernel_init=nnx.initializers.normal(0.01),
            dtype=dtype, param_dtype=jnp.float32, rngs=rngs,
        )
        self.feature_dim = cin

    def features(self, x: jax.Array) -> list[jax.Array]:
        """Per-stage feature maps (C2..C5) — consumed by FPN (RetinaNet)."""
        # named scopes: what the device trace's operations are called
        # (stem, layer1..4 / block<i>, fc), forward and backward alike
        with jax.named_scope("stem"):
            if self.dtype is not None:
                x = x.astype(self.dtype)
            x = nnx.relu(self.stem_bn(self.stem_conv(x)))
            if not self.small_input:
                x = nnx.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        feats = []
        for i, stage in enumerate(self.stages):
            with jax.named_scope(f"layer{i + 1}"):
                x = _run_stage(stage, x)
            feats.append(x)
        return feats

    def __call__(self, x: jax.Array) -> jax.Array:
        x = self.features(x)[-1]
        with jax.named_scope("fc"):
            x = x.mean(axis=(1, 2))  # global average pool
            return self.fc(x)


def resnet18(**kw) -> ResNet:
    return ResNet(BasicBlock, (2, 2, 2, 2), **kw)


def resnet34(**kw) -> ResNet:
    return ResNet(BasicBlock, (3, 4, 6, 3), **kw)


def resnet50(**kw) -> ResNet:
    return ResNet(Bottleneck, (3, 4, 6, 3), **kw)


def resnet101(**kw) -> ResNet:
    return ResNet(Bottleneck, (3, 4, 23, 3), **kw)


def resnet152(**kw) -> ResNet:
    return ResNet(Bottleneck, (3, 8, 36, 3), **kw)


RESNETS = {
    "resnet18": resnet18,
    "resnet34": resnet34,
    "resnet50": resnet50,
    "resnet101": resnet101,
    "resnet152": resnet152,
}
