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


class BasicBlock(nnx.Module):
    expansion = 1

    def __init__(self, cin, planes, stride, norm, rngs, dtype=None):
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

    def __call__(self, x):
        identity = x
        out = nnx.relu(self.bn1(self.conv1(x)))
        out = self.bn2(self.conv2(out))
        if self.down_conv is not None:
            identity = self.down_bn(self.down_conv(x))
        return nnx.relu(out + identity)


class Bottleneck(nnx.Module):
    expansion = 4

    def __init__(self, cin, planes, stride, norm, rngs, dtype=None):
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

    def __call__(self, x):
        identity = x
        out = nnx.relu(self.bn1(self.conv1(x)))
        out = nnx.relu(self.bn2(self.conv2(out)))
        out = self.bn3(self.conv3(out))
        if self.down_conv is not None:
            identity = self.down_bn(self.down_conv(x))
        return nnx.relu(out + identity)


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
                for b, blk in enumerate(stage):
                    with jax.named_scope(f"block{b}"):
                        x = blk(x)
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
