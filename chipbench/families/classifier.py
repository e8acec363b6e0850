"""Image classifiers: a ResNet with SyncBN under cross-entropy."""

from __future__ import annotations


import functools

import numpy as np

from chipbench import flops, reference
from chipbench.families import _shared

optimizer = _shared.optimizer
transform = _shared.transform


def build_model(cfg: dict, key):
    return _shared.build_on_device(
        lambda rngs: _shared.backbone(cfg, cfg["num_classes"], rngs),
        key, cfg["sync_batchnorm"],
    )


def loss_fn(model, batch):
    import jax.numpy as jnp
    import optax

    x, y = batch
    logits = model(x).astype(jnp.float32)  # cross-entropy in f32
    return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()


def outputs(model, batch) -> dict:
    """What the program computes that the reference is compared on: the
    backbone's maps and, from them as ``ResNet.__call__`` does it, the
    logits."""
    maps = _shared.backbone_maps(model, batch[0])
    return maps | {"logits": model.fc(maps["c5"].mean(axis=(1, 2)))}


def make_pool(cfg: dict, n: int, rng: np.random.Generator) -> tuple:
    labels = rng.integers(0, cfg["num_classes"], n, dtype=np.int32)
    return _shared.pixels(rng, n, cfg), labels


def reference_fn(cfg: dict):
    return functools.partial(reference.classifier,
                             dtype=_shared.dtype_of(cfg["compute_dtype"]))


def stem_running_stats(rest: dict) -> dict:
    return _shared.stem_running_stats(rest)


def train_flops_per_image(cfg: dict) -> int:
    return flops.train_flops(flops.classifier_forward_macs(cfg))
