"""What the families share: dtypes, the backbone, the optimizer, pixels."""

from __future__ import annotations

import numpy as np


def dtype_of(name: str):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def backbone(cfg: dict, num_classes: int, rngs):
    from tpu_syncbn.models.resnet import Bottleneck, ResNet

    if cfg["block"] != "bottleneck":
        raise ValueError(f"unknown block {cfg['block']!r}")
    return ResNet(
        Bottleneck, tuple(cfg["layers"]), num_classes=num_classes,
        width=cfg["width"], dtype=dtype_of(cfg["compute_dtype"]), rngs=rngs,
    )


def build_on_device(make, key, sync: bool):
    """The model made on the device in one jitted call from the seed's
    key (eagerly, every initializer is a program of its own: 19 s on the
    chip for ResNet-50), then converted. The key is an argument of that
    program, so every seed runs the same cached program."""
    from flax import nnx

    from tpu_syncbn import nn

    model = nnx.jit(lambda k: make(nnx.Rngs(k)))(key)
    return nn.convert_sync_batchnorm(model) if sync else model


def optimizer(cfg: dict, global_batch: int):
    """The configuration's optimizer, its learning rate scaled linearly
    from the batch the recipe states it for to this run's global batch."""
    import optax

    opt = cfg["optimizer"]
    if opt["name"] != "sgd":
        raise ValueError(f"unknown optimizer {opt['name']!r}")
    lr = opt["lr"] * global_batch / opt["lr_batch"]
    parts = []
    if opt.get("clip_global_norm"):
        parts.append(optax.clip_by_global_norm(opt["clip_global_norm"]))
    if opt["weight_decay"]:
        parts.append(optax.add_decayed_weights(opt["weight_decay"]))
    parts.append(optax.sgd(lr, momentum=opt["momentum"]))
    return optax.chain(*parts) if len(parts) > 1 else parts[0]


def pixels(rng: np.random.Generator, n: int, cfg: dict) -> np.ndarray:
    """``n`` decoded uint8 images at the configuration's shape."""
    h, w = cfg["image_size"]
    return rng.integers(0, 256, size=(n, h, w, 3), dtype=np.uint8)


def transform(cfg: dict):
    """The program's own transforms on the image of a sample (uint8 ->
    float32 in [0, 1] -> (x - mean) / std); its targets pass through."""
    from tpu_syncbn.data import transforms as T

    norm = T.Compose([T.ToFloat(), T.Normalize(cfg["pixel_mean"],
                                               cfg["pixel_std"])])
    return lambda sample: (norm(sample[0]),) + tuple(sample[1:])


def backbone_maps(resnet, images) -> dict:
    return {f"c{i + 2}": f for i, f in enumerate(resnet.features(images))}


def stem_running_stats(rest: dict) -> dict:
    bn = rest["stem_bn"]
    return {"running_mean": bn["running_mean"],
            "running_var": bn["running_var"]}
