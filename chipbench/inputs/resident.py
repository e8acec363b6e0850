"""Input that bypasses the input layer: a few global batches made from
the seed, transformed once, placed on the devices with the trainer's
batch sharding and cycled. ``train_step`` donates its state, not its
batch, so the same device arrays serve every step.
"""

from __future__ import annotations

import itertools

import numpy as np


def make(family, cfg: dict, wl: dict, dp, seed_seq: np.random.SeedSequence):
    """Returns (iterator of device batches, close)."""
    import jax

    n = wl["input"]["batches"]
    size = wl["per_chip_batch"] * wl["chips"]
    pool = family.make_pool(cfg, n * size, np.random.default_rng(seed_seq))
    transform = family.transform(cfg)  # elementwise: whole batches at once
    placed = [
        jax.device_put(transform(tuple(a[i * size:(i + 1) * size]
                                       for a in pool)), dp.batch_sharding)
        for i in range(n)
    ]
    return itertools.cycle(placed), lambda: None
