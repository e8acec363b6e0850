"""Input through the program's own path: a seeded pool of decoded
samples -> TransformDataset -> DataLoader (threads) -> device_prefetch.

The pool is small (it has to be made in every run) and the epoch is the
source data set's length, indices wrapping around the pool: a user's
epoch is thousands of steps long, and an eight-step epoch would restart
the loader's worker threads every quarter of a second, which no user
sees. Each epoch is shuffled from the seed.
"""

from __future__ import annotations

import numpy as np


def make(family, cfg: dict, wl: dict, dp, seed_seq: np.random.SeedSequence):
    """Returns (iterator of device batches, close)."""
    from tpu_syncbn import data

    params = wl["input"]
    pool_seed, sampler_seed = seed_seq.spawn(2)
    pool = family.make_pool(cfg, params["pool_images"],
                            np.random.default_rng(pool_seed))

    class Pool(data.Dataset):
        """``epoch_images`` samples, sample i being pool[i mod n]."""

        def __len__(self):
            return cfg["epoch_images"]

        def __getitem__(self, i):
            j = i % params["pool_images"]
            return tuple(a[j] for a in pool)

    sampler = data.RandomSampler(
        cfg["epoch_images"], seed=int(sampler_seed.generate_state(1)[0] >> 2)
    )
    loader = data.DataLoader(
        data.TransformDataset(Pool(), family.transform(cfg)),
        wl["per_chip_batch"] * wl["chips"],
        sampler=sampler, num_workers=params["num_workers"], drop_last=True,
    )

    def epochs():
        epoch = 0
        while True:
            sampler.set_epoch(epoch)
            yield from loader
            epoch += 1

    host = epochs()
    batches = data.device_prefetch(host, sharding=dp.batch_sharding)

    def close():
        batches.close()
        host.close()  # ends the loader's generator, which stops its threads
        loader.close()

    return batches, close
