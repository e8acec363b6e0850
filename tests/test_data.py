"""Data pipeline tests: sampler parity with torch.utils.data.DistributedSampler
(the reference's C13, [torch] utils/data/distributed.py), loader ordering and
worker determinism, device prefetch."""

import collections

import numpy as np
import pytest
import torch
from torch.utils.data import DistributedSampler as TorchDistributedSampler

from tpu_syncbn import data as tdata
from tpu_syncbn.data.loader import _build_batch
from tpu_syncbn.obs import tracing


class _TorchSized(torch.utils.data.Dataset):
    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return i


@pytest.mark.parametrize("length,world,drop_last", [
    (100, 4, False),
    (100, 4, True),
    (101, 4, False),   # padding wraparound
    (101, 4, True),    # truncation
    (7, 8, False),     # world > length: heavy padding
    (8, 8, False),
])
def test_sampler_structure_matches_torch_noshuffle(length, world, drop_last):
    """With shuffle=False the index sequence must be IDENTICAL to torch's
    ([torch] utils/data/distributed.py:113-134 arithmetic)."""
    for rank in range(world):
        ours = list(
            tdata.DistributedSampler(
                length, world, rank, shuffle=False, drop_last=drop_last
            )
        )
        theirs = list(
            TorchDistributedSampler(
                _TorchSized(length), world, rank, shuffle=False, drop_last=drop_last
            )
        )
        assert ours == theirs, (length, world, rank, drop_last)


@pytest.mark.parametrize("length,world,drop_last", [(37, 4, False), (37, 4, True)])
def test_sampler_shuffle_partition_properties(length, world, drop_last):
    """Shuffled shards must cover the dataset with the same cardinalities
    and multiplicity structure as the reference (permutation itself is a
    different RNG, by design)."""
    samplers = [
        tdata.DistributedSampler(length, world, r, shuffle=True, seed=5,
                                 drop_last=drop_last)
        for r in range(world)
    ]
    shards = [list(s) for s in samplers]
    per = length // world if drop_last else -(-length // world)
    assert all(len(sh) == per for sh in shards)
    union = sorted(i for sh in shards for i in sh)
    if drop_last:
        # truncated: a subset of indices, each at most once
        assert len(union) == per * world == len(set(union))
    else:
        # padded: every index present; duplicates only from wraparound
        assert set(union) == set(range(length))
        assert len(union) == per * world


def test_sampler_epoch_reshuffles_and_is_deterministic():
    s = tdata.DistributedSampler(50, 2, 0, shuffle=True, seed=3)
    e0 = list(s)
    s.set_epoch(1)
    e1 = list(s)
    s.set_epoch(0)
    again = list(s)
    assert e0 != e1 and e0 == again


def test_sampler_rank_validation():
    with pytest.raises(ValueError):
        tdata.DistributedSampler(10, 2, 5)


def test_loader_sequential_and_drop_last():
    ds = tdata.ArrayDataset(np.arange(10), np.arange(10) * 2)
    dl = tdata.DataLoader(ds, batch_size=4, drop_last=True)
    batches = list(dl)
    assert len(batches) == len(dl) == 2
    np.testing.assert_array_equal(batches[0][0], [0, 1, 2, 3])
    np.testing.assert_array_equal(batches[1][1], [8, 10, 12, 14])
    dl2 = tdata.DataLoader(ds, batch_size=4, drop_last=False)
    assert len(list(dl2)) == len(dl2) == 3


@pytest.mark.parametrize("workers", [1, 3, 8])
def test_threaded_loader_matches_sequential(workers):
    ds = tdata.SyntheticImageDataset(length=50, shape=(8, 8, 3))
    ref = [b for b in tdata.DataLoader(ds, batch_size=8, num_workers=0)]
    got = [b for b in tdata.DataLoader(ds, batch_size=8, num_workers=workers)]
    assert len(ref) == len(got)
    for (rx, ry), (gx, gy) in zip(ref, got):
        np.testing.assert_array_equal(rx, gx)
        np.testing.assert_array_equal(ry, gy)


def test_threaded_loader_propagates_sampler_errors():
    """A sampler raising mid-iteration must surface at the consumer, not
    hang the loop (dispatcher-thread failure path)."""

    class BadSampler(tdata.Sampler):
        def __len__(self):
            return 8

        def __iter__(self):
            yield 0
            yield 1
            raise RuntimeError("sampler exploded")

    ds = tdata.ArrayDataset(np.arange(8))
    dl = tdata.DataLoader(ds, batch_size=1, sampler=BadSampler(), num_workers=2)
    with pytest.raises(RuntimeError, match="sampler exploded"):
        list(dl)


def test_threaded_loader_propagates_worker_errors():
    class Bad(tdata.Dataset):
        def __len__(self):
            return 8

        def __getitem__(self, i):
            if i == 5:
                raise RuntimeError("decode failed")
            return np.zeros(2)

    dl = tdata.DataLoader(Bad(), batch_size=2, num_workers=2)
    with pytest.raises(RuntimeError, match="decode failed"):
        list(dl)


def test_loader_early_exit_leaks_no_threads():
    """Abandoning a threaded iteration mid-epoch must not leak dispatcher
    or worker threads (early stopping / partial validation pattern)."""
    import threading
    import time

    ds = tdata.SyntheticImageDataset(length=200, shape=(8, 8, 3))
    before = threading.active_count()
    for _ in range(5):
        it = iter(tdata.DataLoader(ds, batch_size=4, num_workers=4))
        next(it)
        it.close()
    time.sleep(0.5)  # let stopped threads unwind
    assert threading.active_count() <= before + 1


def test_collate_namedtuple():
    import collections

    Pt = collections.namedtuple("Pt", "x y")
    out = tdata.default_collate([Pt(np.ones(2), 1), Pt(np.zeros(2), 2)])
    assert isinstance(out, Pt)
    assert out.x.shape == (2, 2)
    np.testing.assert_array_equal(out.y, [1, 2])


def test_collate_structures():
    samples = [{"a": np.ones(2), "b": (1, np.zeros(3))} for _ in range(4)]
    out = tdata.default_collate(samples)
    assert out["a"].shape == (4, 2)
    assert out["b"][0].shape == (4,)
    assert out["b"][1].shape == (4, 3)


def test_device_prefetch_round_trip():
    import jax

    ds = tdata.ArrayDataset(np.arange(12, dtype=np.float32))
    dl = tdata.DataLoader(ds, batch_size=4)
    out = list(tdata.device_prefetch(iter(dl), size=2))
    assert len(out) == 3
    assert all(isinstance(b, jax.Array) for b in out)
    np.testing.assert_array_equal(np.asarray(out[2]), [8, 9, 10, 11])


def test_device_prefetch_sharded():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_syncbn import runtime

    mesh = runtime.data_parallel_mesh()
    sharding = NamedSharding(mesh, P("data"))
    ds = tdata.ArrayDataset(np.arange(32, dtype=np.float32).reshape(16, 2))
    dl = tdata.DataLoader(ds, batch_size=8)
    out = list(tdata.device_prefetch(iter(dl), sharding=sharding))
    assert len(out) == 2
    assert out[0].sharding.is_equivalent_to(sharding, 2)


def test_device_prefetch_scan_steps_stacks_chunks():
    """scan_steps=K stages K-stacked chunks with the leading scan axis
    unsharded and the per-step batch axis on the mesh — the layout
    DataParallel.train_steps_batches scans over (docs/PERFORMANCE.md)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_syncbn import runtime

    mesh = runtime.data_parallel_mesh()
    sharding = NamedSharding(mesh, P("data"))
    ds = tdata.ArrayDataset(np.arange(64, dtype=np.float32).reshape(32, 2))
    dl = tdata.DataLoader(ds, batch_size=8)  # 4 batches
    out = list(tdata.device_prefetch(iter(dl), sharding=sharding,
                                     scan_steps=2))
    assert len(out) == 2
    assert all(b.shape == (2, 8, 2) for b in out)
    expect = NamedSharding(mesh, P(None, "data"))
    assert out[0].sharding.is_equivalent_to(expect, 3)
    np.testing.assert_array_equal(
        np.asarray(out[0]).reshape(16, 2), np.arange(32).reshape(16, 2)
    )


def test_device_prefetch_terminal_partial_chunk():
    """Terminal StopIteration with a non-full staging queue: the final
    chunk carries the remainder (leading axis < K) instead of dropping
    it or hanging."""
    ds = tdata.ArrayDataset(np.arange(20, dtype=np.float32).reshape(5, 4))
    dl = tdata.DataLoader(ds, batch_size=1)  # 5 batches, K=2 -> 2+2+1
    out = list(tdata.device_prefetch(iter(dl), scan_steps=2))
    assert [b.shape[0] for b in out] == [2, 2, 1]
    np.testing.assert_array_equal(np.asarray(out[2][0, 0]), [16, 17, 18, 19])
    # empty source: plain StopIteration, no empty chunk
    assert list(tdata.device_prefetch(iter([]), scan_steps=2)) == []


def test_device_prefetch_scan_rejects_non_named_sharding():
    """scan_steps>1 derives the K-stacked layout from the sharding's
    mesh+spec — only a NamedSharding has them, so anything else must
    fail loudly up front, not AttributeError mid-stream."""
    import jax
    from jax.sharding import SingleDeviceSharding

    sh = SingleDeviceSharding(jax.devices()[0])
    with pytest.raises(TypeError, match="NamedSharding"):
        list(tdata.device_prefetch(iter([np.zeros(4, np.float32)]),
                                   sharding=sh, scan_steps=2))


def test_device_prefetch_scan_rejects_dtype_drift():
    """A later batch whose leaves change dtype must error, not be
    silently cast into the first batch's slots (the scan_steps=1 path
    preserves per-batch dtypes — parity demands loudness here)."""
    batches = [np.zeros(4, np.float32), np.zeros(4, np.float64)]
    with pytest.raises(ValueError, match="dtypes"):
        list(tdata.device_prefetch(iter(batches), scan_steps=2))


def test_device_prefetch_staging_copies_host_buffers():
    """Donation-safe ownership, host half: the staging stack must COPY —
    a source iterator recycling one buffer in place (the native staging
    ring's pattern) must not retroactively mutate a staged chunk."""
    buf = np.zeros(4, np.float32)

    def recycling():
        for i in range(4):
            buf[:] = i  # reuse the same backing storage every batch
            yield buf

    out = list(tdata.device_prefetch(recycling(), scan_steps=2, size=1))
    np.testing.assert_array_equal(np.asarray(out[0])[:, 0], [0, 1])
    np.testing.assert_array_equal(np.asarray(out[1])[:, 0], [2, 3])


def test_device_prefetch_staged_chunk_survives_donated_steps():
    """Donation-safe ownership, device half: a staged chunk fed to a
    donate=True trainer must not alias live training state — the
    trainer never donates batches, so the SAME chunk must be re-usable
    and produce the same first-step loss from the same starting state."""
    import jax
    import optax
    from flax import nnx

    from tpu_syncbn import nn as tnn, parallel

    class Net(nnx.Module):
        def __init__(self, rngs):
            self.fc = nnx.Linear(2, 2, rngs=rngs)
            self.bn = tnn.BatchNorm1d(2)

        def __call__(self, x):
            return self.bn(self.fc(x))

    def build():
        return parallel.DataParallel(
            tnn.convert_sync_batchnorm(Net(nnx.Rngs(0))),
            optax.sgd(0.1), lambda m, b: (m(b) ** 2).mean(), donate=True,
        )

    rng = np.random.RandomState(0)
    batches = [rng.randn(16, 2).astype(np.float32) for _ in range(2)]
    dp = build()
    chunks = list(tdata.device_prefetch(
        iter(batches), sharding=dp.batch_sharding, scan_steps=2
    ))
    first = dp.train_steps_batches(chunks[0])
    loss_a = np.asarray(first.loss)
    # chunk buffer still alive after donated state transitions…
    np.testing.assert_array_equal(
        np.asarray(chunks[0]).reshape(32, 2), np.stack(batches).reshape(32, 2)
    )
    # …and a fresh trainer over the SAME chunk reproduces the run — a
    # donated-then-reused staging buffer aliasing state would diverge
    loss_b = np.asarray(build().train_steps_batches(chunks[0]).loss)
    np.testing.assert_allclose(loss_a, loss_b, rtol=1e-6)


def test_distributed_end_to_end_cover():
    """2-replica loaders with the distributed sampler cover the dataset
    exactly (drop_last both levels) — the recipe's step-5 wiring
    (README.md:74-92)."""
    ds = tdata.ArrayDataset(np.arange(64))
    seen = []
    for rank in range(2):
        sampler = tdata.DistributedSampler(len(ds), 2, rank, shuffle=True, seed=1)
        dl = tdata.DataLoader(ds, batch_size=8, sampler=sampler, drop_last=True)
        for batch in dl:
            seen.extend(batch.tolist())
    assert sorted(seen) == list(range(64))


def test_synthetic_dataset_deterministic():
    ds = tdata.SyntheticImageDataset(length=4, seed=9)
    x1, y1 = ds[2]
    x2, y2 = ds[2]
    np.testing.assert_array_equal(x1, x2)
    assert y1 == y2
    with pytest.raises(IndexError):
        ds[4]


def test_staged_iter_roundtrip():
    """C++ staging-ring loader path yields bit-identical batches in order."""
    from tpu_syncbn.runtime import native

    if not native.available():
        pytest.skip("native library unavailable")
    ds = tdata.SyntheticImageDataset(length=24, shape=(8, 8, 3))
    ref = list(tdata.DataLoader(ds, batch_size=4))
    got = list(tdata.staged_iter(iter(tdata.DataLoader(ds, batch_size=4))))
    assert len(ref) == len(got)
    for (rx, ry), (gx, gy) in zip(ref, got):
        np.testing.assert_array_equal(rx, gx)
        np.testing.assert_array_equal(ry, gy)


def test_staged_iter_oversized_batch_bypasses():
    from tpu_syncbn.runtime import native

    if not native.available():
        pytest.skip("native library unavailable")
    ds = tdata.ArrayDataset(np.zeros((4, 256, 256, 3), np.float32))
    out = list(tdata.staged_iter(iter(tdata.DataLoader(ds, batch_size=4)),
                                 slot_mb=1))  # 3 MB batch > 1 MB slot
    assert len(out) == 1 and out[0].shape == (4, 256, 256, 3)


def test_staged_iter_early_exit_and_error_propagation():
    from tpu_syncbn.runtime import native

    if not native.available():
        pytest.skip("native library unavailable")
    import threading
    import time

    # early exit must not crash or leak (producer joined, ring freed)
    ds = tdata.SyntheticImageDataset(length=64, shape=(8, 8, 3))
    before = threading.active_count()
    for _ in range(3):
        it = tdata.staged_iter(iter(tdata.DataLoader(ds, batch_size=4)),
                               slots=2)
        next(it)
        it.close()
    time.sleep(0.3)
    assert threading.active_count() <= before + 1

    # producer-side errors surface at the consumer
    class Bad(tdata.Dataset):
        def __len__(self):
            return 8

        def __getitem__(self, i):
            if i == 5:
                raise RuntimeError("staging decode failed")
            return np.zeros(4, np.float32)

    with pytest.raises(RuntimeError, match="staging decode failed"):
        list(tdata.staged_iter(iter(tdata.DataLoader(Bad(), batch_size=2))))

    # yielded arrays are writable (like every other loader path)
    out = next(tdata.staged_iter(iter(tdata.DataLoader(ds, batch_size=4))))
    out[0][0, 0, 0, 0] = 42.0


# -- process workers (the reference's literal worker model) ---------------


class _FailAt:
    def __init__(self, bad_idx):
        self.bad = bad_idx

    def __len__(self):
        return 16

    def __getitem__(self, i):
        if i == self.bad:
            raise ValueError("boom at 7")
        return np.full((3,), i, np.float32), np.int32(i)


def _init_raises(wid):
    raise RuntimeError("bad init")


def test_process_loader_matches_sequential_order():
    xs = np.arange(24, dtype=np.float32).reshape(12, 2)
    ys = np.arange(12, dtype=np.int64)
    ds = tdata.ArrayDataset(xs, ys)
    seq = list(tdata.DataLoader(ds, batch_size=4))
    proc = list(
        tdata.DataLoader(ds, batch_size=4, num_workers=2,
                         worker_type="process")
    )
    assert len(seq) == len(proc) == 3
    for (sx, sy), (px, py) in zip(seq, proc):
        np.testing.assert_array_equal(sx, px)
        np.testing.assert_array_equal(sy, py)


@pytest.mark.slow  # spawn-heavy: tier-1 runs against an 870s kill
def test_process_loader_propagates_worker_error():
    loader = tdata.DataLoader(
        _FailAt(7), batch_size=4, num_workers=2, worker_type="process"
    )
    with pytest.raises(tdata.WorkerError, match="boom at 7"):
        list(loader)


@pytest.mark.slow  # spawn-heavy: tier-1 runs against an 870s kill
def test_process_loader_worker_init_error():
    ds = tdata.ArrayDataset(np.zeros((8, 2), np.float32))
    loader = tdata.DataLoader(
        ds, batch_size=2, num_workers=1, worker_type="process",
        worker_init_fn=_init_raises,
    )
    with pytest.raises(tdata.WorkerError, match="bad init"):
        list(loader)


def test_worker_type_validation():
    ds = tdata.ArrayDataset(np.zeros((4, 2), np.float32))
    with pytest.raises(ValueError, match="worker_type"):
        tdata.DataLoader(ds, batch_size=2, worker_type="greenlet")


def test_transforms_are_picklable_for_process_workers():
    import pickle

    T = tdata.transforms
    tf = T.Compose([
        T.RandomResizedCrop(8, seed=0),
        T.RandomHorizontalFlip(seed=1),
        T.ToFloat(),
        T.Normalize((0.5,) * 3, (0.25,) * 3),
    ])
    tf2 = pickle.loads(pickle.dumps(tf))
    x = np.random.RandomState(0).randint(0, 256, (16, 16, 3), np.uint8)
    out = tf2(x)
    assert out.shape == (8, 8, 3) and out.dtype == np.float32


class _TaggedDS:
    """__getitem__ returns a worker-settable tag — proves worker_init_fn
    reaches the worker's OWN dataset copy via get_worker_info()."""

    def __init__(self):
        self.tag = -1

    def __len__(self):
        return 8

    def __getitem__(self, i):
        return np.int32(self.tag)


def _tag_with_worker_id(wid):
    info = tdata.get_worker_info()
    assert info is not None and info.id == wid
    info.dataset.tag = wid + 100


@pytest.mark.slow  # spawn/compile-heavy: tier-1 runs against an 870s kill
def test_process_worker_init_reaches_worker_dataset_copy():
    ds = _TaggedDS()
    loader = tdata.DataLoader(
        ds, batch_size=2, num_workers=2, worker_type="process",
        worker_init_fn=_tag_with_worker_id,
    )
    vals = np.concatenate([b for b in loader])
    # batches alternate between the two workers' tags, round-robin
    np.testing.assert_array_equal(vals, [100, 100, 101, 101, 100, 100, 101, 101])
    assert ds.tag == -1  # parent copy untouched
    loader.close()


def test_process_workers_persist_across_epochs():
    xs = np.arange(16, dtype=np.float32).reshape(8, 2)
    ds = tdata.ArrayDataset(xs)
    loader = tdata.DataLoader(ds, batch_size=2, num_workers=2,
                              worker_type="process")
    first = [b.copy() for b in loader]
    procs = loader._pool["procs"]
    second = [b.copy() for b in loader]
    assert loader._pool["procs"] is procs  # no respawn
    assert all(p.is_alive() for p in procs)
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a, b)
    loader.close()
    assert loader._pool is None


def test_process_loader_abandoned_epoch_does_not_leak():
    xs = np.arange(32, dtype=np.float32).reshape(16, 2)
    ds = tdata.ArrayDataset(xs)
    loader = tdata.DataLoader(ds, batch_size=2, num_workers=2,
                              worker_type="process")
    it = iter(loader)
    next(it)  # abandon mid-epoch
    del it
    full = list(loader)  # stale epoch-1 outputs must be dropped
    assert len(full) == 8
    np.testing.assert_array_equal(full[0], xs[:2])
    np.testing.assert_array_equal(full[-1], xs[14:])
    loader.close()


def test_get_worker_info_none_in_main_process():
    assert tdata.get_worker_info() is None


def test_process_loader_rejects_concurrent_iterators():
    ds = tdata.ArrayDataset(np.arange(16, dtype=np.float32).reshape(8, 2))
    loader = tdata.DataLoader(ds, batch_size=2, num_workers=1,
                              worker_type="process")
    it1 = iter(loader)
    next(it1)
    with pytest.raises(RuntimeError, match="ONE active iterator"):
        next(iter(loader))
    it1.close()
    assert len(list(loader)) == 4  # usable again after the first is closed
    loader.close()


class _CropValueDS:
    """Returns the crop of a fixed ramp image — output depends entirely on
    the transform's RNG draw, making decorrelation observable."""

    def __init__(self):
        T = tdata.transforms
        self.transform = T.Compose([T.RandomCrop(4, padding=0, seed=0)])
        self.image = np.arange(16 * 16 * 1, dtype=np.float32).reshape(16, 16, 1)

    def __len__(self):
        return 4

    def __getitem__(self, i):
        return self.transform(self.image)


def _reseed_by_worker(wid):
    tdata.get_worker_info().dataset.transform.reseed(1000 + wid)


@pytest.mark.slow  # spawn/compile-heavy: tier-1 runs against an 870s kill
def test_compose_reseed_decorrelates_process_workers():
    ds = _CropValueDS()
    loader = tdata.DataLoader(
        ds, batch_size=1, num_workers=2, worker_type="process",
        worker_init_fn=_reseed_by_worker,
    )
    crops1 = [b.copy() for b in loader]
    # workers 0 and 1 (alternating batches) draw from different streams
    assert not np.array_equal(crops1[0], crops1[1])
    # and the reseeded streams are deterministic across fresh pools
    loader.close()
    loader2 = tdata.DataLoader(
        ds, batch_size=1, num_workers=2, worker_type="process",
        worker_init_fn=_reseed_by_worker,
    )
    crops2 = [b.copy() for b in loader2]
    for a, b in zip(crops1, crops2):
        np.testing.assert_array_equal(a, b)
    loader2.close()


def test_compose_reseed_is_deterministic_in_process():
    T = tdata.transforms
    x = np.random.RandomState(0).randint(0, 256, (16, 16, 3), np.uint8)
    tf = T.Compose([T.RandomResizedCrop(8, seed=5), T.RandomHorizontalFlip(seed=6)])
    tf.reseed(42)
    a = [tf(x) for _ in range(3)]
    tf.reseed(42)
    b = [tf(x) for _ in range(3)]
    for u, v in zip(a, b):
        np.testing.assert_array_equal(u, v)
    tf.reseed(43)
    c = [tf(x) for _ in range(3)]
    assert any(not np.array_equal(u, w) for u, w in zip(a, c))


@pytest.mark.slow
def test_loader_stress_no_deadlock():
    """Stress the reorder/staleness machinery: random full/partial/
    abandoned iterations over both worker types must neither hang nor
    produce out-of-order batches. The body runs in a watchdog thread so
    a reintroduced deadlock FAILS (join timeout) instead of hanging the
    pytest process forever."""
    xs = np.arange(48, dtype=np.float32).reshape(24, 2)

    def body():
        ds = tdata.ArrayDataset(xs)
        rng = np.random.RandomState(0)
        thread_loader = tdata.DataLoader(ds, batch_size=3, num_workers=3)
        proc_loader = tdata.DataLoader(ds, batch_size=3, num_workers=2,
                                       worker_type="process")
        try:
            for trial in range(30):
                loader = proc_loader if trial % 2 else thread_loader
                take = rng.randint(0, 9)  # 8 full batches per epoch
                it = iter(loader)
                got = []
                for _ in range(take):
                    try:
                        got.append(next(it))
                    except StopIteration:
                        break
                it.close()  # abandon (or finish) the iteration
                for i, b in enumerate(got):
                    np.testing.assert_array_equal(b, xs[i * 3:(i + 1) * 3])
            # after all that abuse, one clean full pass
            full = list(proc_loader)
            assert len(full) == 8
            np.testing.assert_array_equal(full[-1], xs[21:])
        finally:
            proc_loader.close()

    import threading

    errors = []

    def run():
        try:
            body()
        except BaseException as e:  # noqa: BLE001 - report into main thread
            errors.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive(), "loader stress run deadlocked (watchdog fired)"
    if errors:
        raise errors[0]


# -- every sample written into its slot of the batch as it is made ----------

_Pt = collections.namedtuple("_Pt", "x y")


class _Listed(tdata.Dataset):
    """``samples[i]``, made afresh on every read: what a dataset whose
    transforms run at ``__getitem__`` hands the loader."""

    def __init__(self, make, n=6):
        self.make, self.n = make, n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return self.make(i)


def _assert_same_batch(got, want):
    """Equal to the bit, container for container and leaf for leaf."""
    assert type(got) is type(want)
    if isinstance(want, (tuple, list)):
        assert len(got) == len(want)
        for a, b in zip(got, want):
            _assert_same_batch(a, b)
    elif isinstance(want, dict):
        assert list(got) == list(want)
        for k in want:
            _assert_same_batch(got[k], want[k])
    else:
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()


def _built(ds, idxs, collate_fn=tdata.default_collate):
    """The batch ``_build_batch`` makes, and the ``slotted`` arg of its
    ``loader.build`` span."""
    tracer = tracing.install(tracing.Tracer())
    try:
        batch = _build_batch(ds, collate_fn, idxs, seq=0, worker=0)
    finally:
        tracing.uninstall()
    (build,) = [e for e in tracer.events if e["name"] == "loader.build"]
    collates = [e for e in tracer.events if e["name"] == "loader.collate"]
    assert [c["args"]["parent_id"] for c in collates] == [
        build["args"]["span_id"]]  # what is left to assemble, inside
    return batch, build["args"]["slotted"]


def _family_sample(workload):
    """A maker of the benchmark cell's samples at its rehearsal size,
    from its own pool and transform, as ``chipbench/inputs/loader.py``
    makes them."""
    import importlib

    from chipbench import run

    wl = run.rehearsal(run.load_json("workloads", workload + ".json"))
    cfg = run.rehearsal(run.load_json("configs", wl["config"] + ".json"))
    family = importlib.import_module("chipbench.families." + cfg["family"])
    n = wl["input"]["pool_images"]
    pool = family.make_pool(cfg, n, np.random.default_rng(2147483659))
    transform = family.transform(cfg)
    return lambda i: transform(tuple(a[i % n] for a in pool))


SLOTTED = {
    "array": lambda i: np.full((2, 3), i, np.float32),
    "tuple": lambda i: (np.full(3, i, np.int16), np.ones((2, 2)) * i),
    "list": lambda i: [np.arange(4) + i, np.float32(i)],
    "namedtuple": lambda i: _Pt(np.full(2, i, np.uint8), i),
    "dict": lambda i: {"b": np.full(2, i), "a": (i, np.zeros(3, bool))},
    "numpy_scalars": lambda i: (np.float16(i / 3), np.int8(i), np.bool_(i)),
    "zero_d_arrays": lambda i: np.array(i / 7, np.float32),
    "python_scalars": lambda i: (i, i / 3, bool(i % 2), i * 1j),
    "strings": lambda i: ("ab"[i % 2] * 3, b"xyz"),
    "empty": lambda i: (np.zeros((0, 4), np.float32), ()),
}
CELLS = ["r50-train-b128", "retinanet-train-b2", "ouro-l8-train-b2x2048",
         "joyai-l5-train-b1x8192", "sdar-l6-train-b1x4096"]


@pytest.mark.parametrize("case", list(SLOTTED) + CELLS)
def test_slot_by_slot_build_is_default_collate_of_the_samples(case):
    make = SLOTTED[case] if case in SLOTTED else _family_sample(case)
    ds = _Listed(make)
    idxs = [4, 0, 5, 2]
    batch, slotted = _built(ds, idxs)
    assert slotted == 1
    _assert_same_batch(batch, tdata.default_collate([ds[i] for i in idxs]))


FALLBACKS = {
    "ragged": (lambda i: np.zeros(2 + (i == 3), np.float32), ValueError),
    "dtype_changes": (lambda i: np.full(2, i, np.int32 if i < 3 else np.int64),
                      None),
    "python_int_then_float": (lambda i: i if i < 3 else float(i), None),
    "container_changes": (lambda i: (i, i) if i < 3 else [i, i], None),
    "string_length_changes": (lambda i: "a" * (1 + i // 3), None),
    "object_leaf": (lambda i: (None, i), None),
    "missing_key": (lambda i: {"a": i} if i < 3 else {"b": i}, KeyError),
}


@pytest.mark.parametrize("case", list(FALLBACKS))
def test_a_batch_that_cannot_be_slotted_is_collated_whole(case):
    """What a slot cannot hold as ``default_collate`` would build it (a
    shape, dtype or container that changes within the batch, a leaf
    that is not array-like) is collated whole: the same batch, or the
    same error."""
    make, error = FALLBACKS[case]
    ds = _Listed(make)
    idxs = [1, 2, 3, 4]
    if error is not None:
        with pytest.raises(error):
            tdata.default_collate([ds[i] for i in idxs])
        tracer = tracing.install(tracing.Tracer())
        try:
            with pytest.raises(error):
                _build_batch(ds, tdata.default_collate, idxs, seq=0, worker=0)
        finally:
            tracing.uninstall()
        (build,) = [e for e in tracer.events if e["name"] == "loader.build"]
        assert build["args"]["slotted"] == 0
        return
    batch, slotted = _built(ds, idxs)
    assert slotted == 0
    _assert_same_batch(batch, tdata.default_collate([ds[i] for i in idxs]))


def test_a_user_collate_fn_gets_the_list_of_samples():
    ds = _Listed(lambda i: np.full(2, i, np.float32))
    seen = []

    def collate(samples):
        seen.append([s.copy() for s in samples])
        return np.concatenate(samples)

    batch, slotted = _built(ds, [3, 1], collate)
    assert slotted == 0
    np.testing.assert_array_equal(batch, [3, 3, 1, 1])
    assert [s[0] for s in seen[0]] == [3, 1]


def test_untraced_build_is_the_traced_one():
    ds = _Listed(SLOTTED["dict"])
    traced, _ = _built(ds, [0, 1, 2])
    _assert_same_batch(
        _build_batch(ds, tdata.default_collate, [0, 1, 2], seq=0, worker=0),
        traced)


class _Images(tdata.Dataset):
    """uint8 images through the benchmark's image transform; module level,
    so process workers can unpickle it."""

    def __init__(self):
        T = tdata.transforms
        self.norm = T.Compose([T.ToFloat(), T.Normalize(
            (0.485, 0.456, 0.406), (0.229, 0.224, 0.225))])
        self.pool = np.random.default_rng(7).integers(
            0, 256, (5, 12, 10, 3), dtype=np.uint8)

    def __len__(self):
        return 20

    def __getitem__(self, i):
        return self.norm(self.pool[i % 5]), np.int32(i)


@pytest.mark.parametrize("workers,worker_type", [
    (3, "thread"), (8, "thread"), (2, "process")])
def test_loaders_yield_the_sequential_batches_with_the_image_transform(
        workers, worker_type):
    ds = _Images()
    ref = list(tdata.DataLoader(ds, batch_size=4))
    loader = tdata.DataLoader(ds, batch_size=4, num_workers=workers,
                              worker_type=worker_type)
    try:
        got = list(loader)
    finally:
        loader.close()
    assert len(got) == len(ref) == 5
    for a, b in zip(got, ref):
        _assert_same_batch(a, b)
    images = np.stack([ds[i][0] for i in range(4)])
    _assert_same_batch(ref[0][0], images)
