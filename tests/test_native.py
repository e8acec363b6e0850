"""Native C++ runtime tests: MT19937 permutation parity with numpy,
sampler index parity with the Python sampler, staging ring, TCP store."""

import threading

import numpy as np
import pytest

from tpu_syncbn.runtime import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable"
)


@pytest.mark.parametrize("seed", [0, 1, 42, 2**31 - 1, 999983])
@pytest.mark.parametrize("n", [1, 2, 7, 100, 10_000])
def test_permutation_bit_identical_to_numpy(seed, n):
    ours = native.permutation(seed, n)
    theirs = np.random.RandomState(seed).permutation(n)
    np.testing.assert_array_equal(ours, theirs)


@pytest.mark.parametrize("length,world,drop_last,shuffle", [
    (100, 4, False, True),
    (101, 4, True, True),
    (101, 4, False, False),
    (7, 8, False, True),
    (64, 2, True, False),
])
def test_sampler_indices_match_python_sampler(length, world, drop_last, shuffle):
    from tpu_syncbn.data.sampler import DistributedSampler

    for rank in range(world):
        for epoch in (0, 3):
            nat = native.sampler_indices(
                length, world, rank, seed=5, epoch=epoch,
                shuffle=shuffle, drop_last=drop_last,
            )
            # force the pure-python path for comparison
            s = DistributedSampler(
                length, world, rank, shuffle=shuffle, seed=5, drop_last=drop_last
            )
            s.set_epoch(epoch)
            rng = np.random.RandomState(5 + epoch)
            indices = rng.permutation(length) if shuffle else np.arange(length)
            if not drop_last:
                pad = s.total_size - length
                if pad > 0:
                    reps = -(-pad // length)
                    indices = np.concatenate(
                        [indices, np.tile(indices, reps)[:pad]]
                    )
            else:
                indices = indices[: s.total_size]
            expected = indices[rank : s.total_size : world]
            np.testing.assert_array_equal(nat, expected)


def test_sampler_invalid_args():
    with pytest.raises(ValueError):
        native.sampler_indices(10, 2, 5, seed=0, epoch=0, shuffle=True,
                               drop_last=False)


def test_staging_ring_roundtrip_threaded():
    ring = native.StagingRing(n_slots=3, slot_bytes=1024)
    n_batches = 20
    payloads = [np.random.bytes(100 + i) for i in range(n_batches)]

    def producer():
        for p in payloads:
            slot, addr = ring.acquire()
            view = ring.view(addr, len(p))
            view[:] = np.frombuffer(p, dtype=np.uint8)
            ring.commit(slot, len(p))

    t = threading.Thread(target=producer)
    t.start()
    got = []
    for _ in range(n_batches):
        slot, addr, size = ring.consume()
        got.append(bytes(ring.view(addr, size)))
        ring.release(slot)
    t.join()
    assert got == payloads
    ring.close()


def test_staging_ring_alignment():
    ring = native.StagingRing(n_slots=2, slot_bytes=256)
    slot, addr = ring.acquire()
    assert addr % 64 == 0  # 64-byte aligned staging slots
    ring.commit(slot, 1)
    ring.close()


def test_tcp_store_set_get_add():
    server = native.TCPStoreServer()
    try:
        c1 = native.TCPStoreClient("127.0.0.1", server.port)
        c2 = native.TCPStoreClient("127.0.0.1", server.port)
        c1.set("addr", b"10.0.0.1:1234")
        assert c2.get("addr") == b"10.0.0.1:1234"
        assert c1.add("count", 2) == 2
        assert c2.add("count", 3) == 5
        # counters visible through get (string-mirrored)
        assert c1.get("count") == b"5"
        c1.close()
        c2.close()
    finally:
        server.stop()


def test_tcp_store_blocking_get():
    """GET blocks until another client sets the key — the rendezvous wait."""
    server = native.TCPStoreServer()
    try:
        results = {}

        def waiter():
            c = native.TCPStoreClient("127.0.0.1", server.port)
            results["value"] = c.get("late-key")
            c.close()

        t = threading.Thread(target=waiter)
        t.start()
        t.join(timeout=0.3)
        assert t.is_alive()  # still blocked
        setter = native.TCPStoreClient("127.0.0.1", server.port)
        setter.set("late-key", b"now")
        t.join(timeout=5)
        assert not t.is_alive()
        assert results["value"] == b"now"
        setter.close()
    finally:
        server.stop()


def test_tcp_store_barrier():
    server = native.TCPStoreServer()
    try:
        world = 4
        order = []
        lock = threading.Lock()

        def participant(i):
            c = native.TCPStoreClient("127.0.0.1", server.port)
            c.barrier("epoch0", world)
            with lock:
                order.append(i)
            c.close()

        threads = [threading.Thread(target=participant, args=(i,)) for i in range(world)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert len(order) == world
    finally:
        server.stop()


def test_distributed_sampler_uses_native_and_matches():
    """End-to-end: the DistributedSampler's native path produces the exact
    sequence the pure-python path documents."""
    from tpu_syncbn.data.sampler import DistributedSampler

    s = DistributedSampler(101, 4, 1, shuffle=True, seed=7, drop_last=False)
    s.set_epoch(2)
    native_out = list(s)
    rng = np.random.RandomState(7 + 2)
    indices = rng.permutation(101)
    pad = s.total_size - 101
    indices = np.concatenate([indices, indices[:pad]])
    expected = indices[1 : s.total_size : 4].tolist()
    assert native_out == expected


def test_staging_ring_two_producers():
    """Concurrent producers must interleave slots without deadlock (the
    acquire index is recomputed under the lock, not latched stale)."""
    ring = native.StagingRing(n_slots=2, slot_bytes=64)
    n_each = 30
    counter = {"total": 0}
    lock = threading.Lock()

    def producer(tag):
        for i in range(n_each):
            slot, addr = ring.acquire()
            ring.view(addr, 1)[0] = tag
            ring.commit(slot, 1)

    ts = [threading.Thread(target=producer, args=(t,)) for t in (1, 2)]
    for t in ts:
        t.start()
    seen = []
    for _ in range(2 * n_each):
        slot, addr, size = ring.consume()
        seen.append(int(ring.view(addr, 1)[0]))
        ring.release(slot)
    for t in ts:
        t.join(timeout=5)
        assert not t.is_alive()
    assert sorted(set(seen)) == [1, 2]
    assert len(seen) == 2 * n_each
    ring.close()


def test_sampler_seed_wrap_parity():
    """seed+epoch >= 2**32 wraps identically on the native and python paths."""
    from tpu_syncbn.data.sampler import DistributedSampler

    s = DistributedSampler(50, 2, 0, shuffle=True, seed=2**32 - 1)
    s.set_epoch(3)  # wraps to seed 2
    via_native_or_python = list(s)
    expected = np.random.RandomState(2).permutation(50)
    total = s.total_size
    expected = np.concatenate([expected, expected[: total - 50]])[0:total:2]
    assert via_native_or_python == expected.tolist()


def test_tcp_store_get_too_large_raises():
    server = native.TCPStoreServer()
    try:
        c = native.TCPStoreClient("127.0.0.1", server.port)
        c.set("big", b"x" * 100)
        with pytest.raises(ValueError, match="larger than max_bytes"):
            c.get("big", max_bytes=10)
        c.close()
    finally:
        server.stop()


def test_server_stop_with_live_connections_fast():
    import time

    server = native.TCPStoreServer()
    c = native.TCPStoreClient("127.0.0.1", server.port)
    c.set("k", b"v")
    t0 = time.time()
    server.stop()  # must not hang on the live connection
    assert time.time() - t0 < 2


class TestBuildWhenStale:
    """The binary is git-ignored: a checkout has none, and a working
    tree may hold one older than the sources. ``load`` builds in both
    cases and says which of built / loaded / unavailable happened."""

    @pytest.fixture
    def scratch_native(self, tmp_path, monkeypatch):
        import os
        import shutil

        src = os.path.join(native._NATIVE_DIR, "csrc")
        work = tmp_path / "native"
        work.mkdir()
        shutil.copy(os.path.join(native._NATIVE_DIR, "Makefile"), work)
        shutil.copytree(src, work / "csrc")
        monkeypatch.setattr(native, "_NATIVE_DIR", str(work))
        monkeypatch.setattr(native, "_SRC_DIR", str(work / "csrc"))
        monkeypatch.setattr(
            native, "_LIB_PATH", str(work / "libtpu_syncbn_native.so")
        )

        def fresh_process():
            monkeypatch.setattr(native, "_lib", None)
            monkeypatch.setattr(native, "_status", "not loaded")

        fresh_process()
        return work, fresh_process

    def test_absent_builds_then_loads_then_rebuilds_when_stale(
        self, scratch_native
    ):
        import os

        work, fresh_process = scratch_native
        assert native.status() == "built"  # absent: built
        fresh_process()
        assert native.status() == "loaded"  # up to date: no build
        lib = work / "libtpu_syncbn_native.so"
        old = os.path.getmtime(work / "csrc" / "sampler.cc") - 100
        os.utime(lib, (old, old))  # now older than its sources
        fresh_process()
        assert native.status() == "built"
        assert os.path.getmtime(lib) > old

    def test_no_toolchain_is_unavailable_and_said_so(
        self, scratch_native, monkeypatch, caplog
    ):
        import subprocess

        _, fresh_process = scratch_native

        def no_make(*a, **k):
            raise FileNotFoundError("make")

        monkeypatch.setattr(subprocess, "run", no_make)
        with caplog.at_level("WARNING", logger="tpu_syncbn"):
            assert native.load() is None
        assert native.status() == "unavailable"
        assert "native library unavailable" in caplog.text
