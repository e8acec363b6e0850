"""ctypes bindings for the native C++ runtime components
(``native/libtpu_syncbn_native.so``).

These are the TPU-native homes for the reference's native (C++/CUDA)
non-kernel components (SURVEY §2 "Native?" rows):

* sampler index generation (C++ MT19937 identical to numpy's legacy
  RandomState — the index arithmetic of
  ``[torch] utils/data/distributed.py`` in native code);
* staging ring buffer (the pinned-memory batch staging of
  ``DataLoader(pin_memory=True)``, reference ``README.md:88``);
* TCP key/value store + counters (torch's C++ TCPStore behind
  ``init_method='env://'``, reference ``README.md:32``).

The library is built with ``make`` on first use, and again whenever a
source under ``native/csrc/`` is newer than it (the binary is
git-ignored, so what is on disk may predate the sources). Every consumer
has a pure-Python fallback, so the framework works without a toolchain —
but it says so: :func:`status` tells which of built / loaded /
unavailable happened, and an unavailable library is logged once.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_SRC_DIR = os.path.join(_NATIVE_DIR, "csrc")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libtpu_syncbn_native.so")

_lib = None
_lib_lock = threading.Lock()
_status = "not loaded"


def _stale() -> bool:
    """Is the binary absent, or older than any source it is built from?"""
    try:
        built = os.path.getmtime(_LIB_PATH)
    except OSError:
        return True
    return any(
        os.path.getmtime(os.path.join(_SRC_DIR, name)) > built
        for name in os.listdir(_SRC_DIR)
    )


def _build() -> None:
    """``make`` into a name of this process's own, then rename: several
    processes (loader workers) may find the binary stale at once, and
    none of them may load a half-written file."""
    tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["make", "-B", "-C", _NATIVE_DIR, f"OUT={os.path.basename(tmp)}"],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, _LIB_PATH)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def load() -> ctypes.CDLL | None:
    """Load (building if absent or stale) the native library; None if
    unavailable."""
    global _lib, _status
    if _status != "not loaded":
        return _lib
    with _lib_lock:
        if _status != "not loaded":
            return _lib
        try:
            build = _stale()
            if build:
                _build()
            lib = ctypes.CDLL(_LIB_PATH)
        except (OSError, subprocess.SubprocessError) as e:
            _status = "unavailable"
            logging.getLogger("tpu_syncbn").warning(
                "native library unavailable (%s: %s); using the "
                "pure-Python paths", type(e).__name__, e,
            )
            return None
        _configure(lib)
        _lib = lib
        _status = "built" if build else "loaded"
        return _lib


def status() -> str:
    """``"built"`` (compiled by this process), ``"loaded"`` (an
    up-to-date binary was on disk) or ``"unavailable"`` (no toolchain or
    the build failed: the pure-Python paths are in use)."""
    load()
    return _status


def available() -> bool:
    return load() is not None


def _configure(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.tsb_permutation.argtypes = [c.c_uint32, c.c_int64, c.POINTER(c.c_int64)]
    lib.tsb_permutation.restype = None
    lib.tsb_sampler_indices.argtypes = [
        c.c_int64, c.c_int32, c.c_int32, c.c_uint32, c.c_int64,
        c.c_int32, c.c_int32, c.POINTER(c.c_int64),
    ]
    lib.tsb_sampler_indices.restype = c.c_int64

    lib.tsb_ring_create.argtypes = [c.c_int32, c.c_int64]
    lib.tsb_ring_create.restype = c.c_void_p
    lib.tsb_ring_destroy.argtypes = [c.c_void_p]
    lib.tsb_ring_acquire.argtypes = [c.c_void_p, c.POINTER(c.c_void_p)]
    lib.tsb_ring_acquire.restype = c.c_int64
    lib.tsb_ring_commit.argtypes = [c.c_void_p, c.c_int64, c.c_int64]
    lib.tsb_ring_consume.argtypes = [
        c.c_void_p, c.POINTER(c.c_void_p), c.POINTER(c.c_int64)
    ]
    lib.tsb_ring_consume.restype = c.c_int64
    lib.tsb_ring_release.argtypes = [c.c_void_p, c.c_int64]
    lib.tsb_ring_slot_bytes.argtypes = [c.c_void_p]
    lib.tsb_ring_slot_bytes.restype = c.c_int64

    lib.tsb_store_server_start.argtypes = [c.c_uint16, c.POINTER(c.c_uint16)]
    lib.tsb_store_server_start.restype = c.c_void_p
    lib.tsb_store_server_stop.argtypes = [c.c_void_p]
    lib.tsb_store_connect.argtypes = [c.c_char_p, c.c_uint16]
    lib.tsb_store_connect.restype = c.c_int32
    lib.tsb_store_close.argtypes = [c.c_int32]
    lib.tsb_store_set.argtypes = [
        c.c_int32, c.c_char_p, c.POINTER(c.c_uint8), c.c_uint32
    ]
    lib.tsb_store_set.restype = c.c_int32
    lib.tsb_store_get.argtypes = [
        c.c_int32, c.c_char_p, c.POINTER(c.c_uint8), c.c_int64
    ]
    lib.tsb_store_get.restype = c.c_int64
    lib.tsb_store_add.argtypes = [c.c_int32, c.c_char_p, c.c_int64]
    lib.tsb_store_add.restype = c.c_int64


# -- sampler --------------------------------------------------------------


def permutation(seed: int, n: int):
    """numpy ``RandomState(seed).permutation(n)`` computed natively
    (bit-identical; parity enforced in tests). Returns an int64 ndarray,
    or None when the native lib is unavailable."""
    import numpy as np

    lib = load()
    if lib is None:
        return None
    out = np.empty(n, dtype=np.int64)
    lib.tsb_permutation(
        seed & 0xFFFFFFFF, n, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
    )
    return out


def sampler_indices(length, num_replicas, rank, seed, epoch, shuffle, drop_last):
    """Native DistributedSampler epoch shard; None if lib unavailable."""
    import numpy as np

    lib = load()
    if lib is None:
        return None
    if drop_last and length % num_replicas != 0:
        num = length // num_replicas
    else:
        num = -(-length // num_replicas)
    out = np.empty(max(num, 1), dtype=np.int64)
    written = lib.tsb_sampler_indices(
        length, num_replicas, rank, seed & 0xFFFFFFFF, epoch,
        1 if shuffle else 0, 1 if drop_last else 0,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    if written < 0:
        raise ValueError("invalid sampler arguments")
    return out[:written]


# -- staging ring ---------------------------------------------------------


class StagingRing:
    """Reusable aligned staging slots (pinned-memory equivalent). Producer
    threads acquire/commit; the consumer consumes/releases; buffers are
    zero-copy viewable as numpy arrays."""

    def __init__(self, n_slots: int, slot_bytes: int):
        lib = load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._ring = lib.tsb_ring_create(n_slots, slot_bytes)
        if not self._ring:
            raise MemoryError("ring allocation failed")
        self.slot_bytes = slot_bytes

    def acquire(self):
        buf = ctypes.c_void_p()
        slot = self._lib.tsb_ring_acquire(self._ring, ctypes.byref(buf))
        return slot, buf.value

    def commit(self, slot: int, size: int):
        self._lib.tsb_ring_commit(self._ring, slot, size)

    def consume(self):
        buf = ctypes.c_void_p()
        size = ctypes.c_int64()
        slot = self._lib.tsb_ring_consume(
            self._ring, ctypes.byref(buf), ctypes.byref(size)
        )
        return slot, buf.value, size.value

    def release(self, slot: int):
        self._lib.tsb_ring_release(self._ring, slot)

    def view(self, addr: int, nbytes: int):
        """numpy uint8 view of a slot buffer (no copy)."""
        import numpy as np

        return np.ctypeslib.as_array(
            (ctypes.c_uint8 * nbytes).from_address(addr)
        )

    def close(self):
        if self._ring:
            self._lib.tsb_ring_destroy(self._ring)
            self._ring = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# -- TCP store ------------------------------------------------------------


class TCPStoreServer:
    """Rank-0 rendezvous store server (torch TCPStore equivalent)."""

    def __init__(self, port: int = 0):
        lib = load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        out_port = ctypes.c_uint16()
        self._handle = lib.tsb_store_server_start(port, ctypes.byref(out_port))
        if not self._handle:
            raise OSError(f"could not bind store server on port {port}")
        self.port = out_port.value

    def stop(self):
        if self._handle:
            self._lib.tsb_store_server_stop(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.stop()
        except Exception:
            pass


class TCPStoreClient:
    """Client for :class:`TCPStoreServer`: set/get(blocking)/add, plus the
    barrier torch builds from counters."""

    def __init__(self, host: str, port: int):
        lib = load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._fd = lib.tsb_store_connect(host.encode(), port)
        if self._fd < 0:
            raise ConnectionError(f"could not connect to {host}:{port}")

    def set(self, key: str, value: bytes):
        if self._lib.tsb_store_set(
            self._fd, key.encode(),
            (ctypes.c_uint8 * len(value)).from_buffer_copy(value) if value
            else None,
            len(value),
        ) != 0:
            raise ConnectionError("set failed")

    def get(self, key: str, max_bytes: int = 1 << 20) -> bytes:
        buf = (ctypes.c_uint8 * max_bytes)()
        n = self._lib.tsb_store_get(self._fd, key.encode(), buf, max_bytes)
        if n < 0:
            raise ConnectionError("get failed")
        if n > max_bytes:
            raise ValueError(
                f"value for {key!r} is {n} bytes, larger than max_bytes="
                f"{max_bytes}; pass a bigger max_bytes"
            )
        return bytes(buf[:n])

    def add(self, key: str, delta: int) -> int:
        result = self._lib.tsb_store_add(self._fd, key.encode(), delta)
        if result == -(2**63):
            raise ConnectionError("add failed")
        return result

    def barrier(self, name: str, world: int):
        """All ``world`` participants block until everyone arrived — the
        store-barrier used by env:// rendezvous world assembly."""
        arrived = self.add(f"__barrier__{name}", 1)
        if arrived > world:
            raise RuntimeError(f"barrier {name!r} oversubscribed: {arrived}>{world}")
        if arrived == world:
            self.set(f"__barrier_done__{name}", b"1")
        else:
            self.get(f"__barrier_done__{name}")  # blocks until released

    def close(self):
        if self._fd >= 0:
            self._lib.tsb_store_close(self._fd)
            self._fd = -1

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
