"""Checkpoint / resume with integrity manifests.

The reference has no checkpointing (SURVEY §5.4) — but evaluating top-1
parity targets requires persisting params + BN running stats, and the
torch-world convention the recipe implies is "rank 0 writes" (the same
master-only convention as logging, reference ``README.md:9``). This module
provides exactly that: master-host-only atomic writes of any pytree
(params, BatchStats, optimizer state), with numbered steps and pruning.

Serialization is ``flax.serialization`` msgpack — pure pytree bytes, no
pickle execution risk, stable across processes.

Integrity (docs/RESILIENCE.md): every ``ckpt_{N}.msgpack`` is certified by
a sibling ``ckpt_{N}.manifest.json`` holding the payload's checksums
(vectorized ``sum64`` always; CRC32 additionally while the payload is
small enough for a serial pass to be free), byte length, step, and a hash
of the pytree structure. Both files are written
atomically (tmp + rename), payload strictly before manifest, so a crash or
preemption at ANY byte leaves either a fully certified checkpoint or an
uncertified leftover — never a certified-but-truncated one. ``load`` of
the latest checkpoint skips candidates whose certification fails and falls
back to the newest *verified* older step instead of dying on an opaque
msgpack error mid-resume.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import re
import tempfile
import time
import zlib
from typing import Any

import jax
from flax import serialization

from tpu_syncbn.obs import telemetry, tracing
from tpu_syncbn.runtime import distributed as dist

_CKPT_RE = re.compile(r"^ckpt_(\d+)\.msgpack$")
_PUB_RE = re.compile(r"^weights_v(\d+)\.msgpack$")

#: Bump when the manifest schema changes incompatibly.
MANIFEST_FORMAT = 1

#: The atomically-renamed pointer file naming the currently published
#: weight version (serve-side consumers resolve through it, never by
#: directory listing — a half-written version is unreachable until the
#: pointer lands, and the pointer lands only after read-back
#: verification).
PUBLISHED_POINTER = "published.json"

#: Payloads up to this size also get a CRC32 (serial, ~1 GB/s); above it
#: only the vectorized ``sum64`` checksum is computed, keeping manifest
#: verification a small share of the checkpoint round-trip at any size.
_CRC32_MAX_BYTES = int(
    float(os.environ.get("TPU_SYNCBN_CKPT_CRC32_MAX_MB", "32")) * (1 << 20)
)


def payload_sum64(data: bytes) -> str:
    """Fast integrity checksum: little-endian uint64 block sum (mod 2^64)
    plus the tail bytes and the length, hex-encoded. Runs at memory
    bandwidth via numpy (~10-20x zlib.crc32), and *guarantees* detection
    of truncation (length term) and any single bit flip (a flipped bit
    changes one block by ±2^k, which cannot cancel mod 2^64) — the two
    corruption modes a killed writer or bad disk actually produces."""
    import numpy as np

    mv = memoryview(data)
    head = len(data) & ~7
    if head:
        blocks = np.frombuffer(mv[:head], dtype="<u8")
        s = int(np.add.reduce(blocks, dtype=np.uint64))
    else:
        s = 0
    tail = int.from_bytes(bytes(mv[head:]), "little")
    s = (s + tail) & 0xFFFFFFFFFFFFFFFF
    return f"{s:016x}:{len(data):x}"


class CheckpointCorruptError(RuntimeError):
    """Raised when an explicitly requested checkpoint (or every available
    candidate) fails integrity verification or deserialization."""


class PublicationSkewError(RuntimeError):
    """Raised when a published weight version's recorded tree structure
    (manifest ``tree_hash``) does not match what the consumer expects —
    a publisher running ahead of (or behind) the server's model schema.
    Distinct from :class:`CheckpointCorruptError`: the bytes are intact,
    the *shape* is wrong, and retrying the read cannot help."""


def _purify(tree: Any) -> Any:
    """Recursively convert nnx State nodes (not msgpack-serializable) to
    pure nested dicts; leaves other structures alone."""
    from flax import nnx

    from tpu_syncbn import compat

    if isinstance(tree, nnx.State):
        return compat.nnx_to_pure_dict(tree)
    if isinstance(tree, dict):
        return {k: _purify(v) for k, v in tree.items()}
    if isinstance(tree, tuple) and hasattr(tree, "_fields"):  # namedtuple
        return type(tree)(*(_purify(v) for v in tree))
    if isinstance(tree, (list, tuple)):
        return type(tree)(_purify(v) for v in tree)
    return tree


def _unpurify(template: Any, pure: Any) -> Any:
    """Inverse of :func:`_purify`: rebuild State nodes from pure dicts
    using ``template``'s structure."""
    from flax import nnx

    from tpu_syncbn import compat

    if isinstance(template, nnx.State):
        state = jax.tree_util.tree_map(lambda x: x, template)  # copy
        compat.nnx_replace_by_pure_dict(state, pure)
        return state
    if isinstance(template, dict):
        return {k: _unpurify(template[k], pure[k]) for k in template}
    if isinstance(template, tuple) and hasattr(template, "_fields"):
        return type(template)(
            *(_unpurify(t, p) for t, p in zip(template, pure))
        )
    if isinstance(template, (list, tuple)):
        return type(template)(
            _unpurify(t, p) for t, p in zip(template, pure)
        )
    return pure


def _path(directory: str, step: int) -> str:
    return os.path.join(directory, f"ckpt_{step}.msgpack")


def _manifest_path(directory: str, step: int) -> str:
    return os.path.join(directory, f"ckpt_{step}.manifest.json")


def tree_structure_hash(pure_tree: Any) -> str:
    """Stable hash of a pure pytree's *structure* (treedef + per-leaf
    shape/dtype, values excluded) — written into the manifest so a
    checkpoint records which model/optimizer shape produced it."""
    import numpy as np

    leaves, treedef = jax.tree_util.tree_flatten(pure_tree)
    h = hashlib.sha256(repr(treedef).encode())
    for leaf in leaves:
        arr = np.asarray(leaf)
        h.update(f"{arr.dtype.str}:{arr.shape};".encode())
    return h.hexdigest()[:16]


def _atomic_write(directory: str, final_path: str, data: bytes) -> None:
    """tmp + rename in ``directory`` (same filesystem, hence atomic)."""
    fd, tmp = tempfile.mkstemp(dir=directory, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
        os.replace(tmp, final_path)
    finally:
        with contextlib.suppress(FileNotFoundError):
            os.unlink(tmp)


def available_steps(directory: str) -> list[int]:
    if not os.path.isdir(directory):
        return []
    out = []
    for name in os.listdir(directory):
        m = _CKPT_RE.match(name)
        if m:
            out.append(int(m.group(1)))
    return sorted(out)


def read_manifest(directory: str, step: int) -> dict | None:
    """The parsed manifest for ``step``, or None when absent/unreadable
    (pre-manifest checkpoints are legal: they load, but cannot be
    *verified* and lose fallback priority to certified ones)."""
    try:
        with open(_manifest_path(directory, step)) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def _payload_matches(manifest: dict, data: bytes) -> bool:
    if manifest.get("nbytes") != len(data):
        return False
    sum64 = manifest.get("sum64")
    crc32 = manifest.get("crc32")
    if sum64 is None and crc32 is None:
        return False  # a manifest that certifies nothing certifies nothing
    if sum64 is not None and sum64 != payload_sum64(data):
        return False
    if crc32 is not None and crc32 != (zlib.crc32(data) & 0xFFFFFFFF):
        return False
    return True


def verify_checkpoint(directory: str, step: int) -> bool:
    """True iff ``step``'s payload exists AND its manifest certifies it
    (byte length and CRC32 both match). Legacy checkpoints without a
    manifest — and anything truncated, bit-flipped, or mid-write — report
    False. Verification time and failures feed telemetry
    (``checkpoint.verify_s`` / ``checkpoint.verify_failures``,
    docs/OBSERVABILITY.md)."""
    t0 = time.perf_counter()
    with tracing.span("checkpoint_verify", step=int(step)):
        ok = _verify_checkpoint_impl(directory, step)
    telemetry.observe("checkpoint.verify_s", time.perf_counter() - t0)
    if not ok:
        telemetry.count("checkpoint.verify_failures")
    return ok


def _verify_checkpoint_impl(directory: str, step: int) -> bool:
    manifest = read_manifest(directory, step)
    if manifest is None:
        return False
    try:
        with open(_path(directory, step), "rb") as f:
            data = f.read()
    except OSError:
        return False
    return _payload_matches(manifest, data)


def verified_steps(directory: str) -> list[int]:
    """Ascending steps whose manifest certifies the payload."""
    return [s for s in available_steps(directory)
            if verify_checkpoint(directory, s)]


def save_checkpoint(
    directory: str,
    step: int,
    tree: Any,
    *,
    keep: int = 3,
) -> str | None:
    """Write ``tree`` as ``ckpt_{step}.msgpack`` plus its integrity
    manifest — master host only (other hosts return None immediately);
    both writes atomic via tmp+rename, payload before manifest; prunes to
    the newest ``keep`` checkpoints. Save latency rides telemetry
    (``checkpoint.save_s`` histogram + ``checkpoint.saves`` counter) and
    a ``checkpoint_save`` trace span."""
    if not dist.is_master():
        return None
    t0 = time.perf_counter()
    with tracing.span("checkpoint_save", step=int(step)):
        path = _save_checkpoint_impl(directory, step, tree, keep=keep)
    telemetry.observe("checkpoint.save_s", time.perf_counter() - t0)
    telemetry.count("checkpoint.saves")
    return path


def _save_checkpoint_impl(
    directory: str, step: int, tree: Any, *, keep: int
) -> str:
    # nnx State → pure dicts, then one batched device→host fetch
    host_tree = jax.device_get(_purify(tree))
    return _write_host_tree(directory, step, host_tree, keep=keep)


def _write_host_tree(
    directory: str, step: int, host_tree: Any, *, keep: int
) -> str:
    """Serialize + certify an already-host-resident pure tree — the
    write half shared by the synchronous path and the
    :class:`AsyncCheckpointer` background thread."""
    os.makedirs(directory, exist_ok=True)
    data = serialization.to_bytes(host_tree)
    _atomic_write(directory, _path(directory, step), data)
    manifest = {
        "format": MANIFEST_FORMAT,
        "step": int(step),
        "nbytes": len(data),
        "sum64": payload_sum64(data),
        # serial CRC32 only while it's cheap; sum64 carries integrity
        # above the threshold (see _CRC32_MAX_BYTES)
        "crc32": (zlib.crc32(data) & 0xFFFFFFFF)
        if len(data) <= _CRC32_MAX_BYTES else None,
        "tree_hash": tree_structure_hash(host_tree),
    }
    _atomic_write(
        directory, _manifest_path(directory, step),
        json.dumps(manifest).encode(),
    )
    if keep > 0:
        for old in available_steps(directory)[:-keep]:
            # Idempotent prune: a concurrent prune (crashed-and-restarted
            # master, operator cleanup) may have removed a path between
            # our listing and the unlink — losing a save to that race
            # would turn cleanup into a fault. Manifest goes FIRST so an
            # interrupted prune leaves an uncertified payload (skipped by
            # the verified fallback), never a certified dangling manifest.
            with contextlib.suppress(FileNotFoundError):
                os.unlink(_manifest_path(directory, old))
            with contextlib.suppress(FileNotFoundError):
                os.unlink(_path(directory, old))
    return _path(directory, step)


def _load_verified_local(directory: str, pure_target: Any, logger):
    """Single-host latest-checkpoint selection with integrity fallback:
    newest→oldest, skipping any candidate that fails manifest CRC or
    deserialization. Returns (pure_tree, step)."""
    steps = available_steps(directory)
    if not steps:
        raise FileNotFoundError(f"no checkpoints in {directory!r}")
    tried: list[str] = []
    for step in reversed(steps):
        manifest = read_manifest(directory, step)
        try:
            with open(_path(directory, step), "rb") as f:
                data = f.read()
        except OSError as e:
            tried.append(f"step {step}: unreadable ({e})")
            continue
        if manifest is not None and not _payload_matches(manifest, data):
            tried.append(f"step {step}: payload fails manifest CRC/size "
                         "(truncated or corrupt)")
            telemetry.count("checkpoint.verify_failures")
            logger.warning(
                "checkpoint step %d in %s fails integrity verification; "
                "falling back to an older checkpoint", step, directory,
            )
            continue
        try:
            return serialization.from_bytes(pure_target, data), step
        except Exception as e:  # opaque msgpack/structure error
            tried.append(f"step {step}: deserialization failed "
                         f"({type(e).__name__}: {e})")
            logger.warning(
                "checkpoint step %d in %s failed to deserialize (%s); "
                "falling back to an older checkpoint", step, directory, e,
            )
            continue
    raise CheckpointCorruptError(
        f"every checkpoint in {directory!r} failed verification:\n  "
        + "\n  ".join(tried)
    )


def load_checkpoint(directory: str, target: Any, *, step: int | None = None):
    """Restore the latest (or a specific) checkpoint into the structure of
    ``target`` (a pytree template, e.g. ``dp.state_dict()``). Returns
    ``(tree, step)``. Load latency rides telemetry
    (``checkpoint.load_s`` histogram + ``checkpoint.loads`` counter) and
    a ``checkpoint_load`` trace span; skipped-corrupt candidates count
    into ``checkpoint.verify_failures``.
    Raises FileNotFoundError when nothing exists, and
    :class:`CheckpointCorruptError` when an explicitly requested step (or
    every candidate) fails integrity verification.

    Latest-selection (``step=None``) is fault-tolerant: a candidate whose
    manifest does not certify its payload, or whose payload fails to
    deserialize, is skipped with a warning and the newest *verified* older
    checkpoint restores instead — a preempted/interrupted writer can never
    brick resume.

    Multi-host (shared filesystem): hosts first synchronize, then agree on
    the step by taking the *master host's* newest verified — listing
    independently could race the master's in-flight write/prune and
    restore different steps per host, breaking the replicas-identical
    invariant. Followers then open the agreed path directly (with a short
    retry) instead of validating it against their *own* directory listing:
    on a shared filesystem with attribute-cache lag the listing can omit a
    file that is already readable. Followers re-verify the payload against
    the (retry-read) manifest, so every host restores byte-identical state.
    """
    t0 = time.perf_counter()
    with tracing.span("checkpoint_load",
                      step=-1 if step is None else int(step)):
        result = _load_checkpoint_impl(directory, target, step=step)
    telemetry.observe("checkpoint.load_s", time.perf_counter() - t0)
    telemetry.count("checkpoint.loads")
    return result


def _load_checkpoint_impl(directory: str, target: Any, *, step: int | None):
    logger = dist.get_logger("tpu_syncbn.checkpoint")
    multi_host = dist.process_count() > 1
    pure_target = _purify(target)
    if multi_host:
        dist.barrier("ckpt-load")
        if step is None:
            from jax.experimental import multihost_utils
            import numpy as np

            mine = np.asarray(_best_step(directory), dtype=np.int32)
            agreed = int(
                multihost_utils.broadcast_one_to_all(
                    mine, is_source=dist.is_master()
                )
            )
            if agreed < 0:
                # master sees nothing usable: fail identically everywhere
                raise FileNotFoundError(
                    f"no loadable checkpoints in {directory!r} on the "
                    "master host"
                )
            step = agreed
    if multi_host and not dist.is_master():
        data = _read_with_retry(_path(directory, step))
        manifest = _read_manifest_with_retry(directory, step)
        if manifest is not None and not _payload_matches(manifest, data):
            raise CheckpointCorruptError(
                f"host {dist.process_index()}: step {step} payload does "
                "not match its manifest (local read corrupt/truncated)"
            )
        pure = serialization.from_bytes(pure_target, data)
        return _unpurify(target, pure), step
    if step is None:
        pure, step = _load_verified_local(directory, pure_target, logger)
        return _unpurify(target, pure), step
    # explicit step: no fallback — the caller asked for THIS state
    steps = available_steps(directory)
    if step not in steps:
        raise FileNotFoundError(
            f"step {step} not in {steps}" if steps
            else f"no checkpoints in {directory!r}"
        )
    with open(_path(directory, step), "rb") as f:
        data = f.read()
    manifest = read_manifest(directory, step)
    if manifest is not None and not _payload_matches(manifest, data):
        raise CheckpointCorruptError(
            f"checkpoint step {step} in {directory!r} fails manifest "
            f"verification (expected {manifest.get('nbytes')} bytes "
            f"sum64={manifest.get('sum64')}, got {len(data)} bytes "
            f"sum64={payload_sum64(data)})"
        )
    try:
        pure = serialization.from_bytes(pure_target, data)
    except Exception as e:
        raise CheckpointCorruptError(
            f"checkpoint step {step} in {directory!r} failed to "
            f"deserialize ({type(e).__name__}: {e})"
        ) from e
    return _unpurify(target, pure), step


def _best_step(directory: str) -> int:
    """Master's choice for multi-host agreement, mirroring the
    single-host fallback walk (:func:`_load_verified_local`): newest
    first, skipping only candidates whose manifest FAILS to certify
    them; a legacy (manifest-less) step is a trusted candidate exactly
    as it is single-host — the same directory must resume to the same
    step regardless of process_count. -1 when every candidate is a
    corrupt manifested checkpoint (or nothing exists)."""
    for step in reversed(available_steps(directory)):
        manifest = read_manifest(directory, step)
        if manifest is None or verify_checkpoint(directory, step):
            return step
    return -1


def _read_with_retry(path: str, attempts: int = 5, delay: float = 0.2) -> bytes:
    """Open ``path`` directly, retrying briefly on FileNotFoundError —
    shared-filesystem attribute caches can lag a peer's just-completed
    rename even though the data is readable."""
    import time

    for i in range(attempts):
        try:
            with open(path, "rb") as f:
                return f.read()
        except FileNotFoundError:
            if i == attempts - 1:
                raise
            time.sleep(delay * (2**i))
    raise AssertionError("unreachable")


def snapshot_to_host(tree: Any) -> Any:
    """Copy-before-donate snapshot: fetch ``tree`` to host memory as
    pure dicts with every leaf an *owned* numpy copy.

    The owning copy matters twice over: (1) the caller's next donated
    train step invalidates the device buffers the snapshot came from;
    (2) on the CPU backend ``jax.device_get`` can return **zero-copy
    views** whose storage a donated step recycles in place — a snapshot
    that merely referenced them would be silently overwritten while the
    background writer serializes it (the corruption
    :class:`AsyncCheckpointer` exists to avoid paying for
    synchronously). Leaves ``device_get`` already materialized as
    numpy-owned arrays (the TPU/GPU case) are kept as-is — no second
    full-state copy on the hot path."""
    import numpy as np

    def own(x):
        if (isinstance(x, np.ndarray) and x.base is None
                and x.flags["OWNDATA"]):
            return x  # numpy allocated this buffer: nothing can recycle it
        return np.array(x, copy=True) if hasattr(x, "__array__") else x

    return jax.tree_util.tree_map(own, jax.device_get(_purify(tree)))


# ---------------------------------------------------------------------------
# weight publication (serve-side versioned hot swap — docs/RESILIENCE.md
# "Zero-downtime publication")


def _pub_path(directory: str, version: int) -> str:
    return os.path.join(directory, f"weights_v{version}.msgpack")


def _pub_manifest_path(directory: str, version: int) -> str:
    return os.path.join(directory, f"weights_v{version}.manifest.json")


def _pointer_path(directory: str) -> str:
    return os.path.join(directory, PUBLISHED_POINTER)


def published_versions(directory: str) -> list[int]:
    """Ascending weight versions present on disk (payload files — some
    may be unverified leftovers; the pointer is the authority)."""
    if not os.path.isdir(directory):
        return []
    out = []
    for name in os.listdir(directory):
        m = _PUB_RE.match(name)
        if m:
            out.append(int(m.group(1)))
    return sorted(out)


def read_published_pointer(directory: str) -> dict | None:
    """The parsed ``published.json`` pointer, or None when absent or
    unreadable (no version has ever been successfully published)."""
    try:
        with open(_pointer_path(directory)) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def published_version(directory: str) -> int | None:
    """The currently published weight version number, or None."""
    ptr = read_published_pointer(directory)
    if ptr is None or not isinstance(ptr.get("version"), int):
        return None
    return ptr["version"]


def read_published_manifest(directory: str, version: int) -> dict | None:
    try:
        with open(_pub_manifest_path(directory, version)) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def _publish_host_tree(
    directory: str, version: int, host_tree: Any, *, keep: int, step=None,
) -> str:
    """The publication write half (already-host-resident pure tree):
    payload + manifest exactly like a checkpoint (atomic, payload before
    manifest), then a **read-back verification** of the just-landed
    payload against its manifest, and only then the atomic
    ``published.json`` pointer flip. A writer killed at ANY byte — or a
    disk that corrupted the payload in flight — leaves the pointer on
    the previous good version; a consumer can never resolve to a
    truncated or bit-flipped publication."""
    os.makedirs(directory, exist_ok=True)
    data = serialization.to_bytes(host_tree)
    _atomic_write(directory, _pub_path(directory, version), data)
    manifest = {
        "format": MANIFEST_FORMAT,
        "version": int(version),
        "nbytes": len(data),
        "sum64": payload_sum64(data),
        "crc32": (zlib.crc32(data) & 0xFFFFFFFF)
        if len(data) <= _CRC32_MAX_BYTES else None,
        "tree_hash": tree_structure_hash(host_tree),
    }
    if step is not None:
        manifest["step"] = int(step)
    _atomic_write(
        directory, _pub_manifest_path(directory, version),
        json.dumps(manifest).encode(),
    )
    # read-back verification: re-read what the filesystem actually holds
    # (not the bytes still in our hands) before making it reachable
    with open(_pub_path(directory, version), "rb") as f:
        landed = f.read()
    if not _payload_matches(manifest, landed):
        telemetry.count("checkpoint.verify_failures")
        raise CheckpointCorruptError(
            f"publication v{version} failed read-back verification in "
            f"{directory!r} (wrote {len(data)} bytes, read back "
            f"{len(landed)}) — pointer NOT updated"
        )
    pointer = {
        "format": MANIFEST_FORMAT,
        "version": int(version),
        "path": os.path.basename(_pub_path(directory, version)),
        "tree_hash": manifest["tree_hash"],
        "nbytes": len(data),
    }
    if step is not None:
        pointer["step"] = int(step)
    _atomic_write(
        directory, _pointer_path(directory), json.dumps(pointer).encode()
    )
    if keep > 0:
        # prune to the newest `keep`, never the version the pointer
        # names (a rollback target must stay loadable); manifest first,
        # same interrupted-prune reasoning as the checkpoint pruner
        current = pointer["version"]
        for old in published_versions(directory)[:-keep]:
            if old == current:
                continue
            with contextlib.suppress(FileNotFoundError):
                os.unlink(_pub_manifest_path(directory, old))
            with contextlib.suppress(FileNotFoundError):
                os.unlink(_pub_path(directory, old))
    return _pub_path(directory, version)


def publish_version(
    directory: str,
    version: int,
    tree: Any,
    *,
    keep: int = 3,
    step: int | None = None,
) -> str | None:
    """Atomically publish ``tree`` as weight version ``version`` —
    master host only (others return None). The pointer flip happens
    only after the payload passes read-back verification against its
    freshly written manifest, so :func:`load_published` either sees the
    previous good version or this one, never a torn write. Latency
    rides ``checkpoint.publish_s`` + ``checkpoint.publishes``."""
    if not dist.is_master():
        return None
    t0 = time.perf_counter()
    with tracing.span("checkpoint_publish", version=int(version)):
        host_tree = jax.device_get(_purify(tree))
        path = _publish_host_tree(
            directory, version, host_tree, keep=keep, step=step
        )
    telemetry.observe("checkpoint.publish_s", time.perf_counter() - t0)
    telemetry.count("checkpoint.publishes")
    return path


def load_published(
    directory: str,
    target: Any,
    *,
    expect_tree_hash: str | None = None,
):
    """Resolve the ``published.json`` pointer and load that weight
    version into ``target``'s structure. Returns ``(tree, version)``.

    Verification is mandatory, not best-effort: a missing manifest, a
    payload failing its checksums, or a deserialization error raises
    :class:`CheckpointCorruptError` — the caller keeps serving its
    current version (there is no silent fallback walk here; the pointer
    names ONE version and a corrupt publication must be *rejected*, not
    papered over). ``expect_tree_hash`` (the consumer's own
    ``tree_structure_hash`` of its template) additionally rejects a
    structurally skewed publication with
    :class:`PublicationSkewError` before deserialization is attempted."""
    ptr = read_published_pointer(directory)
    if ptr is None or not isinstance(ptr.get("version"), int):
        raise FileNotFoundError(
            f"no published version in {directory!r} (missing or "
            f"unreadable {PUBLISHED_POINTER})"
        )
    version = ptr["version"]
    manifest = read_published_manifest(directory, version)
    if manifest is None:
        telemetry.count("checkpoint.verify_failures")
        raise CheckpointCorruptError(
            f"published v{version} in {directory!r} has no readable "
            "manifest — cannot certify the payload"
        )
    if expect_tree_hash is not None \
            and manifest.get("tree_hash") != expect_tree_hash:
        raise PublicationSkewError(
            f"published v{version} tree_hash "
            f"{manifest.get('tree_hash')!r} != expected "
            f"{expect_tree_hash!r} — publisher and server disagree on "
            "the model structure (schema skew)"
        )
    try:
        with open(_pub_path(directory, version), "rb") as f:
            data = f.read()
    except OSError as e:
        telemetry.count("checkpoint.verify_failures")
        raise CheckpointCorruptError(
            f"published v{version} payload unreadable in {directory!r}: "
            f"{e}"
        ) from e
    if not _payload_matches(manifest, data):
        telemetry.count("checkpoint.verify_failures")
        raise CheckpointCorruptError(
            f"published v{version} in {directory!r} fails manifest "
            f"verification (expected {manifest.get('nbytes')} bytes "
            f"sum64={manifest.get('sum64')}, got {len(data)} bytes "
            f"sum64={payload_sum64(data)})"
        )
    pure_target = _purify(target)
    try:
        pure = serialization.from_bytes(pure_target, data)
    except Exception as e:
        raise CheckpointCorruptError(
            f"published v{version} in {directory!r} failed to "
            f"deserialize ({type(e).__name__}: {e})"
        ) from e
    return _unpurify(target, pure), version


class AsyncCheckpointer:
    """Checkpoint writes off the training hot path
    (docs/PERFORMANCE.md).

    ``save()`` runs the *snapshot* synchronously — one batched
    device→host fetch into owned copies (:func:`snapshot_to_host`, the
    copy-before-donate contract) — then hands serialization, the
    integrity manifest (PR 1: sum64/CRC32/tree hash, byte-identical to
    the synchronous path's), the atomic writes, and pruning to ONE
    background thread. The step loop pays the fetch and nothing else:
    steady-state step time stays flat across saves.

    Ordering and durability:

    * writes are processed strictly in ``save()`` order by a single
      worker — manifests certify in submission order, so the
      newest-VERIFIED resume walk (``load_checkpoint``) never sees an
      out-of-order certification;
    * ``max_pending`` bounds host memory (each pending write holds one
      full state snapshot); a ``save()`` past the bound *blocks* until
      the writer drains — backpressure, never silent dropping;
    * ``flush()`` blocks until everything submitted is durable —
      ``runtime.resilience.ResilientLoop`` flushes on EVERY exit path
      (preemption included), so a SIGTERM landing between submit and
      write cannot lose the boundary checkpoint;
    * a background write failure is re-raised at the next ``save()`` or
      ``flush()`` — an async fault must not be a silent one.

    Master-host-only like :func:`save_checkpoint` (other hosts' saves
    are cheap no-ops). Telemetry: ``checkpoint.async_saves`` counter,
    ``checkpoint.async_snapshot_s`` (what the loop actually pays) and
    the shared ``checkpoint.save_s`` (background write latency).
    """

    def __init__(self, *, keep: int = 3, max_pending: int = 2):
        import queue
        import threading

        if max_pending < 1:
            raise ValueError(f"max_pending must be >= 1, got {max_pending}")
        self.keep = keep
        self._queue: Any = queue.Queue(maxsize=max_pending)
        self._errors: list[BaseException] = []
        self._cond = threading.Condition()
        self._pending = 0  # incremented BEFORE enqueue: a flush() that
        # follows a save() can never miss the write in a handoff window
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, name="async-checkpointer", daemon=True
        )
        self._thread.start()

    # -- worker ------------------------------------------------------------

    def _run(self) -> None:
        while True:
            # idle-wait for work by design: close() always enqueues the
            # None sentinel, so this get provably terminates
            item = self._queue.get()  # audit: ok[unbounded_blocking]
            if item is None:
                return
            op, directory, number, host_tree, keep = item
            t0 = time.perf_counter()
            try:
                if op == "publish":
                    with tracing.span("checkpoint_publish",
                                      version=int(number), mode="async"):
                        _publish_host_tree(
                            directory, number, host_tree, keep=keep
                        )
                    telemetry.observe(
                        "checkpoint.publish_s", time.perf_counter() - t0
                    )
                    telemetry.count("checkpoint.publishes")
                else:
                    with tracing.span("checkpoint_save", step=int(number),
                                      mode="async"):
                        _write_host_tree(
                            directory, number, host_tree, keep=keep
                        )
                    telemetry.observe(
                        "checkpoint.save_s", time.perf_counter() - t0
                    )
                    telemetry.count("checkpoint.saves")
            except BaseException as e:  # surface at next save()/flush()
                with self._cond:
                    self._errors.append(e)
            finally:
                with self._cond:
                    self._pending -= 1
                    self._cond.notify_all()

    def _raise_pending_error(self) -> None:
        with self._cond:
            err = self._errors.pop(0) if self._errors else None
        if err is not None:
            raise RuntimeError(
                "async checkpoint write failed in the background"
            ) from err

    # -- public API --------------------------------------------------------

    @property
    def pending(self) -> int:
        """Writes submitted but not yet durable."""
        with self._cond:
            return self._pending

    def save(self, directory: str, step: int, tree: Any,
             *, keep: int | None = None) -> None:
        """Snapshot ``tree`` now (copy-before-donate) and schedule the
        serialized + certified write. Blocks only for the snapshot —
        and for backpressure when ``max_pending`` writes are already
        queued. Raises any error a previous background write hit."""
        self._raise_pending_error()
        if self._closed:
            raise RuntimeError("AsyncCheckpointer is closed")
        if not dist.is_master():
            return
        t0 = time.perf_counter()
        host_tree = snapshot_to_host(tree)
        telemetry.observe(
            "checkpoint.async_snapshot_s", time.perf_counter() - t0
        )
        telemetry.count("checkpoint.async_saves")
        with self._cond:
            self._pending += 1
        # enqueue OUTSIDE the condition: a bounded-queue put may block on
        # backpressure, and the worker needs the condition to drain —
        # blocking here IS the documented max_pending backpressure, and
        # the single worker can only stop via close()'s sentinel (its
        # loop catches BaseException per item), so the put always drains
        self._queue.put(("save", directory, int(step), host_tree,  # audit: ok[unbounded_blocking]
                         self.keep if keep is None else keep))

    def publish(self, directory: str, version: int, tree: Any,
                *, keep: int | None = None) -> None:
        """Snapshot ``tree`` now and schedule an atomic weight
        *publication* (:func:`publish_version`: payload + manifest +
        read-back verification + pointer flip) through the same ordered
        worker as :meth:`save` — so a ``save(step=N)`` followed by a
        ``publish(version=N)`` certifies in submission order and a
        ``flush()`` covers both. Same backpressure, master-host-only,
        and error-surfacing contracts as :meth:`save`."""
        self._raise_pending_error()
        if self._closed:
            raise RuntimeError("AsyncCheckpointer is closed")
        if not dist.is_master():
            return
        t0 = time.perf_counter()
        host_tree = snapshot_to_host(tree)
        telemetry.observe(
            "checkpoint.async_snapshot_s", time.perf_counter() - t0
        )
        with self._cond:
            self._pending += 1
        self._queue.put(("publish", directory, int(version), host_tree,  # audit: ok[unbounded_blocking]
                         self.keep if keep is None else keep))

    def flush(self, timeout: float | None = None) -> bool:
        """Block until every submitted write is durable (or ``timeout``
        seconds pass — returns False on timeout). Re-raises background
        write errors."""
        with self._cond:
            done = self._cond.wait_for(lambda: self._pending == 0, timeout)
        self._raise_pending_error()
        return done

    def close(self, timeout: float | None = None) -> None:
        """Flush, then stop the worker thread. Idempotent. If the flush
        times out (worker wedged on a hung write) the sentinel is
        offered without blocking — honoring the caller's bound — and
        the daemon worker is left to die with the process."""
        import queue

        if self._closed:
            return
        self.flush(timeout)
        self._closed = True
        try:
            self._queue.put_nowait(None)
        except queue.Full:
            return  # wedged mid-write with a full queue: see docstring
        self._thread.join(timeout=5)

    def __enter__(self) -> "AsyncCheckpointer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _read_manifest_with_retry(
    directory: str, step: int, attempts: int = 3, delay: float = 0.2
) -> dict | None:
    """Follower-side manifest read: retries FileNotFoundError like the
    payload read, but resolves to None (legacy checkpoint / still-lagging
    listing) instead of raising — the payload is the authority, the
    manifest an extra check when visible."""
    try:
        data = _read_with_retry(
            _manifest_path(directory, step), attempts=attempts, delay=delay
        )
        return json.loads(data)
    except (OSError, json.JSONDecodeError):
        return None
