"""Collective communication over mesh axes — the TPU-native replacement for
the reference stack's NCCL process-group layer.

The reference recipe's collectives (reference ``README.md:29-35`` selects the
``'nccl'`` backend; the ops its stack actually issues are pinned in SURVEY §5.8):

* ``all_gather(_single)`` — SyncBN forward stats exchange
  (``[torch] nn/modules/_functions.py:74-86``)
* ``all_reduce(SUM)`` — SyncBN backward (``:160-165``) + DDP gradient buckets
* ``broadcast`` — DDP init-time parameter sync
  (``[torch] nn/parallel/distributed.py:1066-1072``)

Here each op is a thin wrapper over ``jax.lax`` named-axis collectives, legal
inside any ``shard_map``/``pmap``-traced function over a mesh axis. XLA lowers
them to AllReduce/AllGather/CollectivePermute HLOs scheduled over ICI/DCN —
compiler-scheduled rather than runtime-issued, which subsumes NCCL stream
management and DDP's bucketing/overlap machinery (the latency-hiding
scheduler overlaps them with compute automatically).

Also hosts :func:`reduce_moments` — the count-weighted cross-replica moment
reduction that is the numerical core of SyncBatchNorm (the TPU-native
equivalent of ``batch_norm_gather_stats_with_counts``,
``[torch] nn/modules/_functions.py:106-115``): replicas contribute
(sum, sumsq, count) and receive exact global (mean, biased var, count),
correct for uneven/empty shards.
"""

from __future__ import annotations

import math
import operator
import threading
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpu_syncbn.compat import axis_size as _compat_axis_size
from tpu_syncbn.obs import numerics as obs_numerics, telemetry
from tpu_syncbn.runtime.distributed import DATA_AXIS

Pytree = Any

#: Running total of trace-time collective payload bytes (every _tally
#: adds here alongside the per-op counters) — the O(1) read that lets
#: DispatchWireTally run on the step loop without snapshotting the
#: registry per dispatch.
_traced_bytes_lock = threading.Lock()
_traced_bytes_total = 0


def traced_bytes_total() -> int:
    """Trace-time collective bytes tallied so far in this process."""
    with _traced_bytes_lock:
        return _traced_bytes_total


def _tally(op: str, tree: Pytree) -> None:
    """Per-op call + estimated-byte counters (``collectives.<op>.calls``
    / ``.bytes``) when telemetry is enabled.

    These count at **trace time**: collectives in this module execute
    while XLA traces the step program, once per compilation, not once
    per step — so the tallies are the per-program collective inventory
    (DS-Sync's "how much does this step synchronize", arxiv 2007.03298).
    Per-execution traffic is this estimate times the step count; the
    payload estimate is the mathematical per-replica input size
    (shape × itemsize), which for an all-reduce equals what ring
    algorithms move within a factor of 2(N-1)/N.

    Tally at the TRANSMISSION site with the array that actually moves:
    byte counts are shape × itemsize of the tallied leaves, so a helper
    that re-packs its input before the wire (``psum_in_groups`` fusing a
    bf16 tree into one f32 payload, the quantized paths below sending
    int8) must tally the packed/quantized payload, not its logical
    input — otherwise the inventory reports the logical itemsize while
    the wire carries a different one."""
    if not telemetry.enabled():
        return
    nbytes = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        try:
            shape = tuple(getattr(leaf, "shape", ()))
            dtype = getattr(leaf, "dtype", None)
            itemsize = np.dtype(dtype).itemsize if dtype is not None else 0
            nbytes += int(math.prod(shape)) * itemsize
        except (TypeError, ValueError):
            continue  # abstract/dynamic leaf: skip, keep the call count
    telemetry.count(f"collectives.{op}.calls")
    telemetry.count(f"collectives.{op}.bytes", nbytes)
    # O(1) running total for DispatchWireTally — reading it per dispatch
    # must not pay a full registry snapshot on the step loop's hot path
    global _traced_bytes_total
    with _traced_bytes_lock:
        _traced_bytes_total += nbytes


def axis_size(axis_name: str = DATA_AXIS) -> int:
    """World size along a mesh axis — the reference's ``world_size``
    (``README.md:33``), available inside the compiled step."""
    return _compat_axis_size(axis_name)


def axis_index(axis_name: str = DATA_AXIS) -> jax.Array:
    """This replica's index along a mesh axis — the reference's ``rank``
    (``README.md:34``), as a traced scalar."""
    return lax.axis_index(axis_name)


def psum(tree: Pytree, axis_name: str = DATA_AXIS) -> Pytree:
    """Sum every leaf across the axis: ``dist.all_reduce(SUM)``
    (as used by SyncBN backward, ``[torch] nn/modules/_functions.py:160-165``)."""
    _tally("psum", tree)
    return lax.psum(tree, axis_name)


def pmean(tree: Pytree, axis_name: str = DATA_AXIS) -> Pytree:
    """Mean every leaf across the axis — all_reduce followed by the divide
    DDP's reducer applies to gradients (``[torch] nn/parallel/distributed.py``
    Reducer grad averaging)."""
    _tally("pmean", tree)
    return lax.pmean(tree, axis_name)


def pmax(tree: Pytree, axis_name: str = DATA_AXIS) -> Pytree:
    """Elementwise max across the axis (all_reduce(MAX))."""
    _tally("pmax", tree)
    return lax.pmax(tree, axis_name)


def pmin(tree: Pytree, axis_name: str = DATA_AXIS) -> Pytree:
    """Elementwise min across the axis (all_reduce(MIN))."""
    _tally("pmin", tree)
    return lax.pmin(tree, axis_name)


def all_gather(
    tree: Pytree,
    axis_name: str = DATA_AXIS,
    *,
    axis: int = 0,
    tiled: bool = False,
) -> Pytree:
    """Gather every replica's leaf along a new (or tiled) leading axis:
    ``dist.all_gather_into_tensor`` (SyncBN forward stats exchange,
    ``[torch] nn/modules/_functions.py:74-77``)."""
    _tally("all_gather", tree)
    return lax.all_gather(tree, axis_name, axis=axis, tiled=tiled)


def broadcast(tree: Pytree, src: int = 0, axis_name: str = DATA_AXIS) -> Pytree:
    """Every replica receives replica ``src``'s value: ``dist.broadcast``
    (DDP init-time param/buffer sync from rank 0,
    ``[torch] nn/parallel/distributed.py:1066-1072``).

    SPMD formulation: gather all replicas' values and select ``src``'s.
    XLA folds the gather+index; for the init-time use the cost is a one-off.

    ``axis_name`` may be a tuple of mesh axes (a composed layout such as
    ``('data', 'fsdp')``): ``src`` is then a linear rank decomposed
    row-major over the axes in the order given, and the masked psum runs
    over all of them at once.
    """
    _tally("broadcast", tree)
    size = int(_compat_axis_size(axis_name))  # static at trace time
    if not -size <= src < size:
        raise ValueError(
            f"broadcast src={src} out of range for axis {axis_name!r} of size {size}"
        )
    src = src % size
    # psum of the masked value: no world_size× gather buffer, one AllReduce.
    if isinstance(axis_name, (tuple, list)):
        axes = tuple(axis_name)
        sizes = [int(_compat_axis_size(a)) for a in axes]
        coords, rem = [], src
        for n in reversed(sizes):
            coords.append(rem % n)
            rem //= n
        coords.reverse()
        is_src = jnp.bool_(True)
        for a, c in zip(axes, coords):
            is_src = jnp.logical_and(is_src, lax.axis_index(a) == c)
        psum_axes: object = axes
    else:
        is_src = lax.axis_index(axis_name) == src
        psum_axes = axis_name

    def one(x):
        return lax.psum(jnp.where(is_src, x, jnp.zeros_like(x)), psum_axes)

    return jax.tree_util.tree_map(one, tree)


def pcast_varying(tree: Pytree, axis_name: str = DATA_AXIS) -> Pytree:
    """Idempotently cast every leaf to device-varying over ``axis_name``
    (``lax.pcast`` raises on an already-varying input, and mixed trees are
    common: SyncBN stats come out of their psum unvarying while plain-BN
    stats stay varying). Shared home for the VMA-cast used by the
    trainers and the sequence-parallel scan carries — one place to adapt
    if jax's vma/pcast API shifts again."""

    axes = tuple(axis_name) if isinstance(axis_name, (tuple, list)) else (axis_name,)

    def leaf(x):
        for a in axes:
            if a not in getattr(jax.typeof(x), "vma", frozenset()):
                x = lax.pcast(x, a, to="varying")
        return x

    return jax.tree_util.tree_map(leaf, tree)


def ppermute(
    tree: Pytree, perm: list[tuple[int, int]], axis_name: str = DATA_AXIS
) -> Pytree:
    """Point-to-point ring/permutation sends (CollectivePermute over ICI).
    No reference analogue in the recipe; exposed for ring-style algorithms."""
    _tally("ppermute", tree)
    return lax.ppermute(tree, axis_name, perm)


def all_to_all(
    tree: Pytree,
    axis_name: str = DATA_AXIS,
    *,
    split_axis: int = 0,
    concat_axis: int = 0,
    tiled: bool = True,
) -> Pytree:
    """All-to-all resharding (sequence/expert-parallel building block).
    Not used by the reference recipe; exposed as the mesh-ready extension
    point SURVEY §2 calls for."""
    _tally("all_to_all", tree)
    return lax.all_to_all(
        tree, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled
    )


def reduce_scatter(
    x: jax.Array, axis_name: str = DATA_AXIS, *, scatter_dimension: int = 0
) -> jax.Array:
    """Sum across the axis, then shard the result along ``scatter_dimension``
    (ReduceScatter HLO). The building block for ZeRO-style sharded optimizer
    states (out of reference scope, SURVEY §2, but mesh-ready)."""
    _tally("reduce_scatter", x)
    return lax.psum_scatter(
        x, axis_name, scatter_dimension=scatter_dimension, tiled=True
    )


def _prime_factors(n: int) -> list:
    """Ascending prime factorization (with multiplicity); empty for 1."""
    fs, f = [], 2
    while n > 1:
        while n % f == 0:
            fs.append(f)
            n //= f
        f += 1 if f == 2 else 2
    return fs


def _stage_perm(
    groups: tuple, stride: int, f: int, k: int
) -> list:
    """(source, dest) ppermute pairs for shift ``k`` of a radix-``f``
    mixed-radix butterfly stage at ``stride``, within equal-size replica
    ``groups`` (arbitrary membership): each member receives from the
    group member whose position digit at this stride is ``k`` ahead
    (mod f). Contiguous groups are the special case
    ``groups[i] = range(i*g, (i+1)*g)``."""
    perm = []
    for g in groups:
        for pos, rank in enumerate(g):
            d = (pos // stride) % f
            src_pos = pos + (((d + k) % f) - d) * stride
            perm.append((g[src_pos], rank))
    return perm


def normalize_group_spec(group_size):
    """Canonicalize a ``group_size`` value: an int-like scalar stays an
    int (contiguous groups of that size); anything else must be a rank
    partition and becomes hashable nested tuples of exact ints
    (``operator.index`` — a non-integral rank like 1.9 is an error, not
    a silent truncation). ONE normalization shared by ``SyncBatchNorm``,
    ``convert_sync_batchnorm`` and ``psum_in_groups`` so the value
    hashes/compares identically across jit cache keys. ``None`` passes
    through (full-world sync)."""
    if group_size is None:
        return None
    if isinstance(group_size, bool):
        raise ValueError(f"group_size must be an int or a rank "
                         f"partition, got {group_size!r}")
    try:
        return operator.index(group_size)  # int, np.integer, ...
    except TypeError:
        pass
    try:
        return tuple(tuple(operator.index(r) for r in g)
                     for g in group_size)
    except (TypeError, ValueError) as e:
        raise ValueError(
            "group_size must be an int or a sequence of rank "
            f"sequences of exact integers, got {group_size!r}"
        ) from e


def _validate_partition(world: int, groups: tuple) -> tuple:
    """Check a normalized rank partition: every rank in [0, world)
    exactly once, no empty groups. Returns it unchanged."""
    flat = [r for g in groups for r in g]
    if any(not g for g in groups) or sorted(flat) != list(range(world)):
        raise ValueError(
            f"groups {groups!r} must partition ranks 0..{world - 1}: "
            "every rank exactly once, no empty groups (torch builds its "
            "process groups under the same constraint — "
            "[torch] distributed/distributed_c10d.py new_group)"
        )
    return groups


def psum_in_groups(
    tree: Pytree, axis_name: str, group_size
) -> Pytree:
    """Sum within replica subgroups along the axis — the TPU form of
    torch's ``process_group`` scoping (e.g. SyncBN synced within a node
    rather than the whole world).

    ``group_size`` is either

    * an ``int`` g: contiguous groups ``[0..g), [g..2g), ...`` (g must
      divide the axis size) — the common topology-shaped case, or
    * an explicit partition — a sequence of rank sequences covering
      every rank exactly once, e.g. ``((0, 3, 5, 6), (1, 2, 4, 7))`` —
      matching the arbitrary rank sets torch's ``process_group``
      accepts (``[torch] nn/modules/batchnorm.py:706``).

    ``lax.psum(axis_index_groups=...)`` is unimplemented under shard_map's
    VMA checker (jax 0.9: the type system cannot express a group-varying
    reduce result), so equal-size groups take a **mixed-radix butterfly**
    of ``ppermute``s: the group size is factorized and each prime factor
    ``f`` contributes one stage of ``f - 1`` shifted exchanges —
    O(payload · Σ(fᵢ − 1)) traffic for ANY group size (log₂ g messages
    when g is a power of two, where radix-2 stages reduce to the classic
    recursive-doubling XOR butterfly), never an O(world) gather. All
    perms are compile-time constants, VMA-legal CollectivePermute HLOs;
    for contiguous groups XLA schedules them over the direct ICI
    neighbor links the groups sit on (arbitrary-membership groups keep
    the same message count but may route across the mesh). The whole
    tree moves as ONE fused payload, keeping the "one collective per BN
    layer" property.

    Unequal-size groups cannot share one butterfly schedule (stage
    counts differ per group), so they fall back to a masked all-gather:
    one AllGather of the fused payload plus a per-replica constant
    membership row — O(world · payload) traffic, the same order as the
    reference's SyncBN stats exchange (``all_gather`` of every rank's
    stats, ``[torch] nn/modules/_functions.py:74-86``), so the fallback
    is never worse than the semantics it emulates.

    Latency note: a large *prime* factor f contributes f-1 dependent
    exchange rounds (ring-like latency), so e.g. g=13 pays 12 round
    trips where a gather would pay one. Real stat-sync groups are
    topology-shaped (2/4/8 replicas per host, occasionally 3/6), where
    Σ(fᵢ−1) ≤ 4 — the design targets those; for exotic large-prime
    groups prefer ``group_size=None`` (full-world psum) or an explicit
    unequal partition (which takes the gather path).
    """
    world = _compat_axis_size(axis_name)
    group_size = normalize_group_spec(group_size)
    if isinstance(group_size, int):
        if group_size < 1 or world % group_size:
            raise ValueError(
                f"group_size {group_size} must divide axis size {world}"
            )
        if group_size == world:
            return lax.psum(tree, axis_name)
        groups = tuple(
            tuple(range(i, i + group_size))
            for i in range(0, world, group_size)
        )
    else:
        groups = _validate_partition(world, group_size)
        if len(groups) == 1:
            return lax.psum(tree, axis_name)

    # one fused payload for the whole tree
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    flat = jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in leaves])

    sizes = {len(g) for g in groups}
    if len(sizes) == 1:
        stride = 1
        for f in _prime_factors(sizes.pop()):
            # radix-f stage: each member sums the f values whose
            # mixed-radix position digit at this stride differs — after
            # the stage, every member holds the sum over its digit
            # group; after all stages, the full group sum
            acc = flat
            for k in range(1, f):
                perm = _stage_perm(groups, stride, f, k)
                # wire payload is the fused f32 vector, NOT the caller's
                # tree — tally what each exchange actually transmits
                _tally("ppermute", flat)
                acc = acc + lax.ppermute(flat, axis_name, perm)
            flat = acc
            stride *= f
        summed = flat
    else:
        # masked gather: every replica sees every row, sums its group's
        _tally("all_gather", flat)  # wire dtype: the fused f32 payload
        gathered = lax.all_gather(flat, axis_name)  # (world, payload)
        member = [[0.0] * world for _ in range(world)]
        for g in groups:
            for i in g:
                for j in g:
                    member[i][j] = 1.0
        row = jnp.take(
            jnp.asarray(member, jnp.float32),
            lax.axis_index(axis_name), axis=0,
        )
        # elementwise mask + sum, NOT a matmul: jnp.matmul at default
        # precision runs bf16 multiply passes on TPU, which would break
        # the f32 accumulation the payload was cast to float32 for
        summed = (row[:, None] * gathered).sum(0)

    out = []
    offset = 0
    for l in leaves:
        n = l.size
        out.append(summed[offset : offset + n].reshape(l.shape).astype(l.dtype))
        offset += n
    return jax.tree_util.tree_unflatten(treedef, out)


def ring_all_reduce(
    x: jax.Array, axis_name: str = DATA_AXIS
) -> jax.Array:
    """Bandwidth-optimal ring all-reduce built from ``ppermute`` steps —
    the explicit form of what NCCL's ring kernels (reference ``'nccl'``
    backend, ``README.md:31``) and XLA's AllReduce do internally.

    reduce-scatter phase: N-1 neighbor hops, each accumulating one 1/N
    chunk; all-gather phase: N-1 hops circulating the finished chunks.
    Total traffic per device: 2·(N-1)/N · payload — the ring optimum.

    ``lax.psum`` (one AllReduce HLO that XLA schedules over ICI) is the
    production path; this exists to (a) pin the ring algebra with tests,
    (b) serve as the template for ring-style long-context algorithms
    (ring attention passes KV blocks around the same neighbor cycle
    while overlapping compute — SURVEY §5.7's extension point).
    """
    n = _compat_axis_size(axis_name)
    if n == 1:
        return x
    orig_shape = x.shape
    flat = jnp.ravel(x)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    chunks = flat.reshape(n, -1)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    me = lax.axis_index(axis_name)

    # reduce-scatter: at step s device ``me`` receives the partial sum of
    # chunk (me - s) from its left neighbor and adds its own copy; after
    # N-1 steps it owns the complete sum of chunk (me + 1) % n
    acc = jnp.take(chunks, me, axis=0)
    for s in range(1, n):
        _tally("ppermute", acc)  # each hop moves one 1/N chunk
        acc = lax.ppermute(acc, axis_name, fwd)
        acc = acc + jnp.take(chunks, (me - s) % n, axis=0)
    # all-gather: circulate each finished chunk around the ring
    gathered = [acc]
    cur = acc
    for _ in range(n - 1):
        _tally("ppermute", cur)
        cur = lax.ppermute(cur, axis_name, fwd)
        gathered.append(cur)
    # device me received chunk (me - s + 1) % n at gather step s; restore
    # index order: out[j] = gathered[(me + 1 - j) % n]
    order = jnp.stack(gathered)  # (n, chunk)
    idx = (me + 1 - jnp.arange(n)) % n
    out = jnp.take(order, idx, axis=0).reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(orig_shape)


def reduce_moments(
    local_sum: jax.Array,
    local_sumsq: jax.Array,
    local_count: jax.Array,
    axis_name: str = DATA_AXIS,
    *,
    group_size: int | tuple | None = None,
    mode: str = "none",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Count-weighted global moments from per-replica partial sums.

    The numerical heart of SyncBatchNorm. The reference all_gathers per-rank
    ``[mean, invstd, count]`` and recombines with
    ``batch_norm_gather_stats_with_counts``
    (``[torch] nn/modules/_functions.py:41-115``) precisely because shards
    may be uneven or empty (``:50-57``). Summing raw (sum, sumsq, count)
    with a single fused ``psum`` is algebraically identical, needs one
    collective instead of an all_gather + recombine, and is exact for
    empty shards (they contribute zeros, matching ``:195-205``).

    ``mode`` (default ``"none"`` — stats stay exact fp32) opts the
    (sum, sumsq) payload into a lossy wire dtype via
    :func:`compressed_psum`; the **count always rides fp32** — it feeds
    the safe-divide and the empty-shard semantics, and quantizing an
    integer census would corrupt uneven-shard correctness for a handful
    of saved bytes. Lossy stats cannot be scoped to subgroups
    (``group_size``): the butterfly path re-fuses payloads at f32, so
    combining the two flags raises instead of silently un-compressing.

    Args:
      local_sum:   per-channel sum of x over this replica's local elements.
      local_sumsq: per-channel sum of x² over this replica's local elements.
      local_count: scalar (or per-channel) number of local elements.

    Returns:
      (global_mean, global_biased_var, global_count). Variance is the
      *biased* (1/N) variance — what BN normalizes with; the unbiased
      running-var correction is the caller's job (see ops.batch_norm).
    """
    check_compress_mode(mode)
    triple = (local_sum, local_sumsq, local_count)
    if group_size is not None:
        if isinstance(axis_name, (tuple, list)):
            raise ValueError(
                "group-scoped SyncBN stats need a single stat axis — the "
                "butterfly group reduction is 1-D; a composed layout "
                f"syncs over {tuple(axis_name)}"
            )
        if mode != "none":
            raise ValueError(
                "compressed SyncBN stats (mode="
                f"{mode!r}) cannot be combined with group_size="
                f"{group_size!r}: the group butterfly re-fuses payloads "
                "at f32 — sync the full axis or keep stats exact"
            )
        total_sum, total_sumsq, total_count = psum_in_groups(
            triple, axis_name, group_size
        )
    elif mode != "none":
        total_sum, total_sumsq = compressed_psum(
            (local_sum, local_sumsq), axis_name, mode=mode
        )
        total_count = psum(local_count, axis_name)
    else:
        total_sum, total_sumsq, total_count = psum(triple, axis_name)
    mean, var = moments_from_stats(total_sum, total_sumsq, total_count)
    # numerics drift monitor (ISSUE 13): this replica's batch moments vs
    # the just-synced global ones — local arithmetic after the existing
    # psum, traced only while a trainer's monitor collector is active
    obs_numerics.record_bn_skew(
        local_sum, local_sumsq, local_count, mean, var
    )
    return mean, var, total_count


def moments_from_stats(
    s: jax.Array, sq: jax.Array, count: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """(mean, biased var) from raw partial sums; safe for count==0, and
    clamps the tiny negative values that cancellation in ``sumsq - n·mean²``
    can produce. Single home for this math — both the local path
    (ops.batch_norm) and the cross-replica path above use it."""
    safe = jnp.maximum(count, 1.0)
    mean = s / safe
    var = jnp.maximum(sq / safe - mean * mean, 0.0)
    return mean, var


# ---------------------------------------------------------------------------
# compressed collectives (EQuARX-style quantized all-reduce, arxiv
# 2506.17615; DS-Sync shuffle-sharding, arxiv 2007.03298)

#: Wire-compression modes accepted by every ``compressed_*`` entry point
#: (and the trainers' ``compress=``): ``"none"`` exact fp32, ``"bf16"``
#: dtype-cast (2 B/elem), ``"int8"`` chunk-quantized (1 B/elem + one
#: fp32 scale/zero-point pair per chunk).
COMPRESS_MODES = ("none", "bf16", "int8")

#: Elements per quantization chunk: one (scale, zero-point) pair is
#: shared by this many consecutive elements of the fused payload. 256
#: keeps the fp32 side-channel at 8/256 ≈ 3% of the int8 payload while
#: bounding the blast radius of one outlier element to its own chunk.
DEFAULT_CHUNK_ELEMS = 256


def check_compress_mode(mode: str) -> str:
    if mode not in COMPRESS_MODES:
        raise ValueError(
            f"compression mode must be one of {COMPRESS_MODES}, got {mode!r}"
        )
    return mode


def _tally_compressed(logical_bytes: int, wire_bytes: int) -> None:
    """Trace-time compression accounting (docs/OBSERVABILITY.md):
    ``collectives.compressed_bytes`` counts what the lossy payloads put
    on the wire; the gauge holds logical/wire for the most recent
    compressed collective. The underlying psum/pmax calls tally their
    own per-op bytes at the wire dtype as usual."""
    if not telemetry.enabled():
        return
    telemetry.count("collectives.compressed_bytes", int(wire_bytes))
    telemetry.count(
        "collectives.compressed_saved_bytes",
        max(0, int(logical_bytes) - int(wire_bytes)),
    )
    if wire_bytes:
        telemetry.set_gauge(
            "collectives.compression_ratio", logical_bytes / wire_bytes
        )


def _nbytes(leaves) -> int:
    return sum(
        int(math.prod(tuple(l.shape))) * np.dtype(l.dtype).itemsize
        for l in leaves
    )


def _split_float_leaves(tree: Pytree):
    """(treedef, float-leaf list, float index list, all leaves): the
    compressed paths quantize floating leaves and move anything else
    (int flags, counters) through an exact psum."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    fidx = [i for i, l in enumerate(leaves)
            if jnp.issubdtype(jnp.dtype(l.dtype), jnp.floating)]
    return treedef, [leaves[i] for i in fidx], fidx, leaves


def _fuse_f32(leaves) -> jax.Array:
    """Fuse leaves into ONE flat f32 payload (quantization chunks then
    span leaf boundaries — per-chunk ranges stay local to 256 elements
    regardless of layer shapes)."""
    parts = [jnp.ravel(l).astype(jnp.float32) for l in leaves]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _unfuse(flat: jax.Array, like_leaves, *, cast: bool = True):
    out, offset = [], 0
    for l in like_leaves:
        n = int(math.prod(tuple(l.shape)))
        piece = flat[offset:offset + n].reshape(tuple(l.shape))
        out.append(piece.astype(l.dtype) if cast else piece)
        offset += n
    return out


def _reassemble(treedef, leaves, fidx, freduced, exact):
    """Re-interleave the compressed-reduced float leaves and the
    exactly-reduced non-float leaves back into the original tree order —
    ONE implementation shared by :func:`compressed_psum` and
    :func:`ef_compressed_pmean` so the interleave can't drift between
    them."""
    out = list(leaves)
    fset = set(fidx)
    for i, s in zip(fidx, freduced):
        out[i] = s
    it = iter(exact)
    for i in range(len(out)):
        if i not in fset:
            out[i] = next(it)
    return jax.tree_util.tree_unflatten(treedef, out)


def _int8_qparams(
    blocks: jax.Array, axis_name: str, world: int
) -> tuple[jax.Array, jax.Array, jax.Array, int]:
    """Shared-range asymmetric int8 quantization parameters for chunked
    ``blocks`` (n_chunks, chunk).

    The range is the WORLD range (one tiny fp32 ``pmax`` of the per-chunk
    (-min, max) pairs), so every replica quantizes on the same grid and
    the int8 payloads sum EXACTLY on the wire: per-element magnitudes
    are budgeted to ``qmax = 127 // world``, hence a world-sum of
    ``world × qmax ≤ 127`` — no overflow, and ``psum`` of the int8
    payload is a legal s8 AllReduce whose result is bit-defined. The
    log2(world) bits the budget costs are exactly what error feedback
    (:func:`ef_compressed_pmean`) recovers across steps.

    The budget vanishes at ``world > 127`` (``127 // world == 0``), so
    int8 mode refuses such axes instead of letting a floored qmax wrap
    the s8 accumulator — use ``"bf16"`` there, or reduce hierarchically
    in subgroups."""
    if world > 127:
        raise ValueError(
            f"int8 compression supports axis sizes up to 127, got "
            f"{world}: the no-overflow element budget 127 // world is "
            "zero, so world-sums would wrap int8 — use mode='bf16'"
        )
    n = blocks.shape[0]
    lmin = blocks.min(axis=1)
    lmax = blocks.max(axis=1)
    stats = pmax(jnp.concatenate([-lmin, lmax]), axis_name)
    gmin, gmax = -stats[:n], stats[n:]
    zp = ((gmax + gmin) * 0.5)[:, None]
    half = ((gmax - gmin) * 0.5)[:, None]
    qmax = 127 // world
    scale = jnp.where(half > 0, half / qmax, 1.0)
    q = jnp.clip(
        jnp.round((blocks - zp) / scale), -qmax, qmax
    ).astype(jnp.int8)
    if obs_numerics.active():
        # compression-health monitor (ISSUE 13): fraction of elements
        # sitting at the clip boundary ±qmax — a chunk whose mass pins
        # the shared range edge is saturating, not quantizing. Traced
        # only under an active monitor collector (local arithmetic).
        at_limit = (jnp.abs(q.astype(jnp.int32)) >= qmax)
        obs_numerics.record(
            "clip_fraction", jnp.mean(at_limit.astype(jnp.float32))
        )
    return q, scale, zp, qmax


def _record_int8_headroom(sumq: jax.Array) -> None:
    """Compression-health monitor (ISSUE 13): shared-range overflow
    headroom of a world-summed int8 payload — 1 − max|Σq|/127. The
    ``127 // world`` element budget guarantees this stays ≥ 0; a value
    approaching 0 means the budget is fully consumed and any future
    world growth would wrap the s8 accumulator. Local arithmetic on the
    already-reduced payload; traced only under an active collector."""
    if obs_numerics.active():
        obs_numerics.record(
            "overflow_headroom",
            1.0 - jnp.max(jnp.abs(sumq.astype(jnp.float32))) / 127.0,
        )


def _chunk_pad(flat: jax.Array, chunk: int) -> jax.Array:
    pad = (-flat.size) % chunk
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat


def compressed_psum(
    tree: Pytree,
    axis_name: str = DATA_AXIS,
    *,
    mode: str,
    chunk_size: int = DEFAULT_CHUNK_ELEMS,
) -> Pytree:
    """All-reduce with a compressed wire dtype — everything happens
    inside the compiled step, so XLA schedules one quantize → AllReduce
    → dequantize chain with no host involvement (EQuARX's framing:
    compression as part of the collective, arxiv 2506.17615).

    * ``"none"``  — plain exact :func:`psum` (one code path for callers).
    * ``"bf16"``  — leaves cast to bfloat16 for the wire, summed in
      bf16, cast back: 2× fewer bytes, exact when the addends and sums
      are bf16-representable.
    * ``"int8"``  — float leaves fused into one flat payload, chunk-wise
      asymmetric quantization (shared world range per chunk via one tiny
      fp32 ``pmax``; see :func:`_int8_qparams` for the overflow budget),
      s8 AllReduce, dequantize: ~4× fewer bytes.

    Non-float leaves (counts, flags) always ride an exact psum. Lossy
    modes are *opt-in by signature* — there is no lossy default anywhere
    in the package (the ``lossy_default_mode`` lint rule pins that), and
    the divergence guard's pmin/finiteness collectives never route
    through here."""
    check_compress_mode(mode)
    if mode == "none":
        return psum(tree, axis_name)
    treedef, fleaves, fidx, leaves = _split_float_leaves(tree)
    if not fleaves:
        return psum(tree, axis_name)
    world = _compat_axis_size(axis_name)
    logical = _nbytes(fleaves)
    exact = [l for i, l in enumerate(leaves) if i not in set(fidx)]
    if exact:
        exact = psum(exact, axis_name)
    if mode == "bf16":
        cast = [l.astype(jnp.bfloat16) for l in fleaves]
        _tally_compressed(logical, _nbytes(cast))
        summed = psum(cast, axis_name)
        fsummed = [s.astype(l.dtype) for s, l in zip(summed, fleaves)]
    else:  # int8
        flat = _chunk_pad(_fuse_f32(fleaves), chunk_size)
        blocks = flat.reshape(-1, chunk_size)
        q, scale, zp, _ = _int8_qparams(blocks, axis_name, world)
        # wire = s8 payload + the fp32 (-min, max) pair per chunk the
        # range pmax moves (8 B/chunk) — matches the traced contract
        _tally_compressed(logical, q.size + 8 * q.shape[0])
        sumq = psum(q, axis_name)
        _record_int8_headroom(sumq)
        summed_flat = (
            scale * sumq.astype(jnp.float32) + world * zp
        ).reshape(-1)
        fsummed = _unfuse(summed_flat, fleaves)
    return _reassemble(treedef, leaves, fidx, fsummed, exact)


def compressed_pmean(
    tree: Pytree,
    axis_name: str = DATA_AXIS,
    *,
    mode: str,
    chunk_size: int = DEFAULT_CHUNK_ELEMS,
) -> Pytree:
    """:func:`compressed_psum` followed by the world-size divide — the
    compressed form of DDP's gradient averaging. The divide happens
    post-dequantize in the leaf dtype (for ``world`` a power of two it
    is exact, so the bf16 parity pin holds through the mean)."""
    world = _compat_axis_size(axis_name)
    summed = compressed_psum(
        tree, axis_name, mode=mode, chunk_size=chunk_size
    )
    # plain division, exactly like lax.pmean: float leaves keep their
    # dtype (a weak-typed divisor), integer leaves promote to the float
    # mean — casting back to int would silently truncate counts
    return jax.tree_util.tree_map(lambda s: s / world, summed)


def init_error_feedback(tree: Pytree) -> Pytree:
    """Zero residual matching ``tree``'s float leaves (f32, same shapes;
    non-float leaves carry a zero-size placeholder so the residual tree
    keeps the gradient tree's structure)."""
    def zero(l):
        if jnp.issubdtype(jnp.dtype(l.dtype), jnp.floating):
            return jnp.zeros(tuple(l.shape), jnp.float32)
        return jnp.zeros((0,), jnp.float32)
    return jax.tree_util.tree_map(zero, tree)


def ef_compressed_pmean(
    tree: Pytree,
    residual: Pytree,
    axis_name: str = DATA_AXIS,
    *,
    mode: str,
    chunk_size: int = DEFAULT_CHUNK_ELEMS,
) -> tuple[Pytree, Pytree]:
    """Error-feedback compressed gradient mean (EF-SGD / 1-bit-Adam
    lineage): each replica reduces ``p = g + e`` instead of ``g`` and
    re-captures ``e' = p − C(p)`` — its own quantization error — so
    compression error does NOT accumulate across steps (it is re-sent
    until it lands). Returns ``(mean over replicas of C(p), e')``.

    ``residual`` is per-replica state (every replica's error differs);
    the trainers store it inside ``opt_state`` exactly like the PR 1
    divergence-guard state, so it persists through checkpoints, rides
    fused-scan carries, and is rolled back with everything else on a
    guarded non-finite step. ``mode="none"`` degrades to the exact
    :func:`pmean` with an untouched residual."""
    check_compress_mode(mode)
    if mode == "none":
        return pmean(tree, axis_name), residual
    treedef, fleaves, fidx, leaves = _split_float_leaves(tree)
    if not fleaves:
        return pmean(tree, axis_name), residual
    world = _compat_axis_size(axis_name)
    res_leaves = jax.tree_util.tree_leaves(residual)
    if len(res_leaves) != len(leaves):
        raise ValueError(
            f"residual tree has {len(res_leaves)} leaves, expected "
            f"{len(leaves)} (init with init_error_feedback)"
        )
    fres = [res_leaves[i] for i in fidx]
    p = [g.astype(jnp.float32) + r for g, r in zip(fleaves, fres)]
    logical = _nbytes(fleaves)
    exact = [l for i, l in enumerate(leaves) if i not in set(fidx)]
    if exact:
        exact = pmean(exact, axis_name)
    if mode == "bf16":
        cast = [x.astype(jnp.bfloat16) for x in p]
        _tally_compressed(logical, _nbytes(cast))
        summed = psum(cast, axis_name)
        fmean = [
            (s.astype(jnp.float32) / world).astype(l.dtype)
            for s, l in zip(summed, fleaves)
        ]
        new_res = [x - c.astype(jnp.float32) for x, c in zip(p, cast)]
    else:  # int8
        flat = _chunk_pad(_fuse_f32(p), chunk_size)
        blocks = flat.reshape(-1, chunk_size)
        q, scale, zp, _ = _int8_qparams(blocks, axis_name, world)
        _tally_compressed(logical, q.size + 8 * q.shape[0])
        own = scale * q.astype(jnp.float32) + zp  # this replica's C(p)
        res_flat = (blocks - own).reshape(-1)
        sumq = psum(q, axis_name)
        _record_int8_headroom(sumq)
        mean_flat = (
            (scale * sumq.astype(jnp.float32) + world * zp) / world
        ).reshape(-1)
        fmean = _unfuse(mean_flat, fleaves)
        new_res = _unfuse(res_flat, p, cast=False)
    res_out = list(res_leaves)
    for i, r in zip(fidx, new_res):
        res_out[i] = r
    return (
        _reassemble(treedef, leaves, fidx, fmean, exact),
        jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(residual), res_out
        ),
    )


def compressed_reduce_scatter(
    x: jax.Array,
    axis_name: str = DATA_AXIS,
    *,
    mode: str,
    want_residual: bool = False,
) -> tuple[jax.Array, jax.Array | None]:
    """Compressed ReduceScatter for the ZeRO path: ``x`` is a flat
    vector whose length divides by the world size (the ``FlatLayout``
    invariant); returns ``(summed local shard as f32, residual)``.

    int8 quantizes per scatter shard (the chunk boundaries ARE the
    shard boundaries, so each device dequantizes its own shard with one
    locally-selected scale/zero-point pair); the same shared-range
    overflow budget as :func:`compressed_psum` makes the s8
    ReduceScatter exact on the wire. ``want_residual`` additionally
    returns this replica's full-size compression error (f32, shape of
    ``x``) for error feedback — under ZeRO the residual is inherently
    per-replica and full-size (1× params in f32 per device; EF's known
    memory cost)."""
    check_compress_mode(mode)
    world = _compat_axis_size(axis_name)
    if x.size % world:
        raise ValueError(
            f"payload size {x.size} must divide by the axis size {world}"
        )
    xf = x.astype(jnp.float32)
    if mode == "none":
        return reduce_scatter(xf, axis_name), (
            jnp.zeros_like(xf) if want_residual else None
        )
    logical = xf.size * 4
    if mode == "bf16":
        cast = xf.astype(jnp.bfloat16)
        _tally_compressed(logical, cast.size * 2)
        shard = reduce_scatter(cast, axis_name).astype(jnp.float32)
        res = xf - cast.astype(jnp.float32) if want_residual else None
        return shard, res
    # int8: one quantization chunk per scatter shard
    blocks = xf.reshape(world, -1)
    q, scale, zp, _ = _int8_qparams(blocks, axis_name, world)
    _tally_compressed(logical, q.size + 8 * world)
    sumq = reduce_scatter(q.reshape(-1), axis_name)
    _record_int8_headroom(sumq)
    me = lax.axis_index(axis_name)
    s_me = jnp.take(scale[:, 0], me)
    zp_me = jnp.take(zp[:, 0], me)
    shard = s_me * sumq.astype(jnp.float32) + world * zp_me
    res = None
    if want_residual:
        own = scale * q.astype(jnp.float32) + zp
        res = (blocks - own).reshape(-1)
    return shard, res


def shuffle_sharded_psum(
    tree: Pytree,
    axis_name: str = DATA_AXIS,
    *,
    num_shards: int | None = None,
    mode: str = "none",
    chunk_size: int = DEFAULT_CHUNK_ELEMS,
) -> Pytree:
    """DS-Sync-style shuffle-sharded all-reduce for large trees (arxiv
    2007.03298): the fused payload is partitioned into ``num_shards``
    shards, and each shard is reduced by its own mixed-radix butterfly
    of ``ppermute``s built over a DIFFERENT rank ordering (the full-world
    group rotated by the shard index, through the same
    :func:`_stage_perm` machinery as :func:`psum_in_groups`). Every
    shard's exchange schedule therefore uses different neighbor links at
    each stage — the divide-and-shuffle idea: same total bytes as one
    butterfly, but the per-stage traffic spreads across the torus links
    instead of serializing on one ring, which is what helps when the
    tree is large enough to be bandwidth-bound on a single schedule.

    Composes with the wire modes: ``"bf16"`` runs the butterflies on the
    bf16 payload; ``"int8"`` quantizes once up front (shared world range,
    the usual ``127 // world`` element budget, so int8 partial sums stay
    exact through every stage) and dequantizes once at the end.

    Exact for ``"none"`` (pinned against ``lax.psum``); the result is
    numerically identical on every replica but typed device-varying —
    callers inside ``shard_map`` should declare a varying out-spec or
    re-reduce, which is why the trainers wire :func:`compressed_pmean`
    (unvarying by construction) rather than this variant."""
    check_compress_mode(mode)
    world = _compat_axis_size(axis_name)
    if world == 1:
        return tree
    shards = world if num_shards is None else int(num_shards)
    if shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {shards}")
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    flat = _fuse_f32(leaves)
    logical = flat.size * 4
    scale = zp = None
    if mode == "bf16":
        payload = flat.astype(jnp.bfloat16)
        _tally_compressed(logical, payload.size * 2)
    elif mode == "int8":
        blocks = _chunk_pad(flat, chunk_size).reshape(-1, chunk_size)
        q, scale, zp, _ = _int8_qparams(blocks, axis_name, world)
        payload = q.reshape(-1)
        _tally_compressed(logical, payload.size + 8 * blocks.shape[0])
    else:
        payload = flat
    payload_size = payload.size
    pad = (-payload_size) % shards
    if pad:
        payload = jnp.concatenate(
            [payload, jnp.zeros((pad,), payload.dtype)]
        )
    segs = payload.reshape(shards, -1)
    factors = _prime_factors(world)
    outs = []
    for j in range(shards):
        # shard j's butterfly runs over the world rotated by j: same
        # stage count, different (src, dst) links every stage
        order = tuple((r + j) % world for r in range(world))
        seg = segs[j]
        stride = 1
        for f in factors:
            acc = seg
            for k in range(1, f):
                perm = _stage_perm((order,), stride, f, k)
                _tally("ppermute", seg)
                acc = acc + lax.ppermute(seg, axis_name, perm)
            seg = acc
            stride *= f
        outs.append(seg)
    summed = jnp.concatenate(outs)[:payload_size]
    if mode == "bf16":
        summed_flat = summed.astype(jnp.float32)
    elif mode == "int8":
        summed_flat = (
            scale * summed.reshape(-1, chunk_size).astype(jnp.float32)
            + world * zp
        ).reshape(-1)[:flat.size]
    else:
        summed_flat = summed
    out = _unfuse(summed_flat, leaves)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# live wire-traffic estimation


class DispatchWireTally:
    """Convert trace-time collective inventories into a live per-dispatch
    byte counter (``collectives.dispatched_bytes``).

    The ``collectives.<op>.bytes`` tallies count once per *compilation*
    (:func:`_tally`): a steady-state loop re-executing one compiled
    program moves real bytes every step while the tallies stand still —
    so a rate window over them reads zero exactly when traffic is
    highest. This tally closes the gap: when a dispatch grows the
    trace-time total (a compile happened inside it), the delta is that
    program's per-execution inventory; every dispatch then replays the
    inventory into ``collectives.dispatched_bytes`` (× ``steps`` for
    fused K-step programs — scan bodies tally once but execute K times,
    the same K-invariance the program contracts pin). The windowed
    aggregator (``obs.timeseries``) turns that counter into the live
    bytes/s a network-bound diagnosis or an EQuARX-style compression
    argument needs (PAPERS.md, arXiv:2007.03298 / 2506.17615).

    An estimate, not an exact meter: a concurrent compile on another
    thread (e.g. a serve bucket warming) lands in whichever dispatch
    observes it first. Driven by ``ResilientLoop``; no-op while
    telemetry is disabled."""

    def __init__(self):
        self._program_bytes = 0
        self._last_total = self._traced_total()

    @staticmethod
    def _traced_total() -> int:
        return traced_bytes_total()

    def after_dispatch(self, steps: int = 1) -> None:
        """Record one executed program dispatch covering ``steps``
        optimizer steps."""
        if not telemetry.enabled():
            return
        total = self._traced_total()
        if total > self._last_total:
            # a (re)trace happened inside this dispatch: its delta is
            # the new program's per-execution collective inventory
            self._program_bytes = total - self._last_total
            self._last_total = total
        if self._program_bytes:
            telemetry.count("collectives.dispatched_bytes",
                            self._program_bytes * max(1, int(steps)))
