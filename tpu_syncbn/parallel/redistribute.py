"""Portable train→serve parameter redistribution on the mesh.

Training under ``DataParallel(zero=True)`` leaves parameters in the ZeRO
flat layout (:class:`tpu_syncbn.parallel.zero.FlatLayout`): one padded
1-D vector per dtype, each device holding a contiguous ``1/world``
shard. Serving wants the full parameter pytree replicated on every
device. The cold-start path (``zero.unshard_params`` →
``InferenceEngine.from_trainer``) solves that layout change on the
*host*: every shard is fetched to one process, the tree is assembled in
host memory, then re-uploaded — the whole model materializes on one
host, pinned as ``max_replicated_bytes`` in the sharding goldens.

This module is the on-mesh alternative (ROADMAP item 2; the
layout-change problem of "Memory-efficient array redistribution through
portable collective communication", arXiv 2112.01075, at whole-model
granularity): ONE compiled program per layout pair that ``all_gather``\\s
each dtype group's shards across the data axis and unflattens the full
vectors back into the parameter pytree *inside the same program* —
device-to-device transfer only, bounded at ``(world-1)/world`` of the
parameter bytes per device, and the full tree never exists as host
memory anywhere. The program is golden-pinned as the
``serve.redistribute`` audit contract
(:mod:`tpu_syncbn.audit.jaxpr_audit`), so the gather count and
bytes-on-wire cannot silently regress back into a host gather.

This is the hot path of zero-downtime weight publication
(:mod:`tpu_syncbn.serve.publish`): a live trainer re-shards its current
params straight into the serving layout for an in-process engine swap.
The durable cross-process path (publish to disk, manifest-verified)
goes through :func:`tpu_syncbn.utils.checkpoint.publish_version`.
"""

from __future__ import annotations

from tpu_syncbn.runtime.distributed import DATA_AXIS

__all__ = ["build_redistribute", "portable_redistribute"]


def build_redistribute(layout, mesh, axis_name: str = DATA_AXIS):
    """The compiled redistribution program for one ``FlatLayout`` on one
    mesh: ``{dtype: 1/world-sharded flat vector}`` in, full parameter
    pytree (replicated) out. Build once per (layout, mesh) and reuse —
    the swap path calls it per publication, and params share a layout
    across versions, so the compile amortizes to zero."""
    import jax
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn.compat import shard_map

    def gather_unflatten(store):
        full = {
            dt: jax.lax.all_gather(v, axis_name, tiled=True)
            for dt, v in store.items()
        }
        return layout.unflatten(full)

    # in: every dtype vector sharded 1/world over the data axis (the
    # ZeRO storage layout); out: replicated — each device reconstructs
    # the identical full tree from the gathered vectors, so out_specs
    # P() holds by construction. The VMA checker cannot see that: it
    # types a tiled all_gather's result as varying and refuses P(), and
    # the gather it would accept (all_gather_invariant) is not public —
    # so this one program runs unchecked.
    return jax.jit(shard_map(
        gather_unflatten,
        mesh=mesh,
        in_specs=(P(axis_name),),
        out_specs=P(),
        check_vma=False,
    ))


def portable_redistribute(layout, store, mesh, axis_name: str = DATA_AXIS):
    """Re-shard ZeRO flat parameter shards into the serving layout
    (full pytree, replicated) entirely on the mesh — the collective
    counterpart of :func:`tpu_syncbn.parallel.zero.unshard_params`,
    which does the same layout change through host memory. Returns the
    parameter pytree as replicated device arrays on ``mesh``."""
    return build_redistribute(layout, mesh, axis_name)(store)
