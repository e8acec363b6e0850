"""BatchNorm / SyncBatchNorm modules (flax nnx) with the reference's drop-in
contract.

``SyncBatchNorm`` reproduces the capability of ``torch.nn.SyncBatchNorm``
(reference ``README.md:40-45``; implementation
``[torch] nn/modules/batchnorm.py:650-887``): in training mode, per-channel
batch statistics are reduced across every replica on the ``data`` mesh axis
before normalizing, so each replica normalizes against the *global* batch.
In eval mode (or when no mesh axis is active) it falls back to plain BN with
zero collectives — the reference's need_sync/fallback split
(``[torch] nn/modules/batchnorm.py:837-873``).

Differences from torch, by design (TPU-first):

* layout is channel-last (NHWC) by default — the TPU lane dimension is the
  channel; ``channel_axis`` covers NCHW;
* there is no process-group object: the replica group is a mesh axis name,
  and sync happens whenever the module runs inside ``shard_map``/``pjit``
  with that axis in scope (the trainer arranges this);
* running-stat mutation is an nnx ``BatchStat`` variable update, which the
  compiled step threads functionally (SURVEY §7 "state under jit").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import nnx

from tpu_syncbn.ops import batch_norm as bn_ops
from tpu_syncbn.parallel.collectives import (
    check_compress_mode,
    normalize_group_spec,
)
from tpu_syncbn.runtime.distributed import DATA_AXIS


def _axis_in_scope(axis_name: str) -> bool:
    """True when ``axis_name`` is a live named mesh axis at trace time (i.e.
    we are inside shard_map/pmap over it) — the analogue of the reference's
    ``need_sync = training and dist.is_initialized() and world > 1`` check
    (``[torch] nn/modules/batchnorm.py:837-860``)."""
    from tpu_syncbn import compat

    try:
        compat.axis_size(axis_name)
        return True
    except (NameError, KeyError):
        return False


class BatchNorm(nnx.Module):
    """Plain batch normalization over the batch (+spatial) axes.

    Mirrors ``torch.nn.BatchNorm1d/2d/3d`` semantics
    (``[torch] nn/modules/batchnorm.py``): biased variance for
    normalization, unbiased for the running buffer, ``momentum=None``
    cumulative averaging, optional affine, optional running stats.

    Mode: ``use_running_average`` is flipped by ``nnx``'s standard
    ``model.train()`` / ``model.eval()`` attribute propagation.
    """

    def __init__(
        self,
        num_features: int,
        *,
        eps: float = 1e-5,
        momentum: float | None = 0.1,
        affine: bool = True,
        track_running_stats: bool = True,
        channel_axis: int = -1,
        axis_name: str | None = None,
        group_size: int | tuple | None = None,
        stats_compress: str = "none",
        dtype: jnp.dtype = jnp.float32,
        rngs: nnx.Rngs | None = None,  # unused; accepted for nnx idiom
    ):
        if (
            axis_name is not None
            or group_size is not None
            or stats_compress != "none"
        ) and not isinstance(self, SyncBatchNorm):
            # Plain BN never syncs (that per-replica behavior is the bug
            # the reference exists to fix, README.md:3); accepting sync
            # parameters here and ignoring them would silently reintroduce it.
            raise ValueError(
                "plain BatchNorm does not sync across replicas; use "
                "SyncBatchNorm (or convert_sync_batchnorm) for "
                f"axis_name={axis_name!r} / group_size={group_size!r} / "
                f"stats_compress={stats_compress!r}"
            )
        self.num_features = num_features
        self.eps = eps
        self.momentum = momentum
        self.affine = affine
        self.track_running_stats = track_running_stats
        self.channel_axis = channel_axis
        self.axis_name = axis_name
        # int stays int (contiguous groups); an explicit rank partition
        # (torch's arbitrary process_group sets) becomes hashable nested
        # tuples, stable under jit caching; membership is validated
        # against the axis size at trace time (psum_in_groups)
        self.group_size = normalize_group_spec(group_size)
        #: wire dtype of the cross-replica moment reduction — stats stay
        #: exact fp32 unless EXPLICITLY opted into a lossy mode,
        #: independently of any gradient compression the trainer applies
        #: (the count census always stays fp32 either way —
        #: collectives.reduce_moments)
        self.stats_compress = check_compress_mode(stats_compress)
        self.use_running_average = False
        if affine:
            # torch init: weight=1, bias=0 ([torch] nn/modules/batchnorm.py reset_parameters)
            self.weight = nnx.Param(jnp.ones((num_features,), dtype))
            self.bias = nnx.Param(jnp.zeros((num_features,), dtype))
        else:
            self.weight = None
            self.bias = None
        if track_running_stats:
            self.running_mean = nnx.BatchStat(jnp.zeros((num_features,), jnp.float32))
            self.running_var = nnx.BatchStat(jnp.ones((num_features,), jnp.float32))
            self.num_batches_tracked = nnx.BatchStat(jnp.zeros((), jnp.int32))
        else:
            self.running_mean = None
            self.running_var = None
            self.num_batches_tracked = None

    def _check_input(self, x: jax.Array) -> None:
        c = x.shape[self.channel_axis]
        if c != self.num_features:
            raise ValueError(
                f"expected {self.num_features} channels on axis "
                f"{self.channel_axis}, got shape {x.shape}"
            )

    def _sync_axis(self) -> str | None:
        """The mesh axis to sync over, or None for local stats. Plain
        BatchNorm never syncs (torch BN under DDP keeps per-replica stats —
        the exact behavior the reference exists to fix, ``README.md:3``)."""
        return None

    def __call__(self, x: jax.Array, *, mask: jax.Array | None = None) -> jax.Array:
        self._check_input(x)
        w = self.weight[...] if self.weight is not None else None
        b = self.bias[...] if self.bias is not None else None

        use_running = self.use_running_average and self.track_running_stats
        if use_running:
            # eval fallback: zero collectives ([torch] batchnorm.py:863-873)
            return bn_ops.batch_norm_inference(
                x,
                self.running_mean[...],
                self.running_var[...],
                w,
                b,
                eps=self.eps,
                channel_axis=self.channel_axis,
            )

        rm = self.running_mean[...] if self.track_running_stats else None
        rv = self.running_var[...] if self.track_running_stats else None
        nbt = self.num_batches_tracked[...] if self.track_running_stats else None
        sync_axis = self._sync_axis()
        # the names the device trace's operations carry: `syncbn` with
        # `stats`, `psum` and `normalize` inside it (ops.batch_norm)
        with jax.named_scope("syncbn" if sync_axis else "bn"):
            y, (new_rm, new_rv, new_nbt) = bn_ops.batch_norm_train(
                x,
                rm,
                rv,
                nbt,
                w,
                b,
                momentum=self.momentum,
                eps=self.eps,
                channel_axis=self.channel_axis,
                axis_name=sync_axis,
                group_size=self.group_size if sync_axis else None,
                stats_compress=self.stats_compress if sync_axis else "none",
                mask=mask,
            )
        if self.track_running_stats:
            # .value assignment (not var[...] = x): portable across
            # flax versions whose Variable.__setitem__ writes through to
            # the (immutable) jax array instead of rebinding it
            self.running_mean.value = new_rm
            self.running_var.value = new_rv
            self.num_batches_tracked.value = new_nbt
        return y


class BatchNorm1d(BatchNorm):
    """Rank-2/3 inputs (N, C) or (N, L, C) — torch.nn.BatchNorm1d analogue."""

    def _check_input(self, x):
        if x.ndim not in (2, 3):
            raise ValueError(f"BatchNorm1d expects 2D/3D input, got {x.ndim}D")
        super()._check_input(x)


class BatchNorm2d(BatchNorm):
    """Rank-4 inputs (N, H, W, C) — torch.nn.BatchNorm2d analogue (NHWC)."""

    def _check_input(self, x):
        if x.ndim != 4:
            raise ValueError(f"BatchNorm2d expects 4D input, got {x.ndim}D")
        super()._check_input(x)


class BatchNorm3d(BatchNorm):
    """Rank-5 inputs (N, D, H, W, C) — torch.nn.BatchNorm3d analogue."""

    def _check_input(self, x):
        if x.ndim != 5:
            raise ValueError(f"BatchNorm3d expects 5D input, got {x.ndim}D")
        super()._check_input(x)


class SyncBatchNorm(BatchNorm):
    """Cross-replica synchronized BatchNorm — ``torch.nn.SyncBatchNorm``
    rebuilt TPU-native (reference ``README.md:40-45``).

    When training inside a mesh context that carries ``self.axis_name``
    (the trainer's shard_map over the ``data`` axis), per-channel moments
    are reduced across all replicas — or within replica subgroups, the
    torch ``process_group`` scoping (``[torch] nn/modules/batchnorm.py:706``):
    ``group_size`` takes an int (contiguous, topology-shaped subgroups)
    or an explicit partition of ranks like ``((0, 3, 5), (1, 2, 4, 6, 7))``
    for torch's arbitrary rank sets — with one fused psum
    (see ops.batch_norm.sync_moments). Outside any mesh context — eval
    mode, single-replica debugging, world size 1 — it degrades to plain BN
    exactly like the reference's fallback
    (``[torch] nn/modules/batchnorm.py:837-873``).
    """

    def __init__(self, num_features: int, *, axis_name: str = DATA_AXIS, **kw):
        super().__init__(num_features, axis_name=axis_name, **kw)

    @classmethod
    def convert_sync_batchnorm(
        cls, module, axis_name: str = DATA_AXIS,
        group_size: int | tuple | None = None,
        stats_compress: str = "none",
    ):
        """Drop-in spelling parity with
        ``torch.nn.SyncBatchNorm.convert_sync_batchnorm(module,
        process_group)`` (``[torch] nn/modules/batchnorm.py:889``);
        delegates to :func:`tpu_syncbn.nn.convert_sync_batchnorm`."""
        from tpu_syncbn.nn.convert import convert_sync_batchnorm

        return convert_sync_batchnorm(
            module, axis_name, group_size, stats_compress
        )

    def _sync_axis(self) -> str | None:
        # torch's need_sync requires self.training ([torch] nn/modules/
        # batchnorm.py:837-860): eval mode never syncs, even when
        # track_running_stats=False puts eval on the batch-stats path.
        if (
            not self.use_running_average
            and self.axis_name is not None
            and _axis_in_scope(self.axis_name)
        ):
            return self.axis_name
        return None
