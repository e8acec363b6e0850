"""Functional batch-normalization ops with exact reference-stack semantics.

These are the TPU-native equivalents of the ATen CUDA kernels the reference's
SyncBN path calls (``batch_norm_stats`` / ``batch_norm_gather_stats_with_counts``
/ ``batch_norm_elemt`` / ``batch_norm_backward_reduce`` /
``batch_norm_backward_elemt``, invoked at
``[torch] nn/modules/_functions.py:39,106,122,145,171``), expressed as pure
functions XLA fuses into the surrounding step. The backward of the
cross-replica ``psum`` is itself a ``psum`` under autodiff — exactly the
reference's backward all_reduce of ``[sum_dy, sum_dy_xmu]``
(``[torch] nn/modules/_functions.py:160-165``) — so no hand-written VJP is
needed for correctness (a fused Pallas fast path lives in
``tpu_syncbn.ops.pallas_bn``).

Semantics pinned to torch 2.13 (SURVEY §7 "hard parts"):

* normalization uses **biased** (1/N) batch variance; the running-var update
  uses the **unbiased** (1/(N-1)) variance
  (``[torch] nn/modules/batchnorm.py:800-812`` and
  ``_functions.py:106-115``);
* ``momentum=None`` means *cumulative average*: the effective update factor
  is ``1/num_batches_tracked`` (``[torch] nn/modules/batchnorm.py:666-667,
  800-812``);
* count-weighted cross-replica aggregation so uneven/empty shards are exact
  (``[torch] nn/modules/_functions.py:50-62``).

Layout: channel-last (NHWC / N...C) by default — the TPU-friendly layout
(lane dimension = channels). A ``channel_axis`` argument covers NCHW.
"""

from __future__ import annotations

import contextlib
import math
import os

import jax
import jax.numpy as jnp

from tpu_syncbn.parallel.collectives import moments_from_stats, reduce_moments

def set_pallas_mode(mode: str) -> None:
    """Select the BN kernel backend: 'off' (the default: the XLA-fusion
    path, on every backend) or 'on' (the Pallas kernels of
    ``tpu_syncbn.ops.pallas_bn``; interpret mode off-TPU).

    Read at *trace* time for direct functional calls; the trainers
    (``DataParallel``/``GANTrainer``) additionally snapshot the
    kernel-backend decision (and the matching VMA-checker setting) at
    **construction** — call this BEFORE building a trainer. Steps already
    jit-compiled keep the backend they were traced with.
    """
    global _PALLAS_MODE
    if mode not in ("on", "off"):
        raise ValueError(f"pallas mode must be on/off, got {mode!r}")
    _PALLAS_MODE = mode


def get_pallas_mode() -> str:
    """The active BN kernel-backend mode ('on'/'off')."""
    return _PALLAS_MODE


@contextlib.contextmanager
def pallas_mode(mode: str):
    """Scoped :func:`set_pallas_mode`: restores the previous mode on exit.
    The same trace-time/construction-time caveats apply — build trainers
    INSIDE the block for the override to take effect."""
    prev = _PALLAS_MODE
    set_pallas_mode(mode)
    try:
        yield
    finally:
        set_pallas_mode(prev)


_PALLAS_MODE = "off"
_ENV_ALIASES = {
    "1": "on", "true": "on", "yes": "on", "on": "on",
    "0": "off", "false": "off", "no": "off", "off": "off", "": "off",
}
_env_mode = os.environ.get("TPU_SYNCBN_PALLAS", "off").strip().lower()
if _env_mode in _ENV_ALIASES:
    set_pallas_mode(_ENV_ALIASES[_env_mode])
else:
    import warnings

    warnings.warn(
        f"ignoring unrecognized TPU_SYNCBN_PALLAS={_env_mode!r} "
        "(expected on/off or 1/0/true/false); using 'off'"
    )


def _use_pallas() -> bool:
    return _PALLAS_MODE == "on"


def _reduction_axes(ndim: int, channel_axis: int) -> tuple[int, ...]:
    ca = channel_axis % ndim
    return tuple(i for i in range(ndim) if i != ca)


def _shape_for_channel(ndim: int, channel_axis: int, c: int) -> list[int]:
    shape = [1] * ndim
    shape[channel_axis % ndim] = c
    return shape


def batch_norm_stats(
    x: jax.Array, *, channel_axis: int = -1
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-channel local partial moments: (sum, sumsq, count).

    Equivalent role to ``torch.batch_norm_stats``
    (``[torch] nn/modules/_functions.py:39``) but returns raw sums rather
    than (mean, invstd): sums compose across replicas with a single psum,
    which is how :func:`sync_moments` aggregates them.

    Accumulates in float32 regardless of input dtype (bf16-safe).
    """
    axes = _reduction_axes(x.ndim, channel_axis)
    xf = x.astype(jnp.float32)
    s = jnp.sum(xf, axis=axes)
    sq = jnp.sum(xf * xf, axis=axes)
    # x.shape is static at trace time: count is a compile-time constant.
    count = jnp.float32(math.prod(x.shape[a] for a in axes))
    return s, sq, count


def sync_moments(
    x: jax.Array,
    *,
    channel_axis: int = -1,
    axis_name: str | None = None,
    group_size: int | tuple | None = None,
    stats_compress: str = "none",
    mask: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-channel (mean, biased var, count) over the batch — cross-replica
    when ``axis_name`` is given.

    This is the fused TPU form of the reference's forward stats exchange:
    local ``batch_norm_stats`` → all_gather of ``[mean, invstd, count]`` →
    ``batch_norm_gather_stats_with_counts``
    (``[torch] nn/modules/_functions.py:39-115``) collapses to local
    (sum, sumsq, count) + one ``psum``.

    ``mask`` (broadcastable to x with channel axis size 1) marks valid
    elements, supporting the uneven/empty-shard contract
    (``_functions.py:50-57``).
    """
    with jax.named_scope("stats"):
        if mask is None:
            s, sq, count = batch_norm_stats(x, channel_axis=channel_axis)
        else:
            axes = _reduction_axes(x.ndim, channel_axis)
            xf = x.astype(jnp.float32)
            mf = jnp.broadcast_to(mask, x.shape).astype(jnp.float32)
            s = jnp.sum(xf * mf, axis=axes)
            sq = jnp.sum(xf * xf * mf, axis=axes)
            count = jnp.sum(mf, axis=axes)  # per-channel (all equal when
            # the mask has channel-axis size 1); reduce_moments handles
            # either form
    if axis_name is not None:
        with jax.named_scope("psum"):
            return reduce_moments(
                s, sq, count, axis_name, group_size=group_size,
                mode=stats_compress,
            )
    mean, var = moments_from_stats(s, sq, count)
    return mean, var, count


def fold_scale_shift(
    mean: jax.Array,
    var: jax.Array,
    weight: jax.Array | None,
    bias: jax.Array | None,
    eps: float,
) -> tuple[jax.Array, jax.Array]:
    """Fold (mean, var, γ, β, eps) into per-channel (scale, shift) so the
    normalize is one FMA per element: ``y = x·scale + shift``. Single home
    for this folding — used by both the XLA and Pallas paths."""
    invstd = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    scale = invstd if weight is None else invstd * weight.astype(jnp.float32)
    shift = -mean.astype(jnp.float32) * scale
    if bias is not None:
        shift = shift + bias.astype(jnp.float32)
    return scale, shift


def batch_norm_elemt(
    x: jax.Array,
    mean: jax.Array,
    var: jax.Array,
    weight: jax.Array | None,
    bias: jax.Array | None,
    eps: float,
    *,
    channel_axis: int = -1,
) -> jax.Array:
    """Elementwise normalize+affine: ``torch.batch_norm_elemt``
    (``[torch] nn/modules/_functions.py:122``). Computes in f32, returns in
    x.dtype; XLA fuses the whole expression into neighbors."""
    shape = _shape_for_channel(x.ndim, channel_axis, mean.shape[0])
    scale, shift = fold_scale_shift(mean, var, weight, bias, eps)
    y = x.astype(jnp.float32) * scale.reshape(shape) + shift.reshape(shape)
    return y.astype(x.dtype)


def update_running_stats(
    running_mean: jax.Array,
    running_var: jax.Array,
    num_batches_tracked: jax.Array,
    batch_mean: jax.Array,
    batch_var: jax.Array,
    count: jax.Array,
    momentum: float | None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Running-stats update with exact torch semantics.

    * increments ``num_batches_tracked`` (``[torch] nn/modules/batchnorm.py:
      800-807``);
    * effective factor = ``momentum``, or ``1/num_batches_tracked`` when
      ``momentum`` is None (cumulative moving average, ``:666-667, 808-812``);
    * running_var absorbs the **unbiased** variance ``var * n/(n-1)``
      (``[torch] nn/modules/_functions.py:106-115`` applies the Bessel
      correction with the *global* count), while normalization uses the
      biased variance. For n<=1 torch would divide by zero; we keep the
      biased value instead of propagating inf into the buffer.
    """
    nbt = num_batches_tracked + 1
    if momentum is None:
        factor = 1.0 / nbt.astype(jnp.float32)
    else:
        factor = jnp.asarray(momentum, jnp.float32)
    unbiased = jnp.where(
        count > 1.0, batch_var * (count / jnp.maximum(count - 1.0, 1.0)), batch_var
    )
    new_mean = (1.0 - factor) * running_mean + factor * batch_mean
    new_var = (1.0 - factor) * running_var + factor * unbiased
    return new_mean, new_var, nbt


def batch_norm_train(
    x: jax.Array,
    running_mean: jax.Array | None,
    running_var: jax.Array | None,
    num_batches_tracked: jax.Array | None,
    weight: jax.Array | None,
    bias: jax.Array | None,
    *,
    momentum: float | None = 0.1,
    eps: float = 1e-5,
    channel_axis: int = -1,
    axis_name: str | None = None,
    group_size: int | tuple | None = None,
    stats_compress: str = "none",
    mask: jax.Array | None = None,
):
    """Full training-mode BN forward (optionally cross-replica synced).
    ``group_size`` scopes the sync to replica subgroups — an int for
    contiguous groups of that size, or an explicit rank partition for
    torch's arbitrary ``process_group`` rank sets (both routed through
    ``parallel.collectives.psum_in_groups``).

    Returns ``(y, (new_running_mean, new_running_var, new_num_batches_tracked))``;
    the stats triple is ``(None, None, None)`` when running stats aren't
    tracked (``track_running_stats=False`` mode, which normalizes by batch
    stats and keeps no buffers).

    With ``axis_name`` set this is SyncBatchNorm: the only cross-replica
    traffic is one fused psum of ``2C+1`` floats — the reference's
    ``all_gather(world×(2C+1))`` + recombine (``_functions.py:41-115``),
    collapsed. Backward under autodiff emits the matching psum of
    ``[sum_dy, sum_dy_xmu]`` exactly as the reference does by hand
    (``_functions.py:160-165``).
    """
    channel_last = channel_axis in (-1, x.ndim - 1)
    if _use_pallas() and channel_last and mask is None \
            and group_size is None and stats_compress == "none":
        # (compressed stats keep the XLA path: the Pallas backward issues
        # its own hand-written psum, which must stay exact)
        # fused Pallas fast path (ops.pallas_bn): one-pass stats kernel,
        # folded normalize, hand-derived backward issuing the reference's
        # exact collectives
        from tpu_syncbn.ops import pallas_bn

        y, mean, var, count = pallas_bn.fused_batch_norm(
            x, weight, bias, eps, axis_name
        )
    else:
        mean, var, count = sync_moments(
            x, channel_axis=channel_axis, axis_name=axis_name,
            group_size=group_size, stats_compress=stats_compress,
            mask=mask,
        )
        with jax.named_scope("normalize"):
            y = batch_norm_elemt(
                x, mean, var, weight, bias, eps, channel_axis=channel_axis
            )
    if running_mean is None:
        return y, (None, None, None)
    # Buffers do not participate in autodiff (torch updates them in-place,
    # outside the graph — [torch] nn/modules/_functions.py:106 mutates
    # running stats inside a no-grad kernel).
    mean_s, var_s, count_s = (
        jax.lax.stop_gradient(mean),
        jax.lax.stop_gradient(var),
        jax.lax.stop_gradient(count),
    )
    new_rm, new_rv, nbt = update_running_stats(
        running_mean, running_var, num_batches_tracked, mean_s, var_s, count_s, momentum
    )
    return y, (new_rm, new_rv, nbt)


def batch_norm_inference(
    x: jax.Array,
    running_mean: jax.Array,
    running_var: jax.Array,
    weight: jax.Array | None,
    bias: jax.Array | None,
    *,
    eps: float = 1e-5,
    channel_axis: int = -1,
) -> jax.Array:
    """Eval-mode BN: normalize by running stats, **zero collectives** — the
    reference's non-sync fallback (``[torch] nn/modules/batchnorm.py:863-873``,
    selected when not training per ``:836-842``)."""
    return batch_norm_elemt(
        x, running_mean, running_var, weight, bias, eps, channel_axis=channel_axis
    )
