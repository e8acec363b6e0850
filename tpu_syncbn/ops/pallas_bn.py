"""Pallas TPU kernels for the BatchNorm hot ops.

TPU-native equivalents of the ATen CUDA kernels the reference's SyncBN calls
(``batch_norm_stats`` / ``batch_norm_elemt`` / ``batch_norm_backward_reduce``
/ ``batch_norm_backward_elemt``, ``aten/src/ATen/native/cuda/
Normalization.cu``, invoked from ``[torch] nn/modules/_functions.py:39,122,
145,171`` — SURVEY §2 C9, a mandated native-equivalent component).

Three fused single-pass kernels over a channel-last view ``(M, C)`` where
``M = N·H·W``:

* :func:`bn_stats`            — per-channel ``(Σx, Σx²)`` in one read of x.
* :func:`bn_normalize`        — ``y = x·scale + shift`` (scale/shift folded
                                from mean/var/γ/β on the host side of the
                                kernel, so the inner loop is one FMA).
* :func:`bn_backward_reduce`  — per-channel ``(Σdy, Σdy·x̂)`` in one fused
                                read of (dy, x) — these are exactly the two
                                tensors the reference all_reduces in its
                                backward (``_functions.py:160-165``).

All kernels accumulate in float32 VMEM scratch regardless of input dtype
(bf16-safe), tile ``M`` on the sublane axis with channels on the lane axis
(the natural TPU layout), and run under ``interpret=True`` off-TPU so the
CPU test mesh exercises the same code path.

``fused_batch_norm`` wires them into a ``jax.custom_vjp`` whose forward and
backward issue the identical cross-replica psums as the XLA-fusion path in
``ops.batch_norm`` — kernels swap in under the same numerical contract
(golden-tested against both torch and the XLA path).
"""

from __future__ import annotations

import functools


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_syncbn.parallel.collectives import moments_from_stats

# Max rows per grid step (sublane-aligned); channels ride the 128-wide
# lane axis. 256 ranked best of {128, 256, 512, 1024} over the ResNet-50
# BN shape set in a sweep on one v5e chip recorded 2026-07-31 (sum of
# fused fwd+bwd: 256 -> 28.2 ms, 1024 -> 32.3, 128 -> 36.5, 512 -> 44.8;
# the record was removed in PR 21 and the ranking is not measured at
# HEAD — re-run benchmarks/pallas_block_sweep.py before leaning on it).
_BLOCK_M = 256

# The fattest kernel (bn_backward_reduce) streams TWO (block, C) operands
# through Pallas's double-buffered pipeline: working set = 2 operands x 2
# buffers x block*C*itemsize. The first on-chip run of the full ResNet-50
# step at block 512, C=2048, f32 hit the TPU's scoped-VMEM ceiling at
# exactly that arithmetic (16.02 MiB vs the 16 MiB limit) — a failure
# the standalone kernel sweep and interpret mode both miss
# (tests/test_tpu_compile.py asks the compiler). Budget leaves headroom
# for scratch/semaphores.
_VMEM_BUDGET_BYTES = 14 * 2**20


def _block_m(c: int, itemsize: int) -> int:
    """Largest power-of-two block <= _BLOCK_M whose double-buffered
    two-stream working set fits the scoped-VMEM budget, down to one
    sublane tile of rows (8 at 4 bytes, 16 at 2). The v5e compiler
    accepts 64 rows up to C=16384 f32 and refuses C=32768 there (16.25
    MiB scoped against a 16 MiB limit; tests/test_tpu_compile.py), so
    the block follows the budget below 64; channels too wide even for
    one tile are refused here, at trace time, by name."""
    floor = max(8, 32 // itemsize)
    m = _BLOCK_M
    while m > floor and 4 * m * c * itemsize > _VMEM_BUDGET_BYTES:
        m //= 2
    if 4 * m * c * itemsize > _VMEM_BUDGET_BYTES:
        raise ValueError(
            f"pallas_bn: {c} channels of {itemsize}-byte elements do not "
            f"fit the scoped-VMEM budget ({_VMEM_BUDGET_BYTES} B) even at "
            f"{m} rows per block; use the XLA path (set_pallas_mode('off'))"
        )
    return m


from tpu_syncbn.ops._pallas_common import interpret as _interpret


from tpu_syncbn.ops._pallas_common import sds as _sds


def _as_2d(x: jax.Array) -> tuple[jax.Array, int]:
    """Collapse all non-channel axes of a channel-last array into rows."""
    c = x.shape[-1]
    return x.reshape(-1, c), c


def _pad_rows(x2: jax.Array, block: int) -> tuple[jax.Array, int]:
    m = x2.shape[0]
    padded = pl.cdiv(m, block) * block
    if padded != m:
        x2 = jnp.pad(x2, ((0, padded - m), (0, 0)))
    return x2, m


# -- stats kernel ---------------------------------------------------------


def _stats_kernel(x_ref, sum_ref, sumsq_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xf = x_ref[...].astype(jnp.float32)
    acc_ref[0, :] += jnp.sum(xf, axis=0)
    acc_ref[1, :] += jnp.sum(xf * xf, axis=0)

    @pl.when(i == pl.num_programs(0) - 1)
    def _done():
        # (1, C) outputs: 2-D lane-aligned layout per the TPU tiling rules
        sum_ref[0, :] = acc_ref[0, :]
        sumsq_ref[0, :] = acc_ref[1, :]


def bn_stats(x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused per-channel (sum, sumsq, count) — one pass over x.

    Same contract as ``ops.batch_norm.batch_norm_stats`` (the XLA path);
    the reference's ``batch_norm_stats`` CUDA kernel returns (mean, invstd)
    but raw sums compose across replicas with a single psum (SURVEY §7).
    """
    x2, c = _as_2d(x)
    block = _block_m(c, x.dtype.itemsize)
    x2, m = _pad_rows(x2, block)  # zero rows contribute 0 to both sums
    s, sq = _stats_2d(x2, c, block)
    return s, sq, jnp.float32(m)


def _stats_2d(x2: jax.Array, c: int, block: int) -> tuple[jax.Array, jax.Array]:
    """Stats kernel over an (M', C) view already padded to ``block``."""
    grid = (x2.shape[0] // block,)
    s, sq = pl.pallas_call(
        _stats_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, c), lambda i: (i, 0), memory_space=pltpu.VMEM)
        ],
        out_specs=[
            pl.BlockSpec((1, c), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _sds((1, c), jnp.float32, x2),
            _sds((1, c), jnp.float32, x2),
        ],
        scratch_shapes=[pltpu.VMEM((2, c), jnp.float32)],
        interpret=_interpret(),
    )(x2)
    return s[0], sq[0]


# -- normalize kernel -----------------------------------------------------


def _normalize_kernel(x_ref, scale_ref, shift_ref, y_ref):
    xf = x_ref[...].astype(jnp.float32)
    y = xf * scale_ref[0, :] + shift_ref[0, :]
    y_ref[...] = y.astype(y_ref.dtype)


def bn_normalize(
    x: jax.Array,
    mean: jax.Array,
    var: jax.Array,
    weight: jax.Array | None,
    bias: jax.Array | None,
    eps: float,
) -> jax.Array:
    """Fused elementwise normalize+affine (``batch_norm_elemt``,
    ``[torch] nn/modules/_functions.py:122``): scale/shift are folded to
    one FMA per element (shared folding in ops.batch_norm)."""
    from tpu_syncbn.ops.batch_norm import fold_scale_shift

    scale, shift = fold_scale_shift(mean, var, weight, bias, eps)
    x2, c = _as_2d(x)
    block = _block_m(c, x.dtype.itemsize)
    x2p, m = _pad_rows(x2, block)
    y = _normalize_2d(x2p, scale, shift, c, x.dtype, block)
    return y[:m].reshape(x.shape)


def _normalize_2d(x2p, scale, shift, c, out_dtype, block):
    """Normalize kernel over an (M', C) view already padded to ``block``."""
    grid = (x2p.shape[0] // block,)
    return pl.pallas_call(
        _normalize_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, c), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (block, c), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=_sds(x2p.shape, out_dtype, x2p),
        interpret=_interpret(),
    )(x2p, scale[None], shift[None])


# -- backward reduce kernel ----------------------------------------------


def _bwd_reduce_kernel(dy_ref, x_ref, mean_ref, invstd_ref, sdy_ref, sdyx_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dyf = dy_ref[...].astype(jnp.float32)
    xf = x_ref[...].astype(jnp.float32)
    xhat = (xf - mean_ref[0, :]) * invstd_ref[0, :]
    acc_ref[0, :] += jnp.sum(dyf, axis=0)
    acc_ref[1, :] += jnp.sum(dyf * xhat, axis=0)

    @pl.when(i == pl.num_programs(0) - 1)
    def _done():
        sdy_ref[0, :] = acc_ref[0, :]
        sdyx_ref[0, :] = acc_ref[1, :]


def bn_backward_reduce(
    dy: jax.Array, x: jax.Array, mean: jax.Array, invstd: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Fused per-channel (Σdy, Σdy·x̂) — the ``batch_norm_backward_reduce``
    kernel (``[torch] nn/modules/_functions.py:145-154``); Σdy·x̂ relates to
    torch's ``sum_dy_xmu`` by the invstd factor. Zero-padded rows contribute
    dy=0, so the sums are exact."""
    dy2, c = _as_2d(dy)
    x2, _ = _as_2d(x)
    block = _block_m(c, max(dy.dtype.itemsize, x.dtype.itemsize))
    dy2, m = _pad_rows(dy2, block)
    x2, _ = _pad_rows(x2, block)
    grid = (dy2.shape[0] // block,)
    sdy, sdyx = pl.pallas_call(
        _bwd_reduce_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, c), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block, c), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, c), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _sds((1, c), jnp.float32, dy2),
            _sds((1, c), jnp.float32, dy2),
        ],
        scratch_shapes=[pltpu.VMEM((2, c), jnp.float32)],
        interpret=_interpret(),
    )(dy2, x2, mean[None], invstd[None])
    return sdy[0], sdyx[0]


# -- fused custom-vjp batch norm -----------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_batch_norm(x, weight, bias, eps: float, axis_name: str | None):
    """Training-mode BN forward via Pallas kernels, with the hand-derived
    backward of the reference (``[torch] nn/modules/_functions.py:128-180``):
    forward psums (Σx, Σx², n); backward psums (Σdy, Σdy·x̂) — byte-for-byte
    the reference's two collectives, fused kernels in between.

    Returns ``(y, mean, var, count)`` (stats needed for the running-stat
    update, which stays outside the differentiable path)."""
    y, mean, var, count, _ = _fbn_fwd_impl(x, weight, bias, eps, axis_name)
    return y, mean, var, count


def _fbn_fwd_impl(x, weight, bias, eps, axis_name):
    from tpu_syncbn.ops.batch_norm import fold_scale_shift

    # pad the (M, C) view ONCE; both kernels share it
    x2, c = _as_2d(x)
    block = _block_m(c, x.dtype.itemsize)
    x2p, m = _pad_rows(x2, block)
    s, sq = _stats_2d(x2p, c, block)
    count = jnp.float32(m)
    if axis_name is not None:
        s, sq, count = jax.lax.psum((s, sq, count), axis_name)
    mean, var = moments_from_stats(s, sq, count)
    scale, shift = fold_scale_shift(mean, var, weight, bias, eps)
    y = _normalize_2d(x2p, scale, shift, c, x.dtype, block)[:m].reshape(x.shape)
    invstd = jax.lax.rsqrt(var + eps)
    return y, mean, var, count, invstd


def _unwrap_primal(p):
    from jax.custom_derivatives import CustomVJPPrimal

    return p.value if isinstance(p, CustomVJPPrimal) else p


def _fbn_fwd(x, weight, bias, eps, axis_name):
    # symbolic_zeros=True wraps each diff argument in CustomVJPPrimal
    x, weight, bias = map(_unwrap_primal, (x, weight, bias))
    y, mean, var, count, invstd = _fbn_fwd_impl(x, weight, bias, eps, axis_name)
    return (y, mean, var, count), (x, weight, bias, mean, invstd, count)


def _fbn_bwd(eps, axis_name, res, cts):
    from jax.custom_derivatives import SymbolicZero

    x, weight, bias, mean, invstd, count = res
    dy, *stat_cts = cts
    # The mean/var/count outputs feed the (no-grad) running-buffer update
    # only, as in the reference where that update happens inside a no-grad
    # kernel; this VJP defines no gradient for them. symbolic_zeros lets us
    # verify the caller isn't differentiating through them — silently
    # returning zero for a requested gradient would be a wrong answer.
    for name, ct in zip(("mean", "var", "count"), stat_cts):
        if not isinstance(ct, SymbolicZero):
            raise ValueError(
                f"fused_batch_norm defines no gradient for its '{name}' "
                "statistic output (stats feed the no-grad running-buffer "
                "update only); apply jax.lax.stop_gradient to the stats or "
                "differentiate through y alone"
            )
    if isinstance(dy, SymbolicZero):  # only stats were used downstream
        dy = jnp.zeros(dy.shape, dy.dtype)

    sum_dy, sum_dy_xhat = bn_backward_reduce(dy, x, mean, invstd)

    # grad wrt weight/bias use the LOCAL per-replica sums: the reference
    # computes them from the local backward_reduce (_functions.py:145-158)
    # and lets DDP's gradient all-reduce aggregate across replicas — here
    # the outer grad aggregation (shard_map transpose / trainer pmean)
    # plays that role. Using the psum'd sums would double-count by world.
    grad_weight = None if weight is None else sum_dy_xhat
    grad_bias = None if bias is None else sum_dy

    if axis_name is not None:
        # the reference's backward all_reduce(SUM) of [sum_dy, sum_dy_xmu]
        # (_functions.py:160-165) — feeds dx only
        sum_dy, sum_dy_xhat = jax.lax.psum((sum_dy, sum_dy_xhat), axis_name)

    # batch_norm_backward_elemt: dx = (dy - Σdy/n - x̂·Σdy·x̂/n)·invstd·γ
    c = x.shape[-1]
    w = jnp.ones((c,), jnp.float32) if weight is None else weight.astype(jnp.float32)
    mean_dy = sum_dy / count
    mean_dy_xhat = sum_dy_xhat / count

    def dx_fn(xv, dyv):
        xhat = (xv.astype(jnp.float32) - mean) * invstd
        dxv = (
            (dyv.astype(jnp.float32) - mean_dy - xhat * mean_dy_xhat)
            * invstd
            * w
        )
        return dxv.astype(xv.dtype)

    dx = dx_fn(x, dy)
    gw = None if weight is None else grad_weight.astype(weight.dtype)
    gb = None if bias is None else grad_bias.astype(bias.dtype)
    return dx, gw, gb


fused_batch_norm.defvjp(_fbn_fwd, _fbn_bwd, symbolic_zeros=True)
