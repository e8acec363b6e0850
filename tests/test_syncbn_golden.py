"""The anchor golden test (SURVEY §4): SyncBN over N replicas with per-replica
batch B must exactly equal plain BN over one replica with batch N×B — same
normalized output, same running-stats update, same gradients. This is the
defining property of the reference repo."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
from tpu_syncbn.compat import shard_map
from jax.sharding import PartitionSpec as P

from tpu_syncbn import runtime
from tpu_syncbn.ops import batch_norm as ops

N = 8          # replicas
B, C, H, W = 2, 4, 3, 3   # small per-replica batch — the SyncBN use case


def _global_x(seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(N * B, H, W, C) * 1.7 + 0.3).astype(np.float32)


def test_syncbn_equals_big_batch_bn_forward_and_stats():
    mesh = runtime.data_parallel_mesh()
    x = _global_x()
    w = jnp.asarray(np.random.RandomState(1).uniform(0.5, 1.5, C).astype(np.float32))
    b = jnp.asarray(np.random.RandomState(2).uniform(-0.5, 0.5, C).astype(np.float32))
    rm, rv, nbt = jnp.zeros(C), jnp.ones(C), jnp.zeros((), jnp.int32)

    def synced(xs, rm, rv, nbt):
        y, (rm2, rv2, nbt2) = ops.batch_norm_train(
            xs, rm, rv, nbt, w, b, momentum=0.1, axis_name="data"
        )
        return y, rm2, rv2, nbt2

    f = shard_map(
        synced, mesh=mesh,
        in_specs=(P("data"), P(), P(), P()),
        out_specs=(P("data"), P(), P(), P()),
    )
    y_sync, rm_s, rv_s, nbt_s = f(jnp.asarray(x), rm, rv, nbt)

    # single-replica big-batch reference
    y_ref, (rm_r, rv_r, nbt_r) = ops.batch_norm_train(
        jnp.asarray(x), rm, rv, nbt, w, b, momentum=0.1
    )
    np.testing.assert_allclose(np.asarray(y_sync), np.asarray(y_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(rm_s), np.asarray(rm_r), rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(rv_s), np.asarray(rv_r), rtol=1e-6, atol=1e-7)
    assert int(nbt_s) == int(nbt_r) == 1

    # and against torch big-batch BN as the independent oracle
    bn = torch.nn.BatchNorm2d(C, momentum=0.1)
    with torch.no_grad():
        bn.weight.copy_(torch.from_numpy(np.asarray(w)))
        bn.bias.copy_(torch.from_numpy(np.asarray(b)))
    yt = bn(torch.from_numpy(np.transpose(x, (0, 3, 1, 2))))
    np.testing.assert_allclose(
        np.asarray(y_sync), np.transpose(yt.detach().numpy(), (0, 2, 3, 1)),
        rtol=1e-4, atol=1e-5,
    )
    np.testing.assert_allclose(np.asarray(rv_s), bn.running_var.numpy(), rtol=1e-5, atol=1e-6)


def test_syncbn_equals_big_batch_bn_gradients():
    """Backward: the psum's autodiff must reproduce the reference's
    all_reduce([sum_dy, sum_dy_xmu]) semantics — per-input grads under
    N-replica SyncBN equal big-batch BN grads."""
    mesh = runtime.data_parallel_mesh()
    x = _global_x(7)
    w = jnp.asarray(np.random.RandomState(3).uniform(0.5, 1.5, C).astype(np.float32))
    b = jnp.zeros(C)
    coeff = jnp.asarray(
        np.random.RandomState(4).randn(N * B, H, W, C).astype(np.float32)
    )

    def local_loss(xs, ws, cs):
        y, _ = ops.batch_norm_train(xs, None, None, None, ws, b, axis_name="data")
        # global-mean loss: each replica contributes its local term / world
        from tpu_syncbn import parallel
        return parallel.psum(jnp.sum(y * cs), "data") / (N * B)

    def grads_sync(xg, wg):
        f = shard_map(
            lambda xs, cs, ws: local_loss(xs, ws, cs),
            mesh=mesh,
            in_specs=(P("data"), P("data"), P()),
            out_specs=P(),
        )
        return jax.grad(lambda xx, ww: f(xx, coeff, ww).sum(), argnums=(0, 1))(xg, wg)

    gx_s, gw_s = grads_sync(jnp.asarray(x), w)

    def big_loss(xg, wg):
        y, _ = ops.batch_norm_train(xg, None, None, None, wg, b)
        return jnp.sum(y * coeff) / (N * B)

    gx_r, gw_r = jax.grad(big_loss, argnums=(0, 1))(jnp.asarray(x), w)
    np.testing.assert_allclose(np.asarray(gx_s), np.asarray(gx_r), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw_s), np.asarray(gw_r), rtol=1e-4, atol=1e-4)


def test_uneven_shards_count_weighted():
    """Replicas with different valid counts: count-weighted sync must equal
    BN over the concatenated valid rows (the _functions.py:50-62 contract)."""
    mesh = runtime.data_parallel_mesh()
    x = _global_x(9)
    counts = np.asarray([2, 1, 2, 0, 1, 2, 1, 2])  # per-replica valid rows (≤ B)
    mask_np = (np.arange(B)[None, :] < counts[:, None]).astype(np.float32)
    mask = jnp.asarray(mask_np.reshape(N * B, 1, 1, 1))

    def f(xs, ms):
        mean, var, count = ops.sync_moments(xs, axis_name="data", mask=ms)
        return jnp.stack([mean, var])[None]

    out = shard_map(
        f, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P("data", None, None)
    )(jnp.asarray(x), mask)
    out = np.asarray(out)

    valid_rows = np.concatenate(
        [x[r * B : r * B + counts[r]] for r in range(N)], axis=0
    ).reshape(-1, C)
    got_mean, got_var = out[0, 0], out[0, 1]
    np.testing.assert_allclose(got_mean, valid_rows.mean(0), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_var, valid_rows.var(0), rtol=1e-4, atol=1e-5)
    # all replicas agree
    for r in range(1, N):
        np.testing.assert_allclose(out[r], out[0], rtol=1e-6, atol=1e-7)


def test_eval_mode_emits_zero_collectives():
    """The compiled eval step must contain no cross-replica communication
    ([torch] nn/modules/batchnorm.py:836-842 fallback contract)."""
    mesh = runtime.data_parallel_mesh()
    rm, rv = jnp.zeros(C), jnp.ones(C)
    w = jnp.ones(C)

    def eval_step(xs):
        return ops.batch_norm_inference(xs, rm, rv, w, None)

    f = jax.jit(
        shard_map(eval_step, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"))
    )
    x = jnp.asarray(_global_x(11))
    hlo = f.lower(x).compile().as_text()
    for coll in ("all-reduce", "all-gather", "collective-permute", "all-to-all"):
        assert coll not in hlo, f"eval step contains {coll}"
    f(x).block_until_ready()


def test_train_mode_emits_exactly_one_fused_allreduce():
    """SyncBN forward should lower to a single fused AllReduce for the
    (sum, sumsq, count) triple — 2C+1 floats, the reference's per-layer
    traffic (SURVEY §3.3) in one collective."""
    mesh = runtime.data_parallel_mesh()
    w = jnp.ones(C)

    def train_step(xs):
        y, _ = ops.batch_norm_train(xs, None, None, None, w, None, axis_name="data")
        return y

    f = jax.jit(
        shard_map(train_step, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"))
    )
    hlo = f.lower(jnp.asarray(_global_x(12))).compile().as_text()
    import re

    # count all-reduce instruction definitions (sync `%all-reduce = ...` or
    # async `%all-reduce-start = ...`; either fuses the (sum,sumsq,count)
    # triple into ONE tuple-shaped collective)
    n_ar = len(re.findall(r"%all-reduce(?:-start)?(?:\.\d+)? = ", hlo))
    assert n_ar == 1, f"expected exactly 1 fused all-reduce, got {n_ar}"
    # no all_gather of per-replica stats (the reference's extra collective)
    assert "all-gather" not in hlo
