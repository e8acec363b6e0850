"""Module-level tests: BatchNorm/SyncBatchNorm nnx modules and the
convert_sync_batchnorm tree rewrite (drop-in contract of
[torch] nn/modules/batchnorm.py:889-951)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
from flax import nnx
from tpu_syncbn import compat
from tpu_syncbn.compat import shard_map
from jax.sharding import PartitionSpec as P

from tpu_syncbn import nn as tnn
from tpu_syncbn import runtime

N, B, C, H, W = 8, 2, 4, 3, 3


def rand_x(seed=0, n=N * B):
    return np.random.RandomState(seed).randn(n, H, W, C).astype(np.float32)


def test_batchnorm_module_matches_torch():
    bn = tnn.BatchNorm2d(C)
    tbn = torch.nn.BatchNorm2d(C)
    x = rand_x()
    for step in range(2):
        x = rand_x(step)
        y = bn(jnp.asarray(x))
        yt = tbn(torch.from_numpy(np.transpose(x, (0, 3, 1, 2))))
        np.testing.assert_allclose(
            np.asarray(y), np.transpose(yt.detach().numpy(), (0, 2, 3, 1)),
            rtol=1e-4, atol=1e-5,
        )
    np.testing.assert_allclose(
        np.asarray(bn.running_var[...]), tbn.running_var.numpy(), rtol=1e-5, atol=1e-6
    )
    assert int(bn.num_batches_tracked[...]) == 2


def test_eval_mode_via_nnx_eval():
    bn = tnn.BatchNorm2d(C)
    x = jnp.asarray(rand_x())
    bn(x)  # one train step
    bn.eval()
    assert bn.use_running_average
    y1 = bn(x)
    y2 = bn(x)  # eval must not mutate stats
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))
    assert int(bn.num_batches_tracked[...]) == 1
    bn.train()
    assert not bn.use_running_average


def test_syncbn_outside_mesh_falls_back_to_local():
    """SyncBatchNorm outside shard_map == plain BN (world-size-1 fallback,
    [torch] nn/modules/batchnorm.py:837-873)."""
    sbn = tnn.SyncBatchNorm(C)
    bn = tnn.BatchNorm2d(C)
    x = jnp.asarray(rand_x(3))
    np.testing.assert_allclose(np.asarray(sbn(x)), np.asarray(bn(x)), rtol=1e-6)


class _Tower(nnx.Module):
    """Nested module tree with BN in attr, list, and dict containers."""

    def __init__(self):
        self.conv = nnx.Conv(C, C, (1, 1), rngs=nnx.Rngs(0))
        self.bn = tnn.BatchNorm2d(C)
        self.blocks = compat.nnx_list([tnn.BatchNorm2d(C), tnn.BatchNorm2d(C)])
        self.named = compat.nnx_dict({"head": tnn.BatchNorm1d(C)})

    def __call__(self, x):
        x = self.conv(x)
        x = self.bn(x)
        for b in self.blocks:
            x = b(x)
        return x


def test_convert_sync_batchnorm_tree_rewrite():
    m = _Tower()
    # move state so we can check it is carried over by reference
    m.bn.running_mean.value = jnp.full((C,), 2.5)
    m.bn.weight.value = jnp.full((C,), 1.5)
    m.eval()
    old_weight_var = m.bn.weight
    old_rm_var = m.bn.running_mean

    out = tnn.convert_sync_batchnorm(m)
    assert out is m
    assert isinstance(m.bn, tnn.SyncBatchNorm)
    assert all(isinstance(b, tnn.SyncBatchNorm) for b in m.blocks)
    assert isinstance(m.named["head"], tnn.SyncBatchNorm)
    assert not isinstance(m.conv, tnn.SyncBatchNorm)
    # variables shared by reference, config and mode preserved
    assert m.bn.weight is old_weight_var
    assert m.bn.running_mean is old_rm_var
    np.testing.assert_allclose(np.asarray(m.bn.running_mean[...]), 2.5)
    assert m.bn.use_running_average  # eval flag carried
    assert m.bn.axis_name == "data"


def test_converted_model_propagates_eval_mode(rng_x=None):
    """Regression (serving contract, ISSUE 5 satellite): on a
    convert_sync_batchnorm-produced tree, nnx's ``model.eval()`` /
    ``model.train()`` must reach every *converted* submodule — attr,
    list, dict, and tuple containers alike — flipping
    ``use_running_average`` so eval normalizes with running stats
    (collective-free) and train goes back to batch stats. A converted
    module that missed the flip would silently serve batch-statistics
    normalization."""
    import collections

    Pair = collections.namedtuple("Pair", ["one", "two"])

    class Mixed(nnx.Module):
        def __init__(self):
            self.tower = _Tower()  # attr + list + dict containers
            # flax 0.12 refuses arrays under an un-annotated tuple
            self.pair = nnx.data(Pair(tnn.BatchNorm1d(C),
                                      nnx.Linear(C, C, rngs=nnx.Rngs(1))))

    m = tnn.convert_sync_batchnorm(Mixed())
    bns = [m.tower.bn, *m.tower.blocks, m.tower.named["head"], m.pair.one]
    assert all(isinstance(b, tnn.SyncBatchNorm) for b in bns)
    assert all(not b.use_running_average for b in bns)

    # accumulate one batch of stats, then flip to eval
    x = jnp.asarray(np.random.RandomState(0).randn(4, 5, 5, C).astype(np.float32))
    m.tower(x)
    m.eval()
    assert all(b.use_running_average for b in bns)
    nbt = int(m.tower.bn.num_batches_tracked[...])
    y1 = m.tower(x)
    y2 = m.tower(x)
    # eval forward is deterministic and mutates nothing
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert int(m.tower.bn.num_batches_tracked[...]) == nbt

    m.train()
    assert all(not b.use_running_average for b in bns)
    m.tower(x)  # train mode tracks again
    assert int(m.tower.bn.num_batches_tracked[...]) == nbt + 1


def test_convert_root_batchnorm():
    bn = tnn.BatchNorm2d(C, momentum=0.3, eps=1e-4)
    out = tnn.convert_sync_batchnorm(bn, axis_name="replica")
    assert isinstance(out, tnn.SyncBatchNorm)
    assert out.momentum == 0.3 and out.eps == 1e-4 and out.axis_name == "replica"


def test_convert_idempotent():
    m = _Tower()
    tnn.convert_sync_batchnorm(m)
    first = m.bn
    tnn.convert_sync_batchnorm(m)
    assert m.bn is first  # already-sync modules untouched


def test_syncbn_module_golden_inside_shard_map():
    """Module-level golden test: converted model over 8 replicas ==
    unconverted model on the full batch."""
    mesh = runtime.data_parallel_mesh()
    x = rand_x(7)

    ref = _Tower()
    y_ref = ref(jnp.asarray(x))

    m = _Tower()
    tnn.convert_sync_batchnorm(m)
    graphdef, state = nnx.split(m)

    def step(state, xs):
        model = nnx.merge(graphdef, state)
        y = model(xs)
        _, new_state = nnx.split(model)
        return y, new_state

    f = shard_map(
        step, mesh=mesh,
        in_specs=(P(), P("data")),
        out_specs=(P("data"), P()),
    )
    y_sync, new_state = f(state, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y_sync), np.asarray(y_ref), rtol=1e-4, atol=1e-5)

    # running stats after the synced step == big-batch reference stats
    nnx.update(m, new_state)
    np.testing.assert_allclose(
        np.asarray(m.bn.running_mean[...]),
        np.asarray(ref.bn.running_mean[...]),
        rtol=1e-5, atol=1e-6,
    )
    assert int(m.bn.num_batches_tracked[...]) == 1


def test_syncbn_eval_no_tracking_stays_local():
    """Eval + track_running_stats=False inside shard_map: torch's need_sync
    requires self.training, so this must use LOCAL batch stats with zero
    collectives ([torch] nn/modules/batchnorm.py:837-860)."""
    mesh = runtime.data_parallel_mesh()
    sbn = tnn.SyncBatchNorm(C, track_running_stats=False)
    sbn.eval()
    graphdef, state = nnx.split(sbn)

    f = jax.jit(
        shard_map(
            lambda st, xs: nnx.merge(graphdef, st)(xs),
            mesh=mesh, in_specs=(P(), P("data")), out_specs=P("data"),
        )
    )
    x = jnp.asarray(rand_x(13))
    hlo = f.lower(state, x).compile().as_text()
    assert "all-reduce" not in hlo and "all-gather" not in hlo
    # per-replica local stats: differs from whole-batch normalization
    y = np.asarray(f(state, x))
    bn_local = tnn.BatchNorm2d(C, track_running_stats=False)
    per_replica = np.concatenate(
        [np.asarray(bn_local(jnp.asarray(np.asarray(x)[i * B : (i + 1) * B])))
         for i in range(N)]
    )
    np.testing.assert_allclose(y, per_replica, rtol=1e-4, atol=1e-5)


class _Hidden(nnx.Module):
    def __init__(self):
        self._bn = tnn.BatchNorm2d(C)  # underscore-named child


def test_convert_reaches_underscore_attrs():
    m = _Hidden()
    tnn.convert_sync_batchnorm(m)
    assert isinstance(m._bn, tnn.SyncBatchNorm)


def test_wrong_rank_raises():
    bn = tnn.BatchNorm2d(C)
    try:
        bn(jnp.zeros((2, 3, C)))
        assert False, "expected ValueError"
    except ValueError as e:
        assert "4D" in str(e)


def test_wrong_channels_raises():
    bn = tnn.BatchNorm2d(C)
    try:
        bn(jnp.zeros((2, 3, 3, C + 1)))
        assert False, "expected ValueError"
    except ValueError as e:
        assert "channels" in str(e)


def test_plain_batchnorm_rejects_axis_name():
    import pytest

    with pytest.raises(ValueError, match="SyncBatchNorm"):
        tnn.BatchNorm2d(C, axis_name="data")


import collections

_BNPair = collections.namedtuple("_BNPair", "a b")


class _WithNamedTuple(nnx.Module):
    def __init__(self):
        # nnx requires explicit nnx.data() for module-bearing namedtuples
        self.pair = compat.nnx_data(_BNPair(tnn.BatchNorm2d(C), tnn.BatchNorm2d(C)))


def test_convert_namedtuple_attr():
    m = _WithNamedTuple()
    tnn.convert_sync_batchnorm(m)
    assert isinstance(m.pair, _BNPair)
    assert isinstance(m.pair.a, tnn.SyncBatchNorm)
    assert isinstance(m.pair.b, tnn.SyncBatchNorm)


def test_syncbn_group_size_syncs_within_subgroups():
    """group_size=4 on 8 replicas: stats sync within each half only — each
    half must match big-batch BN over ITS half (torch process_group
    scoping, [torch] nn/modules/batchnorm.py:706)."""
    mesh = runtime.data_parallel_mesh()
    x = rand_x(31)  # (16, H, W, C): replicas of 2 rows each
    sbn = tnn.SyncBatchNorm(C, group_size=4, track_running_stats=False)
    graphdef, state = nnx.split(sbn)

    f = jax.jit(
        shard_map(
            lambda st, xs: compat.nnx_merge(graphdef, st, copy=True)(xs),
            mesh=mesh, in_specs=(P(), P("data")), out_specs=P("data"),
        )
    )
    y = np.asarray(f(state, jnp.asarray(x)))

    bn_local = tnn.BatchNorm2d(C, track_running_stats=False)
    for half in range(2):
        seg = slice(half * 8, (half + 1) * 8)  # 4 replicas × 2 rows
        expected = np.asarray(bn_local(jnp.asarray(x[seg])))
        np.testing.assert_allclose(y[seg], expected, rtol=1e-4, atol=1e-5)
    # and the two halves genuinely used different stats
    full = np.asarray(bn_local(jnp.asarray(x)))
    assert not np.allclose(y, full, rtol=1e-4, atol=1e-5)


def test_convert_with_group_size():
    m = _Tower()
    tnn.convert_sync_batchnorm(m, group_size=2)
    assert m.bn.group_size == 2


def test_syncbn_arbitrary_group_partition_golden():
    """An arbitrary (non-contiguous) 2-group split of 8 replicas must be
    EXACTLY two independent SyncBNs — torch's process_group accepts any
    rank set ([torch] nn/modules/batchnorm.py:706), not only contiguous
    blocks. Golden: each group's output matches big-batch BN over that
    group's rows, gathered in rank order."""
    mesh = runtime.data_parallel_mesh()
    groups = ((0, 3, 5), (1, 2, 4, 6, 7))
    x = rand_x(37)  # (16, H, W, C): 8 replicas x 2 rows
    sbn = tnn.SyncBatchNorm(
        C, group_size=groups, track_running_stats=False
    )
    graphdef, state = nnx.split(sbn)

    f = jax.jit(
        shard_map(
            lambda st, xs: compat.nnx_merge(graphdef, st, copy=True)(xs),
            mesh=mesh, in_specs=(P(), P("data")), out_specs=P("data"),
        )
    )
    y = np.asarray(f(state, jnp.asarray(x)))

    bn_local = tnn.BatchNorm2d(C, track_running_stats=False)
    rows_of = lambda ranks: np.concatenate(
        [x[2 * r:2 * r + 2] for r in ranks]
    )
    for ranks in groups:
        expected = np.asarray(bn_local(jnp.asarray(rows_of(ranks))))
        got = np.concatenate([y[2 * r:2 * r + 2] for r in ranks])
        np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-5)


def test_convert_normalizes_partition_to_tuples():
    m = _Tower()
    tnn.convert_sync_batchnorm(m, group_size=[[0, 3, 5], [1, 2, 4, 6, 7]])
    assert m.bn.group_size == ((0, 3, 5), (1, 2, 4, 6, 7))


def test_group_size_must_divide_world():
    mesh = runtime.data_parallel_mesh()
    sbn = tnn.SyncBatchNorm(C, group_size=3, track_running_stats=False)
    graphdef, state = nnx.split(sbn)
    f = shard_map(
        lambda st, xs: compat.nnx_merge(graphdef, st, copy=True)(xs),
        mesh=mesh, in_specs=(P(), P("data")), out_specs=P("data"),
    )
    with pytest.raises(ValueError, match="must divide"):
        f(state, jnp.asarray(rand_x(0)))


def test_plain_bn_rejects_group_size():
    with pytest.raises(ValueError, match="SyncBatchNorm"):
        tnn.BatchNorm2d(C, group_size=2)


def test_reconvert_updates_existing_syncbn_scope():
    """torch re-converts SyncBN too: the new process_group wins uniformly."""
    m = _Tower()
    tnn.convert_sync_batchnorm(m)            # full-world
    assert m.bn.group_size is None
    tnn.convert_sync_batchnorm(m, group_size=2)
    assert m.bn.group_size == 2
    assert all(b.group_size == 2 for b in m.blocks)


def test_classmethod_forwards_group_size():
    bn = tnn.BatchNorm2d(C)
    out = tnn.SyncBatchNorm.convert_sync_batchnorm(bn, group_size=4)
    assert isinstance(out, tnn.SyncBatchNorm) and out.group_size == 4


def test_grouped_sync_butterfly_collectives():
    """Power-of-two grouped SyncBN lowers to the ppermute butterfly:
    log2(group) CollectivePermutes of the fused stat triple — NO
    full-world all-gather and NO full-world all-reduce."""
    import re

    mesh = runtime.data_parallel_mesh()
    sbn = tnn.SyncBatchNorm(C, group_size=4, track_running_stats=False)
    graphdef, state = nnx.split(sbn)
    f = jax.jit(
        shard_map(
            lambda st, xs: compat.nnx_merge(graphdef, st, copy=True)(xs),
            mesh=mesh, in_specs=(P(), P("data")), out_specs=P("data"),
            check_vma=False,
        )
    )
    hlo = f.lower(state, jnp.asarray(rand_x(17))).compile().as_text()
    # count by op type (instruction names vary: %all-gather vs %all_gather.7)
    n_ag = len(re.findall(r" all-gather(?:-start)?\(", hlo))
    n_cp = len(re.findall(r" collective-permute(?:-start)?\(", hlo))
    n_ar = len(re.findall(r" all-reduce(?:-start)?\(", hlo))
    assert n_ag == 0, f"expected no all-gather, got {n_ag}"
    assert n_cp == 2, f"expected log2(4)=2 collective-permutes, got {n_cp}"
    assert n_ar == 0, f"expected no full-world all-reduce, got {n_ar}"
