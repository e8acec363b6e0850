"""End-to-end DP trainer tests: the reference's full recipe (convert →
wrap → shard data → train) on 8 simulated replicas, checking DDP's
contracts (grad averaging == big-batch, buffer sync, no_sync accumulation,
loss decreases)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import nnx

from tpu_syncbn import compat
from tpu_syncbn import data as tdata
from tpu_syncbn import nn as tnn
from tpu_syncbn import parallel, runtime

C_IN, C_MID, NUM_CLASSES = 3, 8, 10
GLOBAL_BATCH = 16


class SmallCNN(nnx.Module):
    def __init__(self, rngs: nnx.Rngs):
        self.conv1 = nnx.Conv(C_IN, C_MID, (3, 3), rngs=rngs)
        self.bn1 = tnn.BatchNorm2d(C_MID)
        self.conv2 = nnx.Conv(C_MID, C_MID, (3, 3), rngs=rngs)
        self.bn2 = tnn.BatchNorm2d(C_MID)
        self.fc = nnx.Linear(C_MID, NUM_CLASSES, rngs=rngs)

    def __call__(self, x):
        x = nnx.relu(self.bn1(self.conv1(x)))
        x = nnx.relu(self.bn2(self.conv2(x)))
        x = x.mean(axis=(1, 2))
        return self.fc(x)


def ce_loss(model, batch):
    x, y = batch
    logits = model(x)
    loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
    acc = (logits.argmax(-1) == y).mean()
    return loss, {"acc": acc}


def make_batch(seed=0, n=GLOBAL_BATCH):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 8, 8, C_IN).astype(np.float32)
    y = rng.randint(0, NUM_CLASSES, size=n).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


def test_dp_syncbn_step_equals_single_device_big_batch():
    """THE DDP contract: one DP step over 8 replicas == one big-batch step
    on a single device (grads pmean'd, SyncBN stats global)."""
    model_dp = tnn.convert_sync_batchnorm(SmallCNN(nnx.Rngs(0)))
    dp = parallel.DataParallel(model_dp, optax.sgd(0.1), ce_loss)
    batch = make_batch(0)
    out = dp.train_step(batch)

    # single-device reference: same init, same data, plain BN, big batch
    model_ref = SmallCNN(nnx.Rngs(0))
    graphdef, params, rest = nnx.split(model_ref, nnx.Param, ...)

    def loss_ref(p, r, b):
        m = compat.nnx_merge(graphdef, p, r, copy=True)
        m.train()
        loss, metrics = ce_loss(m, b)
        _, _, new_r = nnx.split(m, nnx.Param, ...)
        return loss, new_r

    (loss_r, new_rest), grads = jax.value_and_grad(loss_ref, has_aux=True)(
        params, rest, batch
    )
    opt = optax.sgd(0.1)
    upd, _ = opt.update(grads, opt.init(params), params)
    params_r = optax.apply_updates(params, upd)

    np.testing.assert_allclose(float(out.loss), float(loss_r), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5
        ),
        dp.params, params_r,
    )
    # running stats equal the big-batch reference's
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        ),
        dp.rest, new_rest,
    )


def test_training_reduces_loss():
    model = tnn.convert_sync_batchnorm(SmallCNN(nnx.Rngs(1)))
    dp = parallel.DataParallel(model, optax.adam(1e-2), ce_loss)
    batch = make_batch(42)  # overfit one batch
    losses = [float(dp.train_step(batch).loss) for _ in range(80)]
    assert losses[-1] < losses[0] * 0.5, losses[::20]


def test_accum_steps_matches_single_step():
    """no_sync parity: accum_steps=4 on one batch == accum_steps=1 for
    models without BN-state coupling (use track_running_stats=False to
    keep microbatch stats out of the comparison)."""

    class NoStatCNN(nnx.Module):
        def __init__(self, rngs):
            self.conv = nnx.Conv(C_IN, C_MID, (3, 3), rngs=rngs)
            self.fc = nnx.Linear(C_MID, NUM_CLASSES, rngs=rngs)

        def __call__(self, x):
            return self.fc(nnx.relu(self.conv(x)).mean(axis=(1, 2)))

    batch = make_batch(7, n=32)  # 4 per replica → microbatches of 1
    outs = {}
    for accum in (1, 4):
        m = NoStatCNN(nnx.Rngs(3))
        dp = parallel.DataParallel(m, optax.sgd(0.05), ce_loss, accum_steps=accum)
        dp.train_step(batch)
        outs[accum] = dp.params
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        ),
        outs[1], outs[4],
    )


def test_eval_step_no_collectives_and_no_mutation():
    model = tnn.convert_sync_batchnorm(SmallCNN(nnx.Rngs(2)))
    dp = parallel.DataParallel(model, optax.sgd(0.1), ce_loss)
    batch = make_batch(1)
    dp.train_step(batch)
    rest_before = jax.tree_util.tree_map(lambda x: np.asarray(x), dp.rest)
    out1 = dp.eval_step(batch)
    out2 = dp.eval_step(batch)
    np.testing.assert_allclose(float(out1.loss), float(out2.loss))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
        dp.rest, rest_before,
    )


def test_eval_step_normalizes_with_train_accumulated_stats():
    """eval_step ↔ training parity (the serving contract): the BN
    running stats that train_step accumulated are exactly what the
    compiled sharded eval_step normalizes with — its loss equals a
    plain local eval forward on the synced-back model (outside any
    mesh, SyncBN's eval fallback uses the running buffers and nothing
    else)."""
    model = tnn.convert_sync_batchnorm(SmallCNN(nnx.Rngs(4)))
    dp = parallel.DataParallel(model, optax.sgd(0.1), ce_loss)
    for s in range(3):
        dp.train_step(make_batch(s))
    batch = make_batch(9)
    out = dp.eval_step(batch)

    m = dp.sync_to_model()
    m.eval()
    # the stats in play really are the train-accumulated ones
    assert int(m.bn1.num_batches_tracked[...]) == 3
    local_loss, local_metrics = ce_loss(m, batch)
    np.testing.assert_allclose(float(out.loss), float(local_loss),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(float(out.metrics["acc"]),
                               float(local_metrics["acc"]), atol=1e-6)

    # sensitivity control: perturb the running stats and eval_step's
    # answer must move — it is normalizing with these buffers, not
    # recomputing batch statistics
    m.bn1.running_mean.value = m.bn1.running_mean[...] + 10.0
    dp2 = parallel.DataParallel(m, optax.sgd(0.1), ce_loss)
    out2 = dp2.eval_step(batch)
    assert abs(float(out2.loss) - float(out.loss)) > 1e-3


def test_full_recipe_end_to_end():
    """The reference's six steps, in our framework, as a user would write
    them (README.md:9-103), on 8 simulated chips."""
    # step 2 analogue: init + mesh
    runtime.initialize()
    mesh = runtime.data_parallel_mesh()
    # step 3: model + convert
    model = tnn.convert_sync_batchnorm(SmallCNN(nnx.Rngs(0)))
    # step 4: DDP wrap
    dp = parallel.DataParallel(model, optax.sgd(0.05), ce_loss, mesh=mesh)
    # step 5: sharded data
    ds = tdata.SyntheticImageDataset(length=64, shape=(8, 8, C_IN))
    sampler = tdata.DistributedSampler(len(ds), num_replicas=1, rank=0, seed=0)
    loader = tdata.DataLoader(ds, batch_size=GLOBAL_BATCH, sampler=sampler,
                              num_workers=2, drop_last=True)
    # train loop (step 6 is the launcher; covered in test_launcher)
    for epoch in range(2):
        sampler.set_epoch(epoch)
        for batch in tdata.device_prefetch(
            iter(loader), sharding=dp.batch_sharding
        ):
            out = dp.train_step(batch)
    assert np.isfinite(float(out.loss))
    # rank-0 logging convention (step 0, README.md:9)
    runtime.master_print(f"final loss {float(out.loss):.4f}")
    trained = dp.sync_to_model()
    assert int(trained.bn1.num_batches_tracked[...]) == 8  # 4 steps × 2 epochs


class _BNOnly(nnx.Module):
    """Just a BatchNorm — lets tests compute expected buffer values by hand."""

    def __init__(self):
        self.bn = tnn.BatchNorm2d(C_IN)

    def __call__(self, x):
        return self.bn(x)


def bn_loss(model, batch):
    x, _ = batch
    return (model(x) ** 2).mean()


def test_plain_bn_buffers_follow_replica0_with_broadcast():
    """Unconverted model + broadcast_buffers=True: after a step, the
    replicated buffers hold REPLICA 0's local stats (DDP's forward buffer
    broadcast, [torch] nn/parallel/distributed.py:793)."""
    dp = parallel.DataParallel(_BNOnly(), optax.sgd(0.0), bn_loss)
    batch = make_batch(9)
    dp.train_step(batch)
    # replica 0 owns rows [:2] of the global batch of 16 over 8 replicas
    x0 = np.asarray(batch[0][:2]).reshape(-1, C_IN)
    expected_rm = 0.1 * x0.mean(0)  # momentum=0.1, initial buffer 0
    rm = np.asarray(dp.sync_to_model().bn.running_mean[...])
    np.testing.assert_allclose(rm, expected_rm, rtol=1e-5, atol=1e-6)


def test_plain_bn_buffers_per_replica_without_broadcast():
    """broadcast_buffers=False: buffers are stored honestly per-replica
    ((world, C) sharded), each replica holding ITS local stats — torch's
    local-buffer behavior, never falsely marked replicated."""
    dp = parallel.DataParallel(
        _BNOnly(), optax.sgd(0.0), bn_loss, broadcast_buffers=False
    )
    batch = make_batch(11)
    dp.train_step(batch)
    # locate the running_mean leaf: shape (8, C_IN)
    leaves = [np.asarray(l) for l in jax.tree_util.tree_leaves(dp.rest)]
    rm_all = next(l for l in leaves if l.shape == (8, C_IN) and not np.allclose(l, 1.0))
    x = np.asarray(batch[0])
    for r in range(8):
        xr = x[r * 2 : (r + 1) * 2].reshape(-1, C_IN)
        np.testing.assert_allclose(
            rm_all[r], 0.1 * xr.mean(0), rtol=1e-5, atol=1e-6
        )
    # sync_to_model picks replica 0
    rm0 = np.asarray(dp.sync_to_model().bn.running_mean[...])
    np.testing.assert_allclose(rm0, rm_all[0], rtol=1e-6)


def test_accum_validation():
    with pytest.raises(ValueError):
        parallel.DataParallel(
            SmallCNN(nnx.Rngs(0)), optax.sgd(0.1), ce_loss, accum_steps=0
        )


def test_remat_matches_standard_step():
    """jax.checkpoint must not change step numerics, only memory/FLOPs."""
    batch = make_batch(21)
    outs = {}
    for remat in (False, True):
        m = tnn.convert_sync_batchnorm(SmallCNN(nnx.Rngs(4)))
        dp = parallel.DataParallel(m, optax.sgd(0.05), ce_loss, remat=remat)
        out = dp.train_step(batch)
        outs[remat] = (float(out.loss), dp.params)
    assert outs[False][0] == pytest.approx(outs[True][0], rel=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        ),
        outs[False][1], outs[True][1],
    )


def test_grad_compression_bf16():
    """bf16 grad compression: the gradient all-reduce runs on bf16 buffers
    (HLO-verified) and training stays close to the uncompressed step."""
    batch = make_batch(33)
    outs = {}
    for comp in (None, "bf16"):
        m = tnn.convert_sync_batchnorm(SmallCNN(nnx.Rngs(6)))
        dp = parallel.DataParallel(
            m, optax.sgd(0.05), ce_loss, grad_compression=comp
        )
        out = dp.train_step(batch)
        outs[comp] = (float(out.loss), dp.params)
    # identical forward loss (compression only affects grads)
    assert outs[None][0] == pytest.approx(outs["bf16"][0], rel=1e-6)
    # parameters close but not necessarily identical
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0.02, atol=1e-4
        ),
        outs[None][1], outs["bf16"][1],
    )
    # Lowered program: gradient all_reduces consume bf16 tensors. (The CPU
    # backend may fold the round-trip back to f32 at compile — excess
    # precision is allowed — but the wire-format request is what TPU honors.)
    m2 = tnn.convert_sync_batchnorm(SmallCNN(nnx.Rngs(6)))
    dp2 = parallel.DataParallel(
        m2, optax.sgd(0.05), ce_loss, grad_compression="bf16", donate=False
    )
    txt = dp2._train_step.lower(
        dp2.params, dp2.rest, dp2.opt_state, batch
    ).as_text()
    assert "tensor<bf16>" in txt and "all_reduce" in txt
    # and the uncompressed trainer lowers no bf16 reduction body
    m3 = tnn.convert_sync_batchnorm(SmallCNN(nnx.Rngs(6)))
    dp3 = parallel.DataParallel(m3, optax.sgd(0.05), ce_loss, donate=False)
    txt3 = dp3._train_step.lower(
        dp3.params, dp3.rest, dp3.opt_state, batch
    ).as_text()
    assert "tensor<bf16>" not in txt3


def test_grad_compression_validation():
    with pytest.raises(ValueError, match="grad_compression"):
        parallel.DataParallel(
            SmallCNN(nnx.Rngs(0)), optax.sgd(0.1), ce_loss,
            grad_compression="fp8",
        )


def test_lowered_train_step_cost_analysis():
    # public AOT-lowering hook: flops must be available from the lowered
    # (pre-compile) module
    m = tnn.convert_sync_batchnorm(SmallCNN(nnx.Rngs(0)))
    dp = parallel.DataParallel(m, optax.sgd(0.05), ce_loss, donate=False)
    batch = (
        jnp.zeros((8, 8, 8, 3), jnp.float32),
        jnp.zeros((8,), jnp.int32),
    )
    cost = dp.lowered_train_step(batch).cost_analysis()
    assert cost.get("flops", 0) > 0


def _resnet_trainer(chips):
    from tpu_syncbn.models.resnet import Bottleneck, ResNet

    model = tnn.convert_sync_batchnorm(ResNet(
        Bottleneck, (1, 1, 1, 1), num_classes=10, width=8,
        dtype=jnp.bfloat16, rngs=nnx.Rngs(0)))
    dp = parallel.DataParallel(
        model, optax.sgd(0.1, momentum=0.9),
        lambda m, b: optax.softmax_cross_entropy_with_integer_labels(
            m(b[0]).astype(jnp.float32), b[1]).mean(),
        mesh=runtime.data_parallel_mesh(chips))
    n = 2 * chips
    return dp, (jnp.zeros((n, 32, 32, 3)), jnp.zeros((n,), jnp.int32))


def _retinanet_trainer():
    from tpu_syncbn.models import retinanet as rn
    from tpu_syncbn.models.resnet import BasicBlock, ResNet

    rngs = nnx.Rngs(0)
    model = tnn.convert_sync_batchnorm(rn.RetinaNet(
        num_classes=5, image_size=(64, 64), fpn_channels=16,
        backbone=ResNet(BasicBlock, (1, 1, 1, 1), num_classes=1, width=8,
                        rngs=rngs), rngs=rngs))
    opt = optax.chain(optax.clip_by_global_norm(35.0),
                      optax.add_decayed_weights(1e-4),
                      optax.sgd(0.01, momentum=0.9))
    dp = parallel.DataParallel(model, opt, lambda m, b: m.loss(*b),
                               mesh=runtime.data_parallel_mesh(1))
    batch = (jnp.zeros((2, 64, 64, 3)), jnp.zeros((2, 4, 4)),
             jnp.zeros((2, 4), jnp.int32), jnp.zeros((2, 4), bool))
    return dp, batch


def _looped_lm_trainer():
    from tpu_syncbn.models.looped_lm import LoopedDecoderLM

    model = LoopedDecoderLM(
        vocab_size=96, hidden_size=32, num_heads=4, head_dim=8,
        intermediate_size=48, num_layers=2, loops=4, rope_theta=1e4,
        exit_beta=0.1, attn_impl="xla", rngs=nnx.Rngs(0))
    dp = parallel.DataParallel(
        model, optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1),
        lambda m, b: m.loss(*b), mesh=runtime.data_parallel_mesh(1))
    tokens = jnp.zeros((2, 16), jnp.int32)
    return dp, (tokens, tokens)


@pytest.mark.parametrize("build", [
    pytest.param(lambda: _resnet_trainer(1), id="resnet-sgd-1chip"),
    pytest.param(lambda: _resnet_trainer(4), id="resnet-sgd-mesh4"),
    pytest.param(_retinanet_trainer, id="retinanet-sgd-wd-clip"),
    pytest.param(_looped_lm_trainer, id="looped-lm-adamw"),
])
def test_two_constructions_lower_to_identical_text(build):
    """Two independent constructions of a trainer lower to the same text:
    same text + same jit options is the same compile-cache key, so a
    later process's first step loads what an earlier one compiled (the
    benchmark's ``setup_s`` and ``cache_misses`` rest on it). One case
    for each trainer a cell of the benchmark builds, at toy sizes."""
    texts = []
    for _ in range(2):
        dp, batch = build()
        texts.append(dp.lowered_train_step(batch).as_text())
    assert "func.func public @main" in texts[0]
    assert texts[0] == texts[1]


def test_vma_unvarying_grad_transpose_pinned():
    """Pin the VMA-mode AD semantics behind round 1's "8x off" BN grads:
    under shard_map(check_vma=True), differentiating a *replicated*
    (unvarying) param against sharded data returns a grad that is ALREADY
    psum'd across replicas — the implicit pvary at the param's use
    transposes to a psum. Casting the param to varying OUTSIDE the VJP
    keeps the grad local. The trainer relies on exactly this pair of
    facts (see _microbatch_grads); if a jax upgrade changes either, this
    fails loudly before any silent numeric drift."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = runtime.data_parallel_mesh()
    world = int(mesh.shape["data"])
    x = jnp.arange(float(world * 2)).reshape(world * 2)

    def body(w, xs):
        loss = lambda w: (w * xs).sum()
        g_auto = jax.grad(loss)(w)  # unvarying param: transpose psums
        w_var = jax.lax.pcast(w, "data", to="varying")
        g_local = jax.grad(loss)(w_var)  # varying param: local grad
        return g_auto, jax.lax.psum(g_local, "data")

    f = jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=(P(), P("data")), out_specs=(P(), P()),
            check_vma=True,
        )
    )
    g_auto, g_local_sum = f(jnp.float32(2.0), x)
    # the no-collective autodiff grad already equals the GLOBAL sum:
    np.testing.assert_allclose(np.asarray(g_auto), np.asarray(x.sum()))
    # and explicitly psum'ing the local grads gives the same — so doing
    # BOTH (autodiff through unvarying + explicit psum/pmean) would
    # double-count by exactly the world size
    np.testing.assert_allclose(np.asarray(g_local_sum), np.asarray(x.sum()))


def test_auto_buffer_broadcast_skips_wasted_allreduce():
    """broadcast_buffers='auto' on a fully-converted (SyncBN) model skips
    the per-step DDP buffer broadcast: fewer all-reduces in the compiled
    step than broadcast_buffers=True, and bit-identical training math."""
    import re

    batch = (
        jnp.asarray(np.random.RandomState(3).randn(GLOBAL_BATCH, 8, 8, 3),
                    jnp.float32),
        jnp.asarray(np.random.RandomState(4).randint(
            0, NUM_CLASSES, GLOBAL_BATCH), jnp.int32),
    )

    def build(mode):
        m = tnn.convert_sync_batchnorm(SmallCNN(nnx.Rngs(0)))
        return parallel.DataParallel(
            m, optax.sgd(0.05), ce_loss, broadcast_buffers=mode, donate=False
        )

    def n_allreduce(dp):
        hlo = dp.lowered_train_step(batch).compile().as_text()
        return len(re.findall(r" all-reduce(?:-start)?\(", hlo))

    dp_auto, dp_bcast = build("auto"), build(True)
    assert not dp_auto._per_step_broadcast
    assert dp_bcast._per_step_broadcast
    n_auto, n_bcast = n_allreduce(dp_auto), n_allreduce(dp_bcast)
    assert n_auto < n_bcast, (n_auto, n_bcast)

    out_a = dp_auto.train_step(batch)
    out_b = dp_bcast.train_step(batch)
    np.testing.assert_allclose(float(out_a.loss), float(out_b.loss), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        ),
        dp_auto.params, dp_bcast.params,
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        ),
        dp_auto.rest, dp_bcast.rest,
    )


def test_auto_buffer_broadcast_keeps_broadcast_for_plain_bn():
    m = _BNOnly()  # plain BatchNorm: stats are NOT replicated-safe
    dp = parallel.DataParallel(
        m, optax.sgd(0.05),
        lambda mo, b: jnp.mean(mo(b[0]) ** 2), broadcast_buffers="auto",
        donate=False,
    )
    assert dp._per_step_broadcast


def test_broadcast_buffers_rejects_bad_value():
    with pytest.raises(ValueError, match="broadcast_buffers"):
        parallel.DataParallel(
            SmallCNN(nnx.Rngs(0)), optax.sgd(0.1), ce_loss,
            broadcast_buffers="sometimes",
        )


def test_dp_composes_with_2d_mesh():
    """The mesh-ready extension-point claim (docs/DESIGN.md §8): the DP
    trainer works unchanged when the mesh has an extra (model) axis it
    doesn't use — params replicate over both axes, batch shards over
    "data" only, and the step matches the 1-D-mesh result."""
    from jax.sharding import Mesh

    devs = np.asarray(jax.devices()).reshape(4, 2)
    mesh2d = Mesh(devs, ("data", "model"))
    mesh1d = Mesh(np.asarray(jax.devices()[:4]), ("data",))

    rng = np.random.RandomState(0)
    batch = (
        jnp.asarray(rng.randn(8, 8, 8, 3).astype(np.float32)),
        jnp.asarray(rng.randint(0, NUM_CLASSES, 8).astype(np.int32)),
    )

    def build(mesh):
        m = tnn.convert_sync_batchnorm(SmallCNN(nnx.Rngs(0)))
        return parallel.DataParallel(
            m, optax.sgd(0.05), ce_loss, mesh=mesh, donate=False
        )

    dp2 = build(mesh2d)
    out2 = dp2.train_step(jax.device_put(batch, dp2.batch_sharding))
    dp1 = build(mesh1d)
    out1 = dp1.train_step(jax.device_put(batch, dp1.batch_sharding))
    np.testing.assert_allclose(float(out2.loss), float(out1.loss), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        dp2.params, dp1.params,
    )


class TestScannedTrainSteps:
    """train_steps(batch, n) — n optimizer steps in ONE compiled program
    (on-device lax.scan, no per-step host dispatch) — must be exactly n
    sequential train_step calls: same params, same BN running stats,
    same optimizer state, same per-step losses."""

    def _build(self, donate=False):
        m = tnn.convert_sync_batchnorm(SmallCNN(nnx.Rngs(0)))
        return parallel.DataParallel(
            m, optax.sgd(0.05, momentum=0.9), ce_loss, donate=donate
        )

    @pytest.mark.parametrize("donate", [False, True])
    def test_matches_sequential_steps(self, donate):
        # donate=True is the production default (and what the on-chip
        # scan_dispatch stage runs): the scanned jit must donate state
        # but never the batch, which every iteration re-reads
        batch = make_batch(11)
        dp_seq = self._build(donate)
        seq_losses = [float(dp_seq.train_step(batch).loss) for _ in range(3)]
        dp_scan = self._build(donate)
        out = dp_scan.train_steps(batch, 3)
        assert out.loss.shape == (3,)
        np.testing.assert_allclose(
            np.asarray(out.loss), np.asarray(seq_losses), rtol=1e-5
        )
        for name, a, b in (
            ("params", dp_scan.params, dp_seq.params),
            ("rest", dp_scan.rest, dp_seq.rest),
            ("opt", dp_scan.opt_state, dp_seq.opt_state),
        ):
            jax.tree_util.tree_map(
                lambda x, y: np.testing.assert_allclose(
                    np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6,
                    err_msg=name,
                ),
                a, b,
            )

    def test_composes_with_train_step_and_caches(self):
        batch = make_batch(12)
        dp = self._build()
        dp.train_step(batch)
        out = dp.train_steps(batch, 2)
        assert out.loss.shape == (2,)
        assert 2 in dp._train_steps_cache
        dp.train_steps(batch, 2)  # cache hit, state threads on
        dp.train_step(batch)  # and back to single steps
        assert np.isfinite(float(dp.train_step(batch).loss))

    def test_rejects_bad_n(self):
        dp = self._build()
        with pytest.raises(ValueError, match="n_steps"):
            dp.train_steps(make_batch(13), 0)

    @pytest.mark.parametrize("kwargs", [{"zero": True}, {"accum_steps": 2}],
                             ids=["zero", "accum"])
    def test_composes_with_zero_and_accum(self, kwargs):
        """The scanned loop shares the step body with the single-step
        path, so it must compose with the orthogonal trainer modes:
        ZeRO-sharded state and microbatch accumulation — with the FULL
        state equal to sequential steps (params, BN running stats,
        optimizer state), not just the loss."""
        batch = make_batch(14)

        def build():
            m = tnn.convert_sync_batchnorm(SmallCNN(nnx.Rngs(0)))
            return parallel.DataParallel(
                m, optax.sgd(0.05, momentum=0.9), ce_loss,
                donate=False, **kwargs,
            )

        dp_seq = build()
        seq = [float(dp_seq.train_step(batch).loss) for _ in range(2)]
        dp_scan = build()
        out = dp_scan.train_steps(batch, 2)
        np.testing.assert_allclose(np.asarray(out.loss), seq, rtol=1e-5)
        for name, a, b in (
            ("params", dp_scan.params, dp_seq.params),
            ("rest", dp_scan.rest, dp_seq.rest),
            ("opt", dp_scan.opt_state, dp_seq.opt_state),
        ):
            jax.tree_util.tree_map(
                lambda x, y: np.testing.assert_allclose(
                    np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6,
                    err_msg=name),
                a, b,
            )
