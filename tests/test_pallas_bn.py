"""Pallas BN kernels vs the XLA-fusion path (and therefore vs torch, which
the XLA path is parity-tested against). Run in interpret mode on the CPU
mesh — same kernel code as TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tpu_syncbn.compat import shard_map
from jax.sharding import PartitionSpec as P

from tpu_syncbn import runtime
from tpu_syncbn.ops import batch_norm as xla_ops
from tpu_syncbn.ops import pallas_bn

B, H, W, C = 4, 5, 3, 6


def rand(seed=0, shape=(B, H, W, C)):
    return jnp.asarray(
        np.random.RandomState(seed).randn(*shape).astype(np.float32) * 1.5 + 0.2
    )


def test_bn_stats_matches_xla():
    x = rand(0)
    s_p, sq_p, n_p = pallas_bn.bn_stats(x)
    s_x, sq_x, n_x = xla_ops.batch_norm_stats(x)
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_x), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sq_p), np.asarray(sq_x), rtol=1e-5)
    assert float(n_p) == float(n_x) == B * H * W


def test_bn_stats_nonaligned_rows():
    """M=60 rows is not a multiple of the row block: padding must not
    perturb the sums."""
    x = rand(1, shape=(1, 60, 1, C))
    s_p, sq_p, n_p = pallas_bn.bn_stats(x)
    xf = np.asarray(x).reshape(-1, C)
    np.testing.assert_allclose(np.asarray(s_p), xf.sum(0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sq_p), (xf * xf).sum(0), rtol=1e-5)
    assert float(n_p) == 60


def test_bn_stats_large_multiblock():
    """M > block size exercises the cross-step accumulator."""
    x = rand(2, shape=(8, 16, 16, C))  # M = 2048 = 8 blocks
    s_p, sq_p, _ = pallas_bn.bn_stats(x)
    xf = np.asarray(x).reshape(-1, C)
    np.testing.assert_allclose(np.asarray(s_p), xf.sum(0), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(sq_p), (xf * xf).sum(0), rtol=1e-4)


def test_bn_normalize_matches_xla():
    x = rand(3)
    mean = jnp.asarray(np.random.RandomState(4).randn(C), jnp.float32)
    var = jnp.asarray(np.random.RandomState(5).uniform(0.5, 2, C), jnp.float32)
    w = jnp.asarray(np.random.RandomState(6).uniform(0.5, 1.5, C), jnp.float32)
    b = jnp.asarray(np.random.RandomState(7).randn(C), jnp.float32)
    y_p = pallas_bn.bn_normalize(x, mean, var, w, b, 1e-5)
    y_x = xla_ops.batch_norm_elemt(x, mean, var, w, b, 1e-5)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_x), rtol=1e-5, atol=1e-6)


def test_bn_normalize_no_affine_bf16():
    x = rand(8).astype(jnp.bfloat16)
    mean = jnp.zeros(C)
    var = jnp.ones(C)
    y = pallas_bn.bn_normalize(x, mean, var, None, None, 1e-5)
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(x, np.float32), rtol=0.02, atol=0.02
    )


def test_fused_batch_norm_forward_and_grads_match_xla():
    x = rand(9)
    w = jnp.asarray(np.random.RandomState(10).uniform(0.5, 1.5, C), jnp.float32)
    b = jnp.asarray(np.random.RandomState(11).randn(C), jnp.float32)
    coeff = rand(12)

    def loss_pallas(x, w, b):
        y, _, _, _ = pallas_bn.fused_batch_norm(x, w, b, 1e-5, None)
        return jnp.sum(y * coeff)

    def loss_xla(x, w, b):
        y, _ = xla_ops.batch_norm_train(x, None, None, None, w, b, eps=1e-5)
        return jnp.sum(y * coeff)

    lp, gp = jax.value_and_grad(loss_pallas, argnums=(0, 1, 2))(x, w, b), None
    lx = jax.value_and_grad(loss_xla, argnums=(0, 1, 2))(x, w, b)
    np.testing.assert_allclose(float(lp[0]), float(lx[0]), rtol=1e-5)
    for a, c in zip(lp[1], lx[1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-3, atol=1e-4)


def test_fused_batch_norm_synced_golden():
    """Pallas fused BN over 8 replicas == big-batch XLA BN (fwd + dx)."""
    mesh = runtime.data_parallel_mesh()
    x = rand(13, shape=(16, H, W, C))
    w = jnp.asarray(np.random.RandomState(14).uniform(0.5, 1.5, C), jnp.float32)
    b = jnp.zeros(C)
    coeff = rand(15, shape=(16, H, W, C))

    def local(xs, cs, ws):
        y, mean, var, count = pallas_bn.fused_batch_norm(xs, ws, b, 1e-5, "data")
        return jax.lax.psum(jnp.sum(y * cs), "data")

    f = shard_map(
        local, mesh=mesh,
        in_specs=(P("data"), P("data"), P()),
        out_specs=P(),
        check_vma=False,  # pallas_call outputs carry no vma annotation
    )
    loss_s, (gx_s, gw_s) = jax.value_and_grad(
        lambda xx, ww: f(xx, coeff, ww), argnums=(0, 1)
    )(x, w)

    def big(xx, ww):
        y, _ = xla_ops.batch_norm_train(xx, None, None, None, ww, b, eps=1e-5)
        return jnp.sum(y * coeff)

    loss_r, (gx_r, gw_r) = jax.value_and_grad(big, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(float(loss_s), float(loss_r), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gx_s), np.asarray(gx_r), rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw_s), np.asarray(gw_r), rtol=1e-3, atol=1e-4)


def test_bn_backward_reduce_values():
    x = rand(16)
    dy = rand(17)
    mean = jnp.asarray(np.asarray(x).reshape(-1, C).mean(0))
    var = jnp.asarray(np.asarray(x).reshape(-1, C).var(0))
    invstd = jax.lax.rsqrt(var + 1e-5)
    sdy, sdyx = pallas_bn.bn_backward_reduce(dy, x, mean, invstd)
    dyf = np.asarray(dy).reshape(-1, C)
    xhat = (np.asarray(x).reshape(-1, C) - np.asarray(mean)) * np.asarray(invstd)
    np.testing.assert_allclose(np.asarray(sdy), dyf.sum(0), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(sdyx), (dyf * xhat).sum(0), rtol=1e-4)


def test_module_bn_with_pallas_mode_on():
    """BatchNorm module end-to-end with pallas forced on == pallas off."""
    from tpu_syncbn import nn as tnn
    from tpu_syncbn import ops

    x = rand(20)
    outs = {}
    for mode in ("off", "on"):
        with ops.pallas_mode(mode):
            bn = tnn.BatchNorm2d(C)
            y = bn(x)
            outs[mode] = (np.asarray(y), np.asarray(bn.running_var[...]))
    np.testing.assert_allclose(outs["on"][0], outs["off"][0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs["on"][1], outs["off"][1], rtol=1e-5, atol=1e-6)


def test_default_backend_is_xla_and_opens_no_file(monkeypatch):
    """With no mode set the BN backend is XLA's fusion, and the decision
    reads nothing: tracing a converted model's train step opens no path
    under ``benchmarks/`` (the package chooses no kernel from a harness's
    output directory)."""
    import builtins
    import os

    import optax
    from flax import nnx

    from tpu_syncbn import models, nn as tnn, parallel
    from tpu_syncbn.ops import batch_norm as bn_ops

    if "TPU_SYNCBN_PALLAS" in os.environ:
        pytest.skip("the environment sets a mode")
    assert bn_ops.get_pallas_mode() == "off" and not bn_ops._use_pallas()
    with pytest.raises(ValueError):
        bn_ops.set_pallas_mode("auto")

    real_open = builtins.open
    benchmarks = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks") + os.sep

    def guarded(file, *a, **kw):
        if isinstance(file, (str, os.PathLike)) and os.path.abspath(
                os.fspath(file)).startswith(benchmarks):
            raise AssertionError(f"the package opened {file}")
        return real_open(file, *a, **kw)

    monkeypatch.setattr(builtins, "open", guarded)
    with pytest.raises(AssertionError):  # the guard bites
        open(os.path.join(benchmarks, "artifacts", "zigzag_flops.json"))
    model = tnn.convert_sync_batchnorm(models.resnet18(
        num_classes=4, small_input=True, rngs=nnx.Rngs(0)))
    dp = parallel.DataParallel(
        model, optax.sgd(0.1),
        lambda mo, b: jnp.mean(mo(b[0]) ** 2), donate=False)
    batch = (jnp.zeros((8, 16, 16, 3)), jnp.zeros((8,), jnp.int32))
    text = dp.lowered_train_step(batch).as_text()
    assert "all_reduce" in text and "pallas" not in text


def test_fused_bn_bias_only_grad():
    """Regression: bias-only affine (weight=None, bias given) must produce a
    real bias gradient on the Pallas path, matching the XLA path."""
    x = rand(21)
    b = jnp.asarray(np.random.RandomState(22).randn(C), jnp.float32)
    coeff = rand(23)

    def loss_p(b):
        y, _, _, _ = pallas_bn.fused_batch_norm(x, None, b, 1e-5, None)
        return jnp.sum(y * coeff)

    def loss_x(b):
        y, _ = xla_ops.batch_norm_train(x, None, None, None, None, b, eps=1e-5)
        return jnp.sum(y * coeff)

    gb_p = jax.grad(loss_p)(b)
    gb_x = jax.grad(loss_x)(b)
    assert float(jnp.abs(gb_p).max()) > 0
    np.testing.assert_allclose(np.asarray(gb_p), np.asarray(gb_x), rtol=1e-4, atol=1e-5)


def test_fused_batch_norm_stat_grad_fails_loudly():
    # the VJP defines no gradient for the stat outputs; requesting one must
    # raise, not silently return zeros (advisor finding, round 1)
    x = jnp.asarray(np.random.RandomState(0).randn(4, 8).astype(np.float32))
    w = jnp.ones((8,), jnp.float32)
    b = jnp.zeros((8,), jnp.float32)

    def loss_through_mean(x):
        _, mean, _, _ = pallas_bn.fused_batch_norm(x, w, b, 1e-5, None)
        return mean.sum()

    with pytest.raises(ValueError, match="no gradient for its 'mean'"):
        jax.grad(loss_through_mean)(x)

    def loss_through_y(x):
        y, _, _, _ = pallas_bn.fused_batch_norm(x, w, b, 1e-5, None)
        return y.sum()

    jax.grad(loss_through_y)(x)  # y-only gradient still works


def test_trainer_with_pallas_kernels_matches_xla_path():
    """The exact combination the TPU runs: DataParallel tracing the Pallas
    BN path (check_vma auto-disabled — interpret-mode kernel bodies mix
    unvarying scratch with varying blocks). Must compile, train, and match
    the XLA-fusion trainer step numerically."""
    import optax
    from flax import nnx

    from tpu_syncbn import models, nn, parallel
    from tpu_syncbn.ops import batch_norm as xops

    def build():
        m = nn.convert_sync_batchnorm(
            models.resnet18(num_classes=10, small_input=True,
                            rngs=nnx.Rngs(0))
        )

        def loss_fn(mo, batch):
            xs, ys = batch
            import optax as _o
            return _o.softmax_cross_entropy_with_integer_labels(
                mo(xs), ys
            ).mean()

        return parallel.DataParallel(m, optax.sgd(0.1), loss_fn, donate=False)

    rng = np.random.RandomState(0)
    batch = (
        jnp.asarray(rng.randn(16, 8, 8, 3).astype(np.float32)),
        jnp.asarray(rng.randint(0, 10, 16).astype(np.int32)),
    )

    with xops.pallas_mode("on"):
        dp_pallas = build()
        assert not dp_pallas._check_vma  # pallas ⇒ checker off
        out_p = dp_pallas.train_step(batch)
    # the XLA oracle is forced explicitly (ambient mode could be
    # pallas-active on a TPU host or under TPU_SYNCBN_PALLAS=on)
    with xops.pallas_mode("off"):
        dp_xla = build()
        assert dp_xla._check_vma
        out_x = dp_xla.train_step(batch)

    np.testing.assert_allclose(
        float(out_p.loss), float(out_x.loss), rtol=1e-5
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5
        ),
        dp_pallas.params, dp_xla.params,
    )


def test_group_scoped_model_keeps_vma_checker_under_pallas_mode():
    """Finer gating: with pallas mode ON but a group-scoped model (which
    the BN fast path rejects), only XLA traces — the VMA checker must
    stay enabled and the step must run."""
    import optax
    from flax import nnx

    from tpu_syncbn import models, nn, parallel
    from tpu_syncbn.ops import batch_norm as xops

    with xops.pallas_mode("on"):
        m = nn.convert_sync_batchnorm(
            models.resnet18(num_classes=10, small_input=True,
                            rngs=nnx.Rngs(0)),
            group_size=2,
        )

        def loss_fn(mo, batch):
            import optax as _o
            xs, ys = batch
            return _o.softmax_cross_entropy_with_integer_labels(
                mo(xs), ys
            ).mean()

        dp = parallel.DataParallel(m, optax.sgd(0.1), loss_fn, donate=False)
        # pallas can't trace for this model, so the checker stays on
        assert dp._check_vma
        rng = np.random.RandomState(0)
        batch = (
            jnp.asarray(rng.randn(16, 8, 8, 3).astype(np.float32)),
            jnp.asarray(rng.randint(0, 10, 16).astype(np.int32)),
        )
        out = dp.train_step(batch)
        assert np.isfinite(float(out.loss))


class TestVmemAwareBlock:
    """The first on-chip full-model run at a fixed block of 512 hit the
    TPU's 16 MiB scoped-VMEM ceiling in bn_backward_reduce at C=2048 f32
    (2 operands x 2 pipeline buffers x 512*2048*4 B = 16 MiB + scratch).
    _block_m must keep the fattest kernel's double-buffered working set
    under budget while preserving the sweep-chosen cap (256, per the
    2026-07-31 sweep whose record was removed in PR 21) wherever it
    fits."""

    def test_measured_oom_case_fires_clamp(self, monkeypatch):
        # the historical failure: cap 512, C=2048, f32 must CLAMP to 256
        # (not merely fit) — pinned with the cap forced to 512 so the
        # regression stays detectable whatever cap ships
        monkeypatch.setattr(pallas_bn, "_BLOCK_M", 512)
        assert pallas_bn._block_m(2048, 4) == 256

    def test_clamp_fires_at_shipping_cap(self):
        # at the shipping cap there must exist a real clamping C so the
        # halving path stays exercised: C=4096 f32 (4*256*4096*4 = 16
        # MiB > budget) -> 128
        cap = pallas_bn._BLOCK_M
        assert pallas_bn._block_m(4096, 4) < cap

    def test_sweep_winner_kept_where_it_fits(self):
        # narrow/medium channels run the full sweep-chosen cap
        cap = pallas_bn._BLOCK_M
        assert pallas_bn._block_m(64, 4) == cap
        assert pallas_bn._block_m(1024, 4) == cap
        assert pallas_bn._block_m(2048, 2) == cap  # bf16 halves the rows

    def test_budget_invariant(self):
        for c in (8, 64, 256, 512, 1024, 2048, 4096, 8192, 16384, 32768):
            for itemsize in (2, 4):
                m = pallas_bn._block_m(c, itemsize)
                assert m >= 32 // itemsize  # one sublane tile of rows
                assert 4 * m * c * itemsize <= pallas_bn._VMEM_BUDGET_BYTES

    def test_too_wide_for_one_tile_is_a_named_error(self):
        with pytest.raises(ValueError, match="scoped-VMEM budget"):
            pallas_bn._block_m(1 << 20, 4)

    def test_wide_channel_kernels_correct_at_clamped_block(self):
        """Functional check at a C wide enough to clamp the block below
        the shipping cap (f32 C=4096: 256 -> 128): sums and normalize
        must be exact across the clamp-induced block change, including
        non-multiple row counts."""
        c = 4096
        assert pallas_bn._block_m(c, 4) < pallas_bn._BLOCK_M
        x = jnp.asarray(
            np.random.RandomState(7).randn(300, c).astype(np.float32)
        )
        s, sq, n = pallas_bn.bn_stats(x)
        np.testing.assert_allclose(
            np.asarray(s), np.asarray(x).sum(0), rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(sq), (np.asarray(x) ** 2).sum(0), rtol=1e-3)
        assert float(n) == 300
        mean = s / n
        var = sq / n - mean**2
        y = pallas_bn.bn_normalize(x, mean, var, None, None, 1e-5)
        ref = (np.asarray(x) - np.asarray(mean)) / np.sqrt(
            np.asarray(var) + 1e-5)
        np.testing.assert_allclose(np.asarray(y), ref, atol=2e-4)
        sdy, sdyx = pallas_bn.bn_backward_reduce(
            x, x, mean, jax.lax.rsqrt(var + 1e-5))
        np.testing.assert_allclose(
            np.asarray(sdy), np.asarray(x).sum(0), rtol=1e-3, atol=1e-4)
