"""Numerics observability (ISSUE 13): cross-replica drift and
compression-health monitors inside the compiled step — monitor presence
and meaning, the one-extra-psum wire contract, monitor parity across
wire modes, the analytic EF residual-ratio reference, the publisher →
registry → numerics_drift incident plumbing, and the numerics SLO
rules."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import nnx

from tpu_syncbn import nn as tnn, parallel
from tpu_syncbn.audit.contracts import summarize_jaxpr
from tpu_syncbn.obs import (
    flightrec,
    incident as incident_mod,
    numerics,
    slo as obs_slo,
    telemetry,
    timeseries,
)

FEATURES, CLASSES, GLOBAL_BATCH = 8, 4, 16


class Net(nnx.Module):
    def __init__(self, rngs: nnx.Rngs):
        self.fc1 = nnx.Linear(FEATURES, 16, rngs=rngs)
        self.bn = tnn.BatchNorm1d(16)
        self.fc2 = nnx.Linear(16, CLASSES, rngs=rngs)

    def __call__(self, x):
        return self.fc2(nnx.relu(self.bn(self.fc1(x))))


def ce_loss(model, batch):
    x, y = batch
    logits = model(x)
    return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()


def make_dp(seed=0, **kw):
    model = tnn.convert_sync_batchnorm(Net(nnx.Rngs(seed)))
    return parallel.DataParallel(model, optax.sgd(0.05), ce_loss, **kw)


def make_batch(dp, seed=0, *, offset_first_shard=0.0):
    rng = np.random.RandomState(seed)
    x = rng.randn(GLOBAL_BATCH, FEATURES).astype(np.float32)
    if offset_first_shard:
        # replica 0's shard (the first GLOBAL_BATCH/world rows) drawn
        # from a shifted distribution: planted cross-replica drift
        x[: GLOBAL_BATCH // dp.world] += offset_first_shard
    y = rng.randint(0, CLASSES, GLOBAL_BATCH).astype(np.int32)
    return jax.device_put((jnp.asarray(x), jnp.asarray(y)),
                          dp.batch_sharding)


NUMERICS_BASE = {"bn_mean_skew", "bn_var_skew", "bn_skew_layers",
                 "replica_grad_norm", "replica_grad_norm_disp"}


# ---------------------------------------------------------------------------
# monitor presence + meaning


def test_monitor_keys_by_mode():
    dp = make_dp()
    out = dp.train_step(make_batch(dp))
    assert NUMERICS_BASE <= set(out.monitors)
    assert "clip_fraction" not in out.monitors  # fp32 wire: no quantizer
    assert "ef_residual_ratio" not in out.monitors
    assert float(out.monitors["bn_skew_layers"]) == 1.0  # one SyncBN
    for k in NUMERICS_BASE:
        assert np.isfinite(float(out.monitors[k])), k

    dp8 = make_dp(compress="int8")
    out8 = dp8.train_step(make_batch(dp8))
    assert {"clip_fraction", "overflow_headroom",
            "ef_residual_ratio"} <= set(out8.monitors)
    assert 0.0 <= float(out8.monitors["clip_fraction"]) <= 1.0
    assert 0.0 <= float(out8.monitors["overflow_headroom"]) <= 1.0
    assert float(out8.monitors["ef_residual_ratio"]) >= 0.0


def test_monitors_off_removes_numerics():
    dp = make_dp(monitors=False, compress="int8")
    out = dp.train_step(make_batch(dp))
    assert out.monitors == {}


def test_bn_skew_detects_planted_replica_drift():
    """The monitor's meaning: identical per-replica shards read as zero
    skew; a replica fed from a shifted distribution reads as skew."""

    def tiled_batch(dp, offset_first_shard=0.0):
        rng = np.random.RandomState(0)
        per = GLOBAL_BATCH // dp.world
        shard = rng.randn(per, FEATURES).astype(np.float32)
        x = np.tile(shard, (dp.world, 1))
        if offset_first_shard:
            x[:per] += offset_first_shard
        y = np.tile(rng.randint(0, CLASSES, per).astype(np.int32),
                    dp.world)
        return jax.device_put((jnp.asarray(x), jnp.asarray(y)),
                              dp.batch_sharding)

    dp = make_dp()
    base = float(dp.train_step(tiled_batch(dp)).monitors["bn_mean_skew"])
    dp2 = make_dp()
    skewed = float(
        dp2.train_step(
            tiled_batch(dp2, offset_first_shard=10.0)
        ).monitors["bn_mean_skew"]
    )
    assert base < 1e-3, base          # homogeneous replicas: no skew
    assert skewed > 0.3, skewed       # planted drift: read as skew


def test_grad_norm_dispersion_zero_on_identical_replicas():
    """Identical per-replica data ⇒ identical local grads ⇒ zero
    cross-replica dispersion (and a nonzero replica mean)."""
    dp = make_dp()
    rng = np.random.RandomState(0)
    shard = rng.randn(GLOBAL_BATCH // dp.world, FEATURES).astype(np.float32)
    x = np.tile(shard, (dp.world, 1))
    y = np.tile(
        rng.randint(0, CLASSES, GLOBAL_BATCH // dp.world).astype(np.int32),
        dp.world,
    )
    batch = jax.device_put((jnp.asarray(x), jnp.asarray(y)),
                           dp.batch_sharding)
    out = dp.train_step(batch)
    assert float(out.monitors["replica_grad_norm"]) > 0
    # std/mean from one fused psum of (Σx, Σx²) in f32: on identical
    # replicas E[x²] − E[x]² cancels to rounding, and its square root
    # sits near sqrt(eps_f32) ≈ 3e-4 whenever the all-reduce does not
    # happen to sum 8 equal values exactly
    assert float(out.monitors["replica_grad_norm_disp"]) < 1e-3


# ---------------------------------------------------------------------------
# the one-extra-psum wire contract


def _collectives_of(dp, batch):
    closed = jax.make_jaxpr(dp._train_step)(
        dp._param_store, dp.rest, dp.opt_state, batch
    )
    return summarize_jaxpr(closed)


@pytest.mark.audit
def test_monitors_add_exactly_one_psum():
    """The acceptance rail: the whole numerics monitor family costs ONE
    extra scalar psum per compiled program — no other collective kind,
    no host callbacks (the golden contracts pin the absolute counts;
    this pins the *delta*)."""
    dp_on, dp_off = make_dp(), make_dp(monitors=False)
    batch = make_batch(dp_on)
    on = _collectives_of(dp_on, batch)
    off = _collectives_of(dp_off, make_batch(dp_off))
    assert on["collectives"].get("psum", 0) \
        == off["collectives"].get("psum", 0) + 1
    for kind in set(on["collectives"]) | set(off["collectives"]):
        if kind != "psum":
            assert on["collectives"].get(kind, 0) \
                == off["collectives"].get(kind, 0), kind
    assert not on["host_callbacks"]


@pytest.mark.audit
def test_gan_monitors_add_exactly_one_psum():
    def build(monitors):
        class G(nnx.Module):
            def __init__(self, rngs):
                self.fc = nnx.Linear(4, FEATURES, rngs=rngs)
                self.bn = tnn.BatchNorm1d(FEATURES)

            def __call__(self, z):
                return self.bn(self.fc(z))

        class D(nnx.Module):
            def __init__(self, rngs):
                self.fc = nnx.Linear(FEATURES, 1, rngs=rngs)
                self.bn = tnn.BatchNorm1d(1)

            def __call__(self, x):
                return self.bn(self.fc(x))

        return parallel.GANTrainer(
            tnn.convert_sync_batchnorm(G(nnx.Rngs(0))),
            tnn.convert_sync_batchnorm(D(nnx.Rngs(1))),
            optax.adam(1e-4), optax.adam(1e-4), monitors=monitors,
        )

    def summarize(gan):
        real = jax.ShapeDtypeStruct((GLOBAL_BATCH, FEATURES), jnp.float32)
        z = jax.ShapeDtypeStruct((GLOBAL_BATCH, 4), jnp.float32)
        closed = jax.make_jaxpr(gan._step)(
            gan.g_params, gan.g_rest, gan.d_params, gan.d_rest,
            gan.g_opt_state, gan.d_opt_state, real, z, z,
        )
        return summarize_jaxpr(closed)

    on, off = summarize(build(True)), summarize(build(False))
    assert on["collectives"].get("psum", 0) \
        == off["collectives"].get("psum", 0) + 1
    assert not on["host_callbacks"]


# ---------------------------------------------------------------------------
# monitor parity across wire modes (ISSUE 13 satellite)


@pytest.mark.parametrize("kw", [
    {"compress": "bf16"},
    {"compress": "int8"},
    {"compress": "int8", "error_feedback": False},
])
def test_monitor_parity_under_compression(kw):
    """monitors=True values on the lossy wire paths match the fp32
    path within pinned tolerance: compression perturbs the gradients,
    not the monitor definitions."""
    ref = make_dp()
    dp = make_dp(**kw)
    batch = make_batch(ref)
    m_ref = ref.train_step(batch).monitors
    m = dp.train_step(make_batch(dp)).monitors
    for key in ("bn_mean_skew", "bn_var_skew", "bn_skew_layers"):
        # the forward (and hence the BN moments) is identical pre-update
        np.testing.assert_allclose(
            float(m[key]), float(m_ref[key]), rtol=1e-4, atol=1e-5,
        )
    # grad-norm family: compression is a small perturbation (pinned)
    assert abs(float(m["replica_grad_norm"])
               - float(m_ref["replica_grad_norm"])) \
        <= 0.05 * max(1e-6, float(m_ref["replica_grad_norm"]))
    assert abs(float(m["replica_grad_norm_disp"])
               - float(m_ref["replica_grad_norm_disp"])) <= 0.05
    assert abs(float(m["grad_norm"]) - float(m_ref["grad_norm"])) \
        <= 0.05 * max(1e-6, float(m_ref["grad_norm"]))


def test_zero_mode_monitor_parity_int8():
    ref = make_dp()
    dp = make_dp(compress="int8", zero=True)
    m_ref = ref.train_step(make_batch(ref)).monitors
    m = dp.train_step(make_batch(dp)).monitors
    assert {"clip_fraction", "overflow_headroom",
            "ef_residual_ratio"} <= set(m)
    assert abs(float(m["replica_grad_norm"])
               - float(m_ref["replica_grad_norm"])) \
        <= 0.05 * max(1e-6, float(m_ref["replica_grad_norm"]))


# ---------------------------------------------------------------------------
# EF residual ratio vs the analytic toy-quadratic reference


class _Quad(nnx.Module):
    """w only; loss ½‖w − t‖² ⇒ grad = w − t exactly, identical on
    every replica — the EF recursion is then a closed-form numpy
    simulation."""

    def __init__(self, rngs: nnx.Rngs):
        self.w = nnx.Param(jnp.linspace(0.5, 4.0, FEATURES))

    def __call__(self, x):
        return self.w[...]


def test_ef_residual_ratio_matches_toy_quadratic():
    target = np.linspace(-1.0, 1.0, FEATURES).astype(np.float32)
    lr = 0.25

    def loss_fn(m, batch):
        return 0.5 * jnp.sum((m(batch) - jnp.asarray(target)) ** 2)

    model = _Quad(nnx.Rngs(0))
    dp = parallel.DataParallel(
        model, optax.sgd(lr), loss_fn,
        compress="bf16", error_feedback=True,
    )
    x = jax.device_put(
        jnp.zeros((GLOBAL_BATCH, 1), jnp.float32), dp.batch_sharding
    )

    # numpy reference of the bf16 EF recursion (all replicas identical,
    # so the compressed mean equals one replica's C(p)):
    #   p = g + res;  C(p) = bf16(p);  res' = p − C(p)
    #   ratio = ‖res'‖ / (‖g‖ + eps);  w' = w − lr·C(p)
    w = np.linspace(0.5, 4.0, FEATURES).astype(np.float32)
    res = np.zeros_like(w)
    for _ in range(5):
        g = w - target
        p = g + res
        cast = np.asarray(jnp.asarray(p).astype(jnp.bfloat16)
                          ).astype(np.float32)
        res_new = p - cast
        want = np.linalg.norm(res_new) / (np.linalg.norm(g) + numerics.EPS)
        out = dp.train_step(x)
        got = float(out.monitors["ef_residual_ratio"])
        # rtol 2e-3: the device recursion runs f32, the reference f64
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=1e-7)
        res = res_new
        w = w - lr * cast
    (w_leaf,) = jax.tree_util.tree_leaves(dp.params)
    np.testing.assert_allclose(np.asarray(w_leaf), w, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# publisher → registry → drift trigger


@pytest.fixture
def clean_telemetry():
    telemetry.set_enabled(True)
    telemetry.REGISTRY.reset()
    yield
    telemetry.set_enabled(None)
    telemetry.REGISTRY.reset()


def test_publisher_fills_registry_and_counts(clean_telemetry):
    pub = numerics.NumericsPublisher(thresholds={})
    n = pub.publish(1, {"bn_mean_skew": 0.25, "clip_fraction": 0.5,
                        "grad_norm": 9.9})  # grad_norm: not published
    assert n == 1
    snap = telemetry.snapshot()
    assert snap["histograms"]["numerics.bn_mean_skew"]["count"] == 1
    assert snap["histograms"]["numerics.clip_fraction"]["count"] == 1
    assert "numerics.grad_norm" not in snap["histograms"]
    assert snap["counters"]["numerics.samples"] == 1
    # clip 0.5 > CLIP_SATURATED_FRAC: the saturation counter bumped
    assert snap["counters"]["numerics.clip_saturated"] == 1
    assert pub.last["bn_mean_skew"] == 0.25


def test_publisher_waits_for_device_values(clean_telemetry):
    """The zero-host-sync discipline: a queued entry publishes only
    once its device values report ready."""

    class Fake:
        def __init__(self):
            self.ready = False

        def is_ready(self):
            return self.ready

        def __float__(self):
            return 0.125

    v = Fake()
    pub = numerics.NumericsPublisher(thresholds={})
    assert pub.publish(1, {"bn_mean_skew": v}) == 0  # queued, not forced
    assert "numerics.bn_mean_skew" not in telemetry.snapshot()["histograms"]
    v.ready = True
    assert pub.publish(2, None) == 1  # drains once ready
    assert telemetry.snapshot()["histograms"][
        "numerics.bn_mean_skew"]["count"] == 1


def test_drift_trigger_dumps_exactly_one_valid_bundle(
    clean_telemetry, tmp_path
):
    rec = flightrec.install(flightrec.FlightRecorder(
        incident_dir=str(tmp_path), cooldown_s=30.0,
    ))
    try:
        # pre-trigger evidence: monitors in the step ring
        for step in range(1, 4):
            flightrec.record_step(step, metrics={"loss": 1.0},
                                  monitors={"bn_mean_skew": 0.01})
        pub = numerics.NumericsPublisher(thresholds={"bn_mean_skew": 0.1})
        pub.publish(4, {"bn_mean_skew": 0.5})
        pub.publish(5, {"bn_mean_skew": 0.6})  # cooldown: no second dump
        names = [n for n in os.listdir(tmp_path) if n.endswith(".json")]
        assert len(names) == 1
        bundle = incident_mod.load_bundle(str(tmp_path / names[0]))
        assert bundle["trigger"]["kind"] == "numerics_drift"
        assert bundle["trigger"]["detail"]["monitor"] == "bn_mean_skew"
        assert bundle["trigger"]["detail"]["value"] == 0.5
        # the pre-trigger monitor ring rode along
        steps = bundle["rings"]["steps"]
        assert [e["step"] for e in steps] == [1, 2, 3]
        assert steps[0]["monitors"]["bn_mean_skew"] == 0.01
        assert "numerics_drift" in incident_mod.TRIGGER_KINDS
        assert telemetry.snapshot()["counters"][
            "numerics.drift_trips"] == 2
    finally:
        rec2 = flightrec.uninstall()
        if rec2 is not None:
            rec2.close()


def test_nonfinite_monitor_is_drift(clean_telemetry):
    pub = numerics.NumericsPublisher(thresholds={})
    pub.publish(1, {"ef_residual_ratio": float("nan")})
    snap = telemetry.snapshot()
    assert snap["counters"]["numerics.drift_trips"] == 1
    # NaN never lands in the histogram
    assert "numerics.ef_residual_ratio" not in snap["histograms"]


def test_publisher_bounds_queue(clean_telemetry):
    class Never:
        def is_ready(self):
            return False

        def __float__(self):
            return 0.0

    pub = numerics.NumericsPublisher(thresholds={}, max_pending=4)
    for step in range(10):
        pub.publish(step, {"bn_mean_skew": Never()})
    assert len(pub._pending) == 4
    assert telemetry.snapshot()["counters"]["numerics.dropped"] == 6


def test_publisher_noop_when_telemetry_disabled():
    telemetry.set_enabled(False)
    try:
        pub = numerics.NumericsPublisher()
        assert pub.publish(1, {"bn_mean_skew": 99.0}) == 0
        assert not pub._pending
    finally:
        telemetry.set_enabled(None)


# ---------------------------------------------------------------------------
# SLO rules


def test_numerics_rules_shape_and_fire(clean_telemetry):
    rules = numerics.numerics_rules(windows_s=(10.0,))
    assert [r.name for r in rules] == [
        "numerics_residual", "numerics_skew", "numerics_clip",
    ]
    agg = timeseries.WindowedAggregator()
    agg.tick(now=0.0)
    for _ in range(20):
        telemetry.observe("numerics.ef_residual_ratio", 0.9)  # > 0.5 SLO
        telemetry.observe("numerics.bn_mean_skew", 0.1)       # healthy
        telemetry.count("numerics.samples")
    agg.tick(now=5.0)
    tracker = obs_slo.SLOTracker(agg, rules)
    state = tracker.evaluate(now=5.0)
    assert state["numerics_residual"]["firing"] is True
    assert state["numerics_skew"]["firing"] is False
    assert state["numerics_clip"]["firing"] is False


# ---------------------------------------------------------------------------
# GAN flight-ring satellite + fused-scan composition


def _tiny_gan(**kw):
    class G(nnx.Module):
        def __init__(self, rngs):
            self.fc = nnx.Linear(4, FEATURES, rngs=rngs)
            self.bn = tnn.BatchNorm1d(FEATURES)

        def __call__(self, z):
            return self.bn(self.fc(z))

    class D(nnx.Module):
        def __init__(self, rngs):
            self.fc = nnx.Linear(FEATURES, 1, rngs=rngs)
            self.bn = tnn.BatchNorm1d(1)

        def __call__(self, x):
            return self.bn(self.fc(x))

    return parallel.GANTrainer(
        tnn.convert_sync_batchnorm(G(nnx.Rngs(0))),
        tnn.convert_sync_batchnorm(D(nnx.Rngs(1))),
        optax.adam(1e-4), optax.adam(1e-4), **kw,
    )


def test_gan_steps_reach_flight_ring(tmp_path):
    """ISSUE 13 satellite: GAN incidents used to dump an empty step
    history — train_step must feed the recorder's step ring."""
    gan = _tiny_gan()
    rng = np.random.RandomState(0)
    real = jax.device_put(
        jnp.asarray(rng.randn(GLOBAL_BATCH, FEATURES).astype(np.float32)),
        gan.batch_sharding,
    )
    z = jax.device_put(
        jnp.asarray(rng.randn(GLOBAL_BATCH, 4).astype(np.float32)),
        gan.batch_sharding,
    )
    rec = flightrec.install(flightrec.FlightRecorder(
        incident_dir=str(tmp_path)
    ))
    try:
        gan.train_step(real, z, z)
        gan.train_step(real, z, z)
        snap = rec.rings_snapshot()
        assert [e["step"] for e in snap["steps"]] == [1, 2]
        entry = snap["steps"][-1]
        assert {"d_loss", "g_loss", "d_real", "d_fake"} <= set(
            entry["metrics"]
        )
        assert "bn_mean_skew" in entry["monitors"]
        assert "d_replica_grad_norm_disp" in entry["monitors"]
        # a GAN incident bundle now carries the step history
        path = rec.trigger("manual", force=True)
        bundle = incident_mod.load_bundle(path)
        assert len(bundle["rings"]["steps"]) == 2
    finally:
        rec2 = flightrec.uninstall()
        if rec2 is not None:
            rec2.close()
    # no recorder installed: the counter still advances, nothing crashes
    gan.train_step(real, z, z)
    assert gan.step_count == 3


def test_train_steps_batches_monitor_parity():
    """Numerics monitors are legal scan outputs: the fused K-step path
    reproduces the per-step monitors exactly."""
    from tpu_syncbn.parallel import scan_driver

    dp_seq = make_dp(compress="int8")
    dp_fused = make_dp(compress="int8")
    batches = [make_batch(dp_seq, seed=s) for s in range(3)]
    seq = [dp_seq.train_step(b).monitors for b in batches]
    stacked = jax.device_put(
        scan_driver.stack_batches([jax.device_get(b) for b in batches]),
        dp_fused.scan_batch_sharding,
    )
    fused = dp_fused.train_steps_batches(stacked).monitors
    for key in ("bn_mean_skew", "replica_grad_norm",
                "replica_grad_norm_disp", "clip_fraction",
                "ef_residual_ratio"):
        np.testing.assert_allclose(
            np.asarray(fused[key]),
            [float(m[key]) for m in seq],
            rtol=1e-4, atol=1e-6, err_msg=key,
        )


def test_accum_steps_compose_with_numerics():
    dp = make_dp(accum_steps=2, compress="int8")
    out = dp.train_step(make_batch(dp))
    assert {"bn_mean_skew", "clip_fraction",
            "replica_grad_norm_disp"} <= set(out.monitors)
    assert np.isfinite(float(out.monitors["bn_mean_skew"]))


# ---------------------------------------------------------------------------
# ResilientLoop plumbing


def test_resilient_loop_publishes_numerics(clean_telemetry, tmp_path):
    from tpu_syncbn.runtime.resilience import ResilientLoop

    dp = make_dp()
    batch = make_batch(dp)
    loop = ResilientLoop(dp, str(tmp_path), ckpt_every=100)
    loop.run(iter([batch] * 4), max_steps=4)
    snap = telemetry.snapshot()
    assert snap["histograms"]["numerics.bn_mean_skew"]["count"] == 4
    assert snap["counters"]["numerics.samples"] == 4
