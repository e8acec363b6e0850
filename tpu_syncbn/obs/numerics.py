"""Numerics observability: cross-replica drift and compression-health
monitors computed INSIDE the compiled step, plumbed through the whole
operable stack (ISSUE 13).

The paper exists because per-replica BN statistics silently diverge from
the global batch statistics, and the compressed collectives (ISSUE 12,
EQuARX — arXiv:2506.17615) added a second invisible numerics hazard:
int8 clip saturation and error-feedback residual growth. Neither had a
metric, an alert, or incident evidence. This module closes that gap in
three layers:

**Device side** (inside the already-compiled step — the
``grad_monitors``/``state_health`` discipline, zero extra host syncs):

* a trace-time **collector** (:func:`collect` / :func:`record`) that the
  SyncBN moment reduction and the quantized collectives feed local
  health scalars into while the step traces — per-layer batch-moment
  skew vs the synced value (``collectives.reduce_moments``), int8
  per-chunk clip fraction and shared-range overflow headroom
  (``collectives._int8_qparams`` / the ``sumq`` sites). Producers are
  gated on :func:`active`, so a step built without monitors traces the
  exact same program as before;
* :func:`cross_replica_monitors` — ONE fused scalar ``psum`` that turns
  the per-replica local scalars into replicated monitor outputs: the
  replica mean of every scalar plus, for requested keys, the
  cross-replica relative dispersion (std/mean, from the Σx/Σx² halves
  of the same fused vector). One psum total is the machine-checked
  contract: the re-pinned golden program contracts prove the drift
  monitors add at most this one collective per compiled program.

**Host side** (:class:`NumericsPublisher`): monitors come back as async
device scalars riding ``StepOutput.monitors``. The publisher queues
them and flushes entries only once :meth:`jax.Array.is_ready` — so the
``numerics.*`` registry histograms fill at step cadence with **no
forced host→device sync** on the hot loop. Crossing a drift threshold
fires the ``numerics_drift`` flight-recorder trigger, dumping an
incident bundle whose step ring holds the monitors from *before* the
drift.

**Operable layer**: the registry histograms flow through
``WindowedAggregator`` rolling views like every other metric, so
:func:`numerics_rules` can pin SLO objectives on them
(``numerics.ef_residual_ratio p99 < 0.5``, clip-saturation budget);
``/statusz`` gains a numerics section. docs/OBSERVABILITY.md "Numerics &
drift" documents the monitor and metric tables.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Iterable, Mapping

import jax
import jax.numpy as jnp

from tpu_syncbn.obs import telemetry

#: Denominator guard for the relative-skew / dispersion ratios.
EPS = 1e-6

#: Monitor keys the publisher exports as ``numerics.<key>`` histograms
#: (docs/OBSERVABILITY.md "Numerics & drift" table). Everything else in
#: ``StepOutput.monitors`` (grad_norm, bn health, per-layer keys) stays
#: step-output-only, exactly as before.
PUBLISHED_MONITORS = frozenset({
    "bn_mean_skew", "bn_var_skew",
    "replica_grad_norm", "replica_grad_norm_disp",
    "d_replica_grad_norm", "d_replica_grad_norm_disp",
    "g_replica_grad_norm", "g_replica_grad_norm_disp",
    "clip_fraction", "overflow_headroom", "ef_residual_ratio",
})

#: A step whose ``clip_fraction`` exceeds this bumps the
#: ``numerics.clip_saturated`` counter — the "bad" side of the
#: clip-health availability objective (:func:`numerics_rules`): a chunk
#: with a quarter of its elements pinned at the int8 range edge is
#: saturating, not quantizing.
CLIP_SATURATED_FRAC = 0.25

#: Default drift thresholds the publisher fires the ``numerics_drift``
#: incident trigger on. Units are the monitors' own: BN skew is in
#: global-σ (a local batch mean 8σ from the synced mean is pathological
#: replica divergence, not noise), dispersions are relative std, and the
#: EF residual ratio is ‖residual‖/‖grad‖ (≥4 means compression error
#: dwarfs the signal it rides on). ``NumericsPublisher(thresholds={})``
#: disables triggering.
DEFAULT_DRIFT_THRESHOLDS: dict[str, float] = {
    "bn_mean_skew": 8.0,
    "bn_var_skew": 8.0,
    "replica_grad_norm_disp": 4.0,
    "d_replica_grad_norm_disp": 4.0,
    "g_replica_grad_norm_disp": 4.0,
    "ef_residual_ratio": 4.0,
}


# ---------------------------------------------------------------------------
# trace-time collector (device side)


class Collector:
    """Accumulates local health scalars recorded while a step traces.
    ``summary()`` folds repeated records of one key (one per BN layer,
    one per quantized dtype group) with ``max`` — drift anywhere is
    drift. A disabled collector records nothing and summarizes to ``{}``,
    so the traced program is unchanged."""

    __slots__ = ("enabled", "_records")

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._records: dict[str, list] = {}

    def record(self, key: str, value) -> None:
        self._records.setdefault(key, []).append(value)

    def summary(self) -> dict:
        out: dict = {}
        for key, values in self._records.items():
            acc = values[0]
            for v in values[1:]:
                acc = jnp.maximum(acc, v)
            out[key] = acc
        if "bn_mean_skew" in self._records:
            # how many synced-BN reductions fed the skew monitors: 0 in a
            # monitor dict means the bn_*_skew keys are absent, not vacuous
            out["bn_skew_layers"] = jnp.float32(
                len(self._records["bn_mean_skew"])
            )
        return out


# Collection is trace-time Python: the stack must be thread-local so two
# trainers tracing concurrently (tests, serve warmup next to a train
# loop) cannot cross-record into each other's step.
_tls = threading.local()


def _stack() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


class collect:
    """Context manager activating a :class:`Collector` for the traced
    region::

        with numerics.collect(enabled=bool(self.monitors)) as col:
            out = self.loss_fn(model, batch)
        monitors = col.summary()

    ``enabled=False`` yields an inert collector (producers see no active
    collector and trace nothing), keeping one code shape for both modes.
    Nestable; exception-safe."""

    __slots__ = ("_col",)

    def __init__(self, enabled: bool = True):
        self._col = Collector(enabled)

    def __enter__(self) -> Collector:
        if self._col.enabled:
            _stack().append(self._col)
        return self._col

    def __exit__(self, *exc) -> None:
        if self._col.enabled:
            stack = _stack()
            if stack and stack[-1] is self._col:
                stack.pop()


def active() -> bool:
    """Is a collector active on this thread? Producers gate their
    (traced) health arithmetic on this, so a step built without
    monitors traces the exact program it always did."""
    return bool(getattr(_tls, "stack", None))


def record(key: str, value) -> None:
    """Record one local health scalar into the innermost active
    collector (no-op without one)."""
    stack = getattr(_tls, "stack", None)
    if stack:
        stack[-1].record(key, value)


def record_bn_skew(local_sum, local_sumsq, local_count, mean, var) -> None:
    """Producer for ``collectives.reduce_moments``: this replica's batch
    moments vs the just-synced global ones, as max-over-channel relative
    deviations (mean skew in units of the global σ, var skew relative to
    the global var). Pure local arithmetic AFTER the existing stat psum
    — no collective; no-op without an active collector."""
    if not active():
        return
    from tpu_syncbn.parallel.collectives import moments_from_stats

    lmean, lvar = moments_from_stats(
        jnp.asarray(local_sum, jnp.float32),
        jnp.asarray(local_sumsq, jnp.float32),
        jnp.asarray(local_count, jnp.float32),
    )
    mean32 = jnp.asarray(mean, jnp.float32)
    var32 = jnp.asarray(var, jnp.float32)
    sigma = jnp.sqrt(jnp.maximum(var32, 0.0)) + EPS
    mean_skew = jnp.max(jnp.abs(lmean - mean32) / sigma)
    var_skew = jnp.max(jnp.abs(lvar - var32) / (var32 + EPS))
    record("bn_mean_skew", jax.lax.stop_gradient(mean_skew))
    record("bn_var_skew", jax.lax.stop_gradient(var_skew))


def merge_max(*summaries: Mapping) -> dict:
    """Union of monitor summaries with elementwise ``max`` on shared
    keys — how the GAN step folds its D- and G-substep collections."""
    out: dict = {}
    for summary in summaries:
        for key, value in summary.items():
            out[key] = value if key not in out \
                else jnp.maximum(out[key], value)
    return out


def grad_norm_scalar(grads) -> jax.Array:
    """Local (pre-reduction) gradient global L2 norm, f32 accumulation —
    the per-replica half of the grad-norm-dispersion monitor."""
    sq = jnp.zeros((), jnp.float32)
    for leaf in jax.tree_util.tree_leaves(grads):
        lf = jnp.asarray(leaf, jnp.float32)
        sq = sq + jnp.sum(lf * lf)
    return jnp.sqrt(sq)


def residual_ratio(residual, grad_norm: jax.Array) -> jax.Array:
    """‖EF residual‖ / (‖local grads‖ + eps): how much compression error
    is being re-sent relative to the signal. Rides the same fused psum
    as every other numerics scalar."""
    return grad_norm_scalar(residual) / (grad_norm + EPS)


def cross_replica_monitors(
    scalars: Mapping[str, jax.Array],
    axis_name: str,
    *,
    disp_keys: Iterable[str] = (),
    varying_cast: bool = True,
) -> dict:
    """Replicated monitor outputs from per-replica local scalars with
    ONE fused scalar ``psum`` — the whole wire cost of the numerics
    monitors (machine-checked by the re-pinned program contracts and
    tests/test_numerics.py's one-psum gate).

    Every key yields its replica mean under its own name; keys in
    ``disp_keys`` additionally yield ``<key>_disp`` — the cross-replica
    relative dispersion std/mean computed from the Σx and Σx² halves of
    the same fused vector (a ``pmax`` would be a second collective, so
    the max view is deliberately not offered). ``varying_cast`` mirrors
    the trainers' ``_check_vma`` flag: under the VMA checker the mixed
    varying/unvarying scalars must be cast before stacking."""
    if not scalars:
        return {}
    from tpu_syncbn.parallel import collectives
    from tpu_syncbn.parallel.collectives import pcast_varying

    world = collectives.axis_size(axis_name)
    keys = sorted(scalars)
    dkeys = [k for k in keys if k in set(disp_keys)]
    vals = {k: jnp.asarray(scalars[k], jnp.float32).reshape(())
            for k in keys}
    if varying_cast:
        vals = pcast_varying(vals, axis_name)
    fused = jnp.stack([vals[k] for k in keys]
                      + [vals[k] * vals[k] for k in dkeys])
    summed = collectives.psum(fused, axis_name)
    out: dict = {}
    for i, k in enumerate(keys):
        out[k] = summed[i] / world
    for j, k in enumerate(dkeys):
        mean = out[k]
        ex2 = summed[len(keys) + j] / world
        var = jnp.maximum(ex2 - mean * mean, 0.0)
        out[f"{k}_disp"] = jnp.sqrt(var) / (jnp.abs(mean) + EPS)
    return out


# ---------------------------------------------------------------------------
# host side: publisher + drift trigger


def _entry_ready(values: dict) -> bool:
    for v in values.values():
        is_ready = getattr(v, "is_ready", None)
        if callable(is_ready) and not is_ready():
            return False
    return True


class NumericsPublisher:
    """Publish the numerics monitors of each step into the telemetry
    registry — without forcing a host sync on the step loop.

    ``publish(step, monitors)`` queues the step's :data:`PUBLISHED_MONITORS`
    subset and drains queued entries whose device values have settled
    (``jax.Array.is_ready`` — the same non-blocking probe the flight
    recorder's dump path uses): by the time step ``N+k`` dispatches,
    step ``N``'s scalars are ready and land as ``numerics.<key>``
    histogram observations plus the ``numerics.samples`` /
    ``numerics.clip_saturated`` counters. ``flush()`` drains the
    remainder (blocking — end of run only).

    Each published value is checked against ``thresholds``
    (:data:`DEFAULT_DRIFT_THRESHOLDS`; pass ``{}`` to disable): a
    crossing — or a non-finite monitor, which is drift by definition —
    bumps ``numerics.drift_trips`` and fires the ``numerics_drift``
    flight-recorder trigger, whose bundle carries the pre-drift monitor
    ring. The recorder's cooldown absorbs a monitor that stays hot.

    ``ResilientLoop.run`` drives one of these next to
    ``flightrec.record_step``."""

    def __init__(
        self,
        *,
        thresholds: Mapping[str, float] | None = None,
        clip_saturated_frac: float = CLIP_SATURATED_FRAC,
        max_pending: int = 64,
    ):
        self.thresholds = (dict(DEFAULT_DRIFT_THRESHOLDS)
                           if thresholds is None else dict(thresholds))
        self.clip_saturated_frac = float(clip_saturated_frac)
        self._pending: deque = deque()
        self._max_pending = int(max_pending)
        #: newest published values, for tests/inspection
        self.last: dict[str, float] = {}
        self.published = 0

    def publish(self, step: int, monitors) -> int:
        """Queue one step's monitors; drain every queued entry whose
        values are ready. Returns the number of entries published this
        call. No-op (and no queue growth) while telemetry is disabled
        or the monitors carry no numerics keys."""
        if not telemetry.enabled():
            return 0
        if isinstance(monitors, dict):
            vals = {k: v for k, v in monitors.items()
                    if k in PUBLISHED_MONITORS}
            if vals:
                self._pending.append((int(step), vals))
                while len(self._pending) > self._max_pending:
                    # a wedged device must bound the queue, not grow it:
                    # drop oldest, visibly
                    self._pending.popleft()
                    telemetry.count("numerics.dropped")
        return self._drain(block=False)

    def flush(self) -> int:
        """Blocking drain of everything still queued (forces the host
        sync ``publish`` avoids — end-of-run only)."""
        return self._drain(block=True)

    def _drain(self, *, block: bool) -> int:
        published = 0
        while self._pending:
            step, vals = self._pending[0]
            if not block and not _entry_ready(vals):
                break
            self._pending.popleft()
            self._emit(step, vals)
            published += 1
        self.published += published
        return published

    def _emit(self, step: int, vals: dict) -> None:
        from tpu_syncbn.obs import flightrec

        telemetry.count("numerics.samples")
        for key, raw in vals.items():
            try:
                value = float(raw)
            except (TypeError, ValueError):
                continue
            finite = value == value and abs(value) != float("inf")
            if finite:
                telemetry.observe(f"numerics.{key}", value)
                self.last[key] = value
            if key == "clip_fraction" and finite \
                    and value > self.clip_saturated_frac:
                telemetry.count("numerics.clip_saturated")
            threshold = self.thresholds.get(key)
            if (threshold is not None and finite and value > threshold) \
                    or not finite:
                telemetry.count("numerics.drift_trips")
                flightrec.trigger("numerics_drift", {
                    "monitor": key,
                    "value": value if finite else str(value),
                    "threshold": threshold,
                    "step": step,
                })


# ---------------------------------------------------------------------------
# SLO rules


def numerics_rules(
    *,
    residual_slo: str = "numerics.ef_residual_ratio p99 < 0.5",
    skew_slo: str = "numerics.bn_mean_skew p99 < 4.0",
    clip_target: float = 0.99,
    windows_s=(60.0, 300.0),
    burn_threshold: float = 2.0,
) -> list:
    """The numerics-health rule set (docs/OBSERVABILITY.md "Numerics &
    drift"), ready for ``SLOTracker(agg, numerics_rules()).attach()``:

    * ``numerics_residual`` — the EF residual ratio quantile objective
      (error feedback re-sending more than half the gradient norm at
      p99 means quantization is drowning the signal);
    * ``numerics_skew`` — the BN batch-mean skew quantile objective
      (sustained multi-σ local-vs-synced deviation is replica drift,
      the exact failure SyncBN exists to prevent);
    * ``numerics_clip`` — clip-saturation budget: at most
      ``1 - clip_target`` of published steps may be clip-saturated
      (``SubsetRate`` — saturated steps are a subset of samples)."""
    from tpu_syncbn.obs import slo

    return [
        slo.AlertRule("numerics_residual", residual_slo,
                      windows_s=windows_s, burn_threshold=burn_threshold),
        slo.AlertRule("numerics_skew", skew_slo,
                      windows_s=windows_s, burn_threshold=burn_threshold),
        slo.AlertRule("numerics_clip",
                      slo.SubsetRate(total="numerics.samples",
                                     bad="numerics.clip_saturated",
                                     target=clip_target),
                      windows_s=windows_s, burn_threshold=burn_threshold),
    ]
