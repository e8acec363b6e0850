"""Per-step breakdown: host-side timing seams + on-device step monitors.

Two halves, one goal — see where step time goes (DS-Sync, arxiv
2007.03298: sync/collective cost dominates data-parallel training at
scale and must be measured per step before it can be optimized):

**Host side** (:func:`timed_span`, :func:`instrumented_batches`): the
three seams of a training loop — data-wait (blocking on the input
iterator), host→device transfer dispatch, and the step call itself —
each recorded as a trace span (``obs.tracing``) AND a telemetry
histogram (``obs.telemetry``) in one shot.
``runtime.resilience.ResilientLoop`` drives its loop through these, so
a Perfetto timeline of its run shows ``data_wait`` / ``step`` /
``checkpoint_*`` spans without code changes. Estimated collective
traffic comes from ``parallel.collectives``' trace-time tallies
(``collectives.<op>.calls`` / ``.bytes`` counters — per *compiled
program*, multiplied by step count in the mind of the reader, since the
compiled step replays the same collectives each execution).

**Device side** (:func:`grad_monitors`, :func:`state_health`): scalar
health monitors computed *inside* the already-compiled step and returned
through ``StepOutput.monitors`` — grad global-norm, non-finite counts,
and BN running-stat health. They are ordinary step outputs: jax's async
dispatch means reading them costs nothing until the host actually
fetches a value, so **no extra per-step host→device syncs are
introduced** (the acceptance contract of the obs subsystem). Under
``DataParallel(zero=True)`` the gradient monitors need one scalar psum
(device↔device over ICI, not a host sync) because each device only holds
a gradient shard.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterable, Iterator

import jax
import jax.numpy as jnp
from jax import lax

from tpu_syncbn.obs import telemetry, tracing


# ---------------------------------------------------------------------------
# host side


@contextlib.contextmanager
def timed_span(span_name: str, hist_name: str | None = None, **args):
    """One context manager for the span + histogram pair: a tracing span
    named ``span_name`` (when tracing is on: a tracer installed, or a
    profiler capture running — ``tracing.get``) and a telemetry
    histogram observation into ``hist_name`` seconds (when telemetry is
    enabled). With both off this is a bare yield — hot-loop safe."""
    tracer = tracing.get()
    record = telemetry.enabled() and hist_name is not None
    if tracer is None and not record:
        yield
        return
    t0 = time.perf_counter()
    try:
        if tracer is not None:
            with tracer.span(span_name, **args):
                yield
        else:
            yield
    finally:
        if record:
            telemetry.observe(hist_name, time.perf_counter() - t0)


def instrumented_batches(
    iterator: Iterable,
    *,
    span_name: str = "data_wait",
    hist_name: str = "step.data_wait_s",
) -> Iterator:
    """Yield from ``iterator``, recording the time the consumer spent
    blocked waiting for each batch (span + histogram). Wrap the batch
    source of any step loop::

        for batch in stepstats.instrumented_batches(loader):
            with stepstats.timed_span("step", "step.time_s"):
                out = dp.train_step(batch)
    """
    it = iter(iterator)
    while True:
        try:
            batch = timed_fetch(it, span_name, hist_name)
        except StopIteration:
            return
        yield batch


def timed_fetch(it: Iterator, span_name: str = "data_wait",
                hist_name: str | None = "step.data_wait_s"):
    """``next(it)`` under a ``span_name`` span, observing the blocking
    wait into ``hist_name``. The terminal fetch (StopIteration) closes
    its span but is NOT a histogram sample — it would skew the wait
    distribution by one end-of-epoch entry per epoch. Shared by
    :func:`instrumented_batches` and ``data.device_prefetch``."""
    tracer = tracing.get()
    record = telemetry.enabled() and hist_name is not None
    if tracer is None and not record:
        return next(it)
    t0 = time.perf_counter()
    ctx = (tracer.span(span_name) if tracer is not None
           else contextlib.nullcontext())
    with ctx:
        batch = next(it)  # StopIteration propagates, unrecorded below
    if record:
        telemetry.observe(hist_name, time.perf_counter() - t0)
    return batch


# ---------------------------------------------------------------------------
# device side (call from INSIDE the compiled step)


def grad_monitors(
    grads, axis_name: str | None = None, *, sharded: bool = False
) -> dict:
    """Scalar gradient monitors from a gradient pytree, traced into the
    step: ``grad_norm`` (global L2, f32 accumulation) and
    ``grad_nonfinite`` (count of non-finite entries).

    ``sharded=True`` (ZeRO: each device holds 1/world of the flat grads)
    adds one scalar ``psum`` over ``axis_name`` so the norm is the global
    one — a device-side collective, not a host sync. With replicated
    (already all-reduced) grads leave it False: the local values ARE the
    global values."""
    sq = jnp.zeros((), jnp.float32)
    nonfinite = jnp.zeros((), jnp.float32)
    for leaf in jax.tree_util.tree_leaves(grads):
        lf = leaf.astype(jnp.float32)
        sq = sq + jnp.sum(lf * lf)
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact):
            nonfinite = nonfinite + jnp.sum(
                (~jnp.isfinite(leaf)).astype(jnp.float32)
            )
    if sharded and axis_name is not None:
        sq, nonfinite = lax.psum((sq, nonfinite), axis_name)
    return {"grad_norm": jnp.sqrt(sq), "grad_nonfinite": nonfinite}


def state_health(
    state,
    axis_name: str | None = None,
    *,
    reduce: bool = False,
    per_layer: bool = False,
) -> dict:
    """BN running-stat health monitors from a non-Param state pytree
    (the trainer's ``rest``), traced into the step:

    * ``bn_mean_max_abs`` — max ``|running_mean|`` over every BN layer
      (drift detector);
    * ``bn_var_max`` / ``bn_var_min`` — extremes of ``running_var``
      (a var collapsing to 0 or exploding flags a dying/diverging
      normalizer);
    * ``bn_layers`` — how many running-var buffers were found (0 means
      the other bn_* monitors are vacuous defaults);
    * ``state_nonfinite`` — count of non-finite entries across ALL
      inexact state leaves.

    ``per_layer=True`` additionally emits ``bn_var_min<path>`` /
    ``bn_mean_max_abs<path>`` per BN buffer (the trainer's
    ``monitors="full"``). Leaves are classified by their tree path
    containing ``running_mean`` / ``running_var`` — the nn layer's
    buffer names.

    ``reduce=True`` (per-replica buffer storage,
    ``broadcast_buffers=False``) reduces across ``axis_name`` to the
    worst replica: ``pmax`` for maxima and non-finite counts, ``pmin``
    for ``bn_var_min`` — so the monitors stay replicated step outputs."""
    zero = jnp.zeros((), jnp.float32)
    means: list = []
    variances: list = []
    per: dict = {}
    nonfinite = zero
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        if not hasattr(leaf, "dtype"):
            continue
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact):
            nonfinite = nonfinite + jnp.sum(
                (~jnp.isfinite(leaf)).astype(jnp.float32)
            )
        key = jax.tree_util.keystr(path)
        if "running_mean" in key:
            m = jnp.max(jnp.abs(leaf.astype(jnp.float32)))
            means.append(m)
            if per_layer:
                per[f"bn_mean_max_abs{_layer_key(key, 'running_mean')}"] = m
        elif "running_var" in key:
            v32 = leaf.astype(jnp.float32)
            variances.append((jnp.max(v32), jnp.min(v32)))
            if per_layer:
                per[f"bn_var_min{_layer_key(key, 'running_var')}"] = jnp.min(v32)
    out = {
        "state_nonfinite": nonfinite,
        "bn_layers": jnp.asarray(float(len(variances)), jnp.float32),
        "bn_mean_max_abs": jnp.max(jnp.stack(means)) if means else zero,
        "bn_var_max": (jnp.max(jnp.stack([v for v, _ in variances]))
                       if variances else zero),
        "bn_var_min": (jnp.min(jnp.stack([v for _, v in variances]))
                       if variances else zero),
        **per,
    }
    if reduce and axis_name is not None:
        from tpu_syncbn.parallel.collectives import pcast_varying

        out = pcast_varying(out, axis_name)
        reduced = {}
        for name, value in out.items():
            op = lax.pmin if name.startswith("bn_var_min") else lax.pmax
            reduced[name] = op(value, axis_name)
        out = reduced
    return out


def _layer_key(keystr_path: str, buffer_name: str) -> str:
    """Trim the buffer leaf name off a keystr path and normalize it into
    a compact monitor-key suffix: ``['layers'][0].bn.running_var`` →
    ``.layers.0.bn``."""
    trimmed = keystr_path.split(buffer_name)[0]
    out = []
    token = ""
    for ch in trimmed:
        if ch in "[]'\".":
            if token:
                out.append(token)
                token = ""
        else:
            token += ch
    if token:
        out.append(token)
    return ("." + ".".join(out)) if out else ""


def collective_tallies() -> dict:
    """Host-side convenience: the ``collectives.*`` call/byte counters
    currently in the process registry (trace-time estimates of per-step
    collective traffic — see ``parallel.collectives``)."""
    snap = telemetry.REGISTRY.snapshot()
    return {k: v for k, v in snap["counters"].items()
            if k.startswith("collectives.")}
