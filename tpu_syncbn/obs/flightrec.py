"""Process-wide flight recorder: bounded, always-on rings of recent
activity, dumped as an incident bundle when something goes wrong.

The monitoring layer (ISSUE 8) made the stack live-queryable and the
telemetry layer (ISSUE 2) made it post-hoc inspectable — but both lose
exactly the evidence an incident needs: counters are cumulative,
windowed frames roll off, and trace files only exist when an operator
asked *in advance*. By the time an SLO burn-rate alert fires, a
divergence guard rolls back, the watchdog declares a stall, or the
circuit breaker opens, the seconds *before* the event are gone. The
:class:`FlightRecorder` is the black box: it keeps

* a bounded ring of recent **trace spans** — the same
  :mod:`tpu_syncbn.obs.tracing` records a ``--trace`` file holds, kept
  in a :class:`~tpu_syncbn.obs.tracing.RingTracer` when no tracer was
  installed (memory bounded by construction, no file ever written in
  steady state);
* the **windowed registry** ring it shares with (or owns like) the
  monitoring server's :class:`~tpu_syncbn.obs.timeseries.WindowedAggregator`
  — per-interval counter/histogram deltas covering the recent past;
* a ring of recent **step monitors** — the on-device health scalars
  (grad norms, BN running-stat health, non-finite counts) every
  ``StepOutput.monitors`` already carries, recorded per step by
  :class:`~tpu_syncbn.runtime.resilience.ResilientLoop`;
* a ring of recent **serve decisions** — admission sheds, rejections,
  deadline misses, circuit-breaker transitions, recorded by
  :class:`~tpu_syncbn.serve.batcher.DynamicBatcher` and
  :class:`~tpu_syncbn.serve.admission.CircuitBreaker`;
* a ring of recent **memory watermarks** — per-sample device/host
  readings recorded by :class:`~tpu_syncbn.obs.memwatch.MemorySampler`,
  so an OOM post-mortem has the pre-pressure history;
* a ring of recent **compile events** — one entry per compile seam
  (:func:`tpu_syncbn.obs.profiling.note_compile`), the evidence a
  ``recompile_storm`` bundle names the churning family with;
* a ring of recent **autopilot decisions** — every knob turn (and
  every clamped or suppressed attempt) the closed-loop controller
  (:mod:`tpu_syncbn.runtime.autopilot`) makes, with the triggering
  signal quoted, so a post-mortem can replay the policy history.

On a trigger (:meth:`FlightRecorder.trigger` — fired by the SLO
tracker, the divergence guard, the watchdog, the circuit breaker, or
``POST /incidentz``) the rings plus a full registry snapshot, the
active alert/heartbeat/readiness state, the audit contract fingerprint,
and config/env are dumped atomically as a self-contained,
schema-versioned **incident bundle** (:mod:`tpu_syncbn.obs.incident`).
A cooldown keeps a flapping trigger from flooding the disk, and a
non-blocking trigger lock makes re-entrant triggers (an alert firing
*during* a dump's readiness probe) drop instead of deadlock.

Cost contract (the ``TPU_SYNCBN_TELEMETRY`` discipline): with no
recorder installed, the module-level helpers (:func:`record_step`,
:func:`record_serve`, :func:`trigger`) are one global load and a
``None`` test — no allocation, no lock (guarded by
tests/test_incident.py). Installation is gated by
``TPU_SYNCBN_FLIGHTREC`` (:func:`install_from_env`, called by
``ResilientLoop.run`` and ``DynamicBatcher.__init__`` the same way the
monitoring server's port gate is) or explicit :func:`install`.

Everything here is stdlib-only at module scope (no jax import) so any
layer can import it without ordering hazards.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Any

from tpu_syncbn.obs import telemetry, timeseries, tracing

_ENV_FLAG = "TPU_SYNCBN_FLIGHTREC"
_ENV_DIR = "TPU_SYNCBN_INCIDENT_DIR"
_TRUTHY = ("1", "true", "on", "yes")

#: Default incident-bundle directory when neither the constructor nor
#: ``TPU_SYNCBN_INCIDENT_DIR`` names one.
DEFAULT_INCIDENT_DIR = "incidents"


def _scalarize(value) -> Any:
    """JSON-safe scalar from a ring entry's recorded value: device
    arrays (the monitors are 0-d jax arrays) and numpy scalars go
    through ``float()``; non-finite floats become strings (strict-JSON
    safe); anything unconvertible is dropped by the caller.

    A value whose computation has not settled reads as ``"pending"``
    rather than being fetched: ``float()`` on a device array blocks
    until the producing computation completes, and the one incident
    class where that matters — a hung collective, i.e. exactly the
    ``watchdog_stall`` trigger — would otherwise wedge the dump (and
    the trigger lock) forever. ``is_ready()`` is the non-blocking
    probe."""
    if isinstance(value, bool):
        return value
    if isinstance(value, (int, str)) or value is None:
        return value
    try:
        is_ready = getattr(value, "is_ready", None)
        if callable(is_ready) and not is_ready():
            return "pending"
        f = float(value)
    except Exception:
        return None
    if f != f or f in (float("inf"), float("-inf")):
        return str(f)
    return f


def _scalarize_dict(d) -> dict:
    if not isinstance(d, dict):
        return {}
    out = {}
    for k, v in d.items():
        s = _scalarize(v)
        if s is not None:
            out[str(k)] = s
    return out


class FlightRecorder:
    """Bounded rings of recent cross-subsystem activity plus the
    incident-dump trigger machinery (module docstring has the design).

    ``aggregator`` shares an existing
    :class:`~tpu_syncbn.obs.timeseries.WindowedAggregator` (a
    monitored process) — otherwise the recorder owns one and
    :meth:`start` runs its background sampler. ``cooldown_s`` bounds
    dump frequency per recorder (``force=True`` — the manual trigger —
    bypasses it). ``incident_dir`` defaults to
    ``TPU_SYNCBN_INCIDENT_DIR`` or ``./incidents``; at most
    ``max_bundles`` bundles are retained (oldest pruned).
    """

    def __init__(
        self,
        *,
        span_capacity: int = 2048,
        step_capacity: int = 512,
        serve_capacity: int = 512,
        mem_capacity: int = 512,
        compile_capacity: int = 256,
        autopilot_capacity: int = 256,
        registry: telemetry.Registry | None = None,
        aggregator: timeseries.WindowedAggregator | None = None,
        interval_s: float = 1.0,
        window_capacity: int = 120,
        cooldown_s: float = 30.0,
        incident_dir: str | None = None,
        max_bundles: int = 16,
        now=time.monotonic,
    ):
        for name, v in (("span_capacity", span_capacity),
                        ("step_capacity", step_capacity),
                        ("serve_capacity", serve_capacity),
                        ("mem_capacity", mem_capacity),
                        ("compile_capacity", compile_capacity),
                        ("autopilot_capacity", autopilot_capacity),
                        ("max_bundles", max_bundles)):
            if v < 1:
                raise ValueError(f"{name} must be >= 1, got {v}")
        if cooldown_s < 0:
            raise ValueError(f"cooldown_s must be >= 0, got {cooldown_s}")
        self.registry = registry if registry is not None else telemetry.REGISTRY
        self._owns_aggregator = aggregator is None
        self.aggregator = (
            timeseries.WindowedAggregator(
                self.registry, interval_s=interval_s,
                capacity=window_capacity,
            ) if aggregator is None else aggregator
        )
        self.span_capacity = int(span_capacity)
        self.cooldown_s = float(cooldown_s)
        self.incident_dir = (
            incident_dir
            or os.environ.get(_ENV_DIR, "").strip()
            or DEFAULT_INCIDENT_DIR
        )
        self.max_bundles = int(max_bundles)
        self._now = now
        self._lock = threading.Lock()
        self._steps: deque = deque(maxlen=int(step_capacity))
        self._serve: deque = deque(maxlen=int(serve_capacity))
        self._mem: deque = deque(maxlen=int(mem_capacity))
        self._compile: deque = deque(maxlen=int(compile_capacity))
        self._autopilot: deque = deque(maxlen=int(autopilot_capacity))
        self._contract: dict = {}
        self._seq = 0
        self._last_dump_t: float | None = None
        # non-blocking: a trigger landing while a dump is in flight (or
        # re-entering from the dump's own readiness probe) is dropped,
        # never queued — one bundle per incident, no deadlock
        self._trigger_lock = threading.Lock()
        self._own_tracer: tracing.Tracer | None = None
        #: ``{"id", "path", "trigger", "wall_time"}`` of the newest
        #: bundle, or None — surfaced on ``/statusz``.
        self.last_incident: dict | None = None
        #: always-on local counts (triggers/bundles/suppressed/errors);
        #: mirrored into the registry as ``incident.*`` when telemetry
        #: is enabled.
        self.counters = telemetry.CounterGroup(prefix="incident")
        self._log = None

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "FlightRecorder":
        """Arm the recorder: install a bounded
        :class:`~tpu_syncbn.obs.tracing.RingTracer` if no tracer is
        recording (an existing tracer is
        tapped, not replaced), and start the owned aggregator's
        background sampler. Idempotent."""
        if tracing.get() is None:
            self._own_tracer = tracing.install(
                tracing.RingTracer(self.span_capacity)
            )
        if self._owns_aggregator:
            self.aggregator.start()
        return self

    def close(self) -> None:
        """Stop the owned sampler and uninstall the recorder's own ring
        tracer (only if it is still the installed one)."""
        if self._owns_aggregator:
            self.aggregator.close()
        if self._own_tracer is not None \
                and tracing.get() is self._own_tracer:
            tracing.uninstall()
        self._own_tracer = None

    def __enter__(self) -> "FlightRecorder":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _logger(self):
        if self._log is None:
            from tpu_syncbn.runtime import distributed as dist

            self._log = dist.get_logger("tpu_syncbn.obs")
        return self._log

    # -- recording ---------------------------------------------------------

    def record_step(self, step: int, metrics=None, monitors=None) -> None:
        """Append one step's health record to the step ring. ``metrics``
        / ``monitors`` are kept as-is (0-d device arrays stay async —
        no host sync is forced here); conversion to JSON scalars happens
        at dump time, when a sync is the least of anyone's worries."""
        entry = {"step": int(step), "t": self._now(),
                 "metrics": metrics, "monitors": monitors}
        with self._lock:
            self._steps.append(entry)

    def record_serve(self, kind: str, **detail) -> None:
        """Append one serve decision (shed / rejected / deadline_miss /
        circuit transition / …) to the serve ring."""
        entry = {"kind": str(kind), "t": self._now(), **detail}
        with self._lock:
            self._serve.append(entry)

    def record_mem(self, **reading) -> None:
        """Append one memory-watermark reading (JSON scalars — the
        sampler already flattened device stats) to the mem ring."""
        entry = {"t": self._now(), **reading}
        with self._lock:
            self._mem.append(entry)

    def record_compile(self, family: str, seconds=None, **detail) -> None:
        """Append one compile-seam event to the compile ring."""
        entry = {"family": str(family), "t": self._now(), **detail}
        if seconds is not None:
            entry["seconds"] = round(float(seconds), 6)
        with self._lock:
            self._compile.append(entry)

    def record_autopilot(self, knob: str, **detail) -> None:
        """Append one autopilot decision (escalate / de-escalate /
        retune / clamp / suppress, per knob) to the autopilot ring —
        every policy step lands here whether or not it also dumped an
        incident bundle, so a post-mortem can replay the controller's
        recent history."""
        entry = {"knob": str(knob), "t": self._now(), **detail}
        with self._lock:
            self._autopilot.append(entry)

    def set_contract(self, **fields) -> None:
        """Merge static program-contract facts into the recorder —
        ``flops_per_step`` (HLO cost analysis),
        ``collective_bytes_per_step`` (sharding-auditor bytes-on-wire),
        ``fingerprint`` (:func:`tpu_syncbn.obs.incident.contract_fingerprint`)
        — the join key the attribution report
        (``python -m tpu_syncbn.obs.incident inspect``) uses to split
        step time into compute vs collective shares."""
        with self._lock:
            self._contract.update(fields)

    # -- queries -----------------------------------------------------------

    def contract(self) -> dict:
        with self._lock:
            return dict(self._contract)

    def rings_snapshot(self) -> dict:
        """JSON-ready copy of the step and serve rings (device scalars
        forced to floats here — dump time, not record time)."""
        with self._lock:
            steps = list(self._steps)
            serve = list(self._serve)
            mem = list(self._mem)
            compiles = list(self._compile)
            autopilot = list(self._autopilot)
        return {
            "steps": [
                {
                    "step": e["step"], "t": round(e["t"], 6),
                    "metrics": _scalarize_dict(e["metrics"]),
                    "monitors": _scalarize_dict(e["monitors"]),
                }
                for e in steps
            ],
            "serve": [
                {k: (_scalarize(v) if k != "kind" else v)
                 for k, v in e.items()}
                for e in serve
            ],
            "mem": [
                {k: (_scalarize(v) if k not in ("source",
                                                "contract_source") else v)
                 for k, v in e.items()}
                for e in mem
            ],
            "compile": [
                {k: (_scalarize(v) if k != "family" else v)
                 for k, v in e.items()}
                for e in compiles
            ],
            # decision fields (knob/action/signal/from/to) are strings
            # by construction; scalarize only the numeric payload
            "autopilot": [
                {k: (v if isinstance(v, str) else _scalarize(v))
                 for k, v in e.items()}
                for e in autopilot
            ],
        }

    def ring_coverage(self) -> dict:
        """How far back the step ring reaches: entry count and the
        monotonic span between its oldest and newest entries."""
        with self._lock:
            steps = list(self._steps)
        seconds = (steps[-1]["t"] - steps[0]["t"]) if len(steps) > 1 else 0.0
        return {"steps": len(steps), "seconds": round(seconds, 6)}

    # -- the trigger -------------------------------------------------------

    def trigger(
        self, kind: str, detail: dict | None = None, *, force: bool = False,
    ) -> str | None:
        """Dump an incident bundle now; returns its path, or ``None``
        when the trigger was suppressed (cooldown, a dump already in
        flight) or the dump failed (logged — a recorder must never take
        down the workload it records). ``force=True`` (the manual
        trigger) bypasses the cooldown."""
        if not self._trigger_lock.acquire(blocking=False):
            self.counters.bump("suppressed")
            return None
        try:
            t = self._now()
            with self._lock:
                cooled = (force or self._last_dump_t is None
                          or t - self._last_dump_t >= self.cooldown_s)
                if cooled:
                    self._last_dump_t = t
                    self._seq += 1
                    seq = self._seq
            if not cooled:
                self.counters.bump("suppressed")
                return None
            self.counters.bump("triggers")
            from tpu_syncbn.obs import incident as incident_mod

            t0 = time.perf_counter()
            bundle = incident_mod.build_bundle(
                self, kind, dict(detail or {}), seq=seq
            )
            path = incident_mod.write_bundle(
                bundle, self.incident_dir, max_bundles=self.max_bundles
            )
            dump_s = time.perf_counter() - t0
            with self._lock:
                self.last_incident = {
                    "id": bundle["incident_id"], "path": path,
                    "trigger": kind, "wall_time": bundle["wall_time"],
                }
            self.counters.bump("bundles")
            telemetry.observe("incident.dump_s", dump_s)
            telemetry.set_gauge("incident.bundle_bytes",
                                os.path.getsize(path))
            tracing.instant("incident_bundle", trigger=kind,
                            incident_id=bundle["incident_id"])
            self._logger().warning(
                "incident bundle %s dumped to %s (trigger=%s, %.0f ms)",
                bundle["incident_id"], path, kind, dump_s * 1e3,
            )
            return path
        except Exception:
            self.counters.bump("errors")
            # a failed dump must not spend the cooldown: the NEXT
            # trigger for this incident should get its chance at a
            # bundle (transient write errors would otherwise silence
            # non-forced triggers for a whole cooldown window)
            with self._lock:
                if self._last_dump_t == t:
                    self._last_dump_t = None
            self._logger().exception(
                "incident dump failed (trigger=%s) — continuing", kind,
            )
            return None
        finally:
            self._trigger_lock.release()


# ---------------------------------------------------------------------------
# module-level installed recorder (the hot-path API)


_installed: FlightRecorder | None = None
_install_lock = threading.Lock()


def install(recorder: FlightRecorder | None = None) -> FlightRecorder:
    """Install ``recorder`` (or a fresh default one) as the process
    flight recorder the module helpers feed; starts it. Returns it."""
    global _installed
    with _install_lock:
        if recorder is None:
            recorder = FlightRecorder()
        recorder.start()
        _installed = recorder
        return recorder


def uninstall() -> FlightRecorder | None:
    """Remove and return the installed recorder (closing it is the
    caller's choice — its rings stay intact for inspection)."""
    global _installed
    with _install_lock:
        rec, _installed = _installed, None
        return rec


def get() -> FlightRecorder | None:
    return _installed


def install_from_env() -> FlightRecorder | None:
    """Install (once) the process recorder if ``TPU_SYNCBN_FLIGHTREC``
    is truthy; return it (or the one already installed, or ``None`` when
    the env gate is off). Idempotent — ``ResilientLoop.run`` and
    ``DynamicBatcher.__init__`` both call it, so exporting the env var
    is the whole knob, exactly like ``TPU_SYNCBN_METRICS_PORT``."""
    global _installed
    if os.environ.get(_ENV_FLAG, "").strip().lower() not in _TRUTHY:
        return None
    with _install_lock:
        if _installed is not None:
            return _installed
        _installed = FlightRecorder().start()
        return _installed


def record_step(step: int, metrics=None, monitors=None) -> None:
    """Feed one step record to the installed recorder (one global load
    + None test when no recorder is installed — hot-loop safe)."""
    rec = _installed
    if rec is not None:
        rec.record_step(step, metrics=metrics, monitors=monitors)


def record_serve(kind: str, **detail) -> None:
    """Feed one serve decision to the installed recorder (no-op without
    a recorder)."""
    rec = _installed
    if rec is not None:
        rec.record_serve(kind, **detail)


def record_compile(family: str, seconds=None, **detail) -> None:
    """Feed one compile-seam event to the installed recorder (no-op
    without one)."""
    rec = _installed
    if rec is not None:
        rec.record_compile(family, seconds, **detail)


def record_autopilot(knob: str, **detail) -> None:
    """Feed one autopilot decision to the installed recorder (no-op
    without one)."""
    rec = _installed
    if rec is not None:
        rec.record_autopilot(knob, **detail)


def trigger(
    kind: str, detail: dict | None = None, *, force: bool = False,
) -> str | None:
    """Fire the installed recorder's trigger (no-op without one)."""
    rec = _installed
    if rec is not None:
        return rec.trigger(kind, detail, force=force)
    return None


def install_signal_trigger(signum: int | None = None):
    """Opt-in: make a signal the manual trigger (the no-HTTP escape
    hatch — ``kill -USR2 <pid>`` dumps a bundle the way ``POST
    /incidentz`` does). Signal handlers are process-global and
    main-thread-only, and SIGUSR1 already belongs to the serving drain
    tests, so this defaults to SIGUSR2 and is never installed
    implicitly. Returns the previous handler."""
    import signal as _signal

    if signum is None:
        signum = _signal.SIGUSR2

    def _handle(sig, frame):
        trigger("manual", {"source": "signal", "signum": int(sig)},
                force=True)

    return _signal.signal(signum, _handle)
