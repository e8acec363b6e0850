"""Fault-tolerant training runtime: preemption, stalls, retries.

The paper's recipe assumes every worker, every rendezvous, and every step
succeeds; on real slices preemption, flaky coordinator DNS, hung data
workers, and NaN blow-ups are the common case. This module is the host-side
resilience layer (docs/RESILIENCE.md is the failure-mode → behavior map):

* :class:`PreemptionGuard` — SIGTERM/SIGINT become a *checkpoint request at
  the next step boundary* instead of a mid-step kill (the Cloud TPU
  preemption contract: a grace window after SIGTERM, then SIGKILL).
* :class:`Watchdog` / :func:`stall_guard` — a collective or data fetch that
  stalls past a deadline dumps per-host diagnostics (thread stacks, device
  and process identity) and surfaces a :class:`StallError` rather than
  hanging the job silently until the scheduler reaps it.
* :func:`retry_with_backoff` — bounded exponential backoff with
  *deterministic* jitter (keyed, no wall-clock randomness) shared by the
  rendezvous retry in ``runtime.distributed.initialize``.
* :class:`ResilientLoop` — composes the above with the manifest-verified
  checkpoint store (``utils.checkpoint``) and the trainer's on-device
  divergence guard into a preemption-safe step loop with
  ``resume_latest`` orchestration and a ``restore_last_good`` policy.

Everything here is host-level control flow: no jax tracing, usable with any
trainer exposing ``state_dict``/``load_state_dict``/``train_step``.
"""

from __future__ import annotations

import contextlib
import io
import os
import signal
import sys
import threading
import time
import traceback
import zlib
from typing import Any, Callable, Iterable, Iterator

from tpu_syncbn.runtime import distributed as dist


class StallError(RuntimeError):
    """A step collective or data fetch exceeded its watchdog deadline."""


# ---------------------------------------------------------------------------
# preemption


class PreemptionGuard:
    """Convert SIGTERM/SIGINT into a polite "checkpoint at the next step
    boundary, then exit" request.

    Usage::

        with PreemptionGuard() as guard:
            for batch in loader:
                dp.train_step(batch)
                if guard.preempted:
                    save_checkpoint(ckpt_dir, step, dp.state_dict())
                    break

    The first signal only sets a flag (checked via :attr:`preempted` at
    step boundaries — never mid-step, so the saved state is a step-exact
    snapshot). A *second* signal re-raises through the previously
    installed handler: an impatient operator's double Ctrl-C still kills
    the process immediately.

    Signal handlers are process-global and only installable from the main
    thread; constructing the guard elsewhere raises ``ValueError`` (from
    ``signal.signal``) rather than silently not protecting anything.
    """

    def __init__(
        self,
        signals: tuple = (signal.SIGTERM, signal.SIGINT),
        *,
        callback: Callable[[int], None] | None = None,
    ):
        self._signals = tuple(signals)
        self._callback = callback
        self._subscribers: list[Callable[[int], None]] = []
        self._event = threading.Event()
        self._prev: dict[int, Any] = {}
        self._received: int | None = None
        self._installed = False

    def subscribe(self, fn: Callable[[int], None]) -> None:
        """Add a listener invoked (after the construction ``callback``)
        on the FIRST signal delivery. Lets late-attached components —
        e.g. a :class:`~tpu_syncbn.serve.publish.SwapController` that
        must drain a mid-swap engine — hook the same guard the training
        loop and batcher already share. Listener exceptions are
        swallowed: a broken subscriber must not turn a polite drain
        into a crash inside a signal handler."""
        self._subscribers.append(fn)

    # -- handler ----------------------------------------------------------

    def _handle(self, signum, frame):
        if self._event.is_set():
            # second delivery: defer to the original disposition (usually
            # fatal) — the operator means it
            self._restore()
            os.kill(os.getpid(), signum)
            return
        self._received = signum
        self._event.set()
        dist.get_logger("tpu_syncbn.resilience").warning(
            "received signal %d: will checkpoint at the next step boundary "
            "and exit", signum,
        )
        if self._callback is not None:
            self._callback(signum)
        for fn in self._subscribers:
            with contextlib.suppress(Exception):
                fn(signum)

    def __enter__(self) -> "PreemptionGuard":
        for s in self._signals:
            self._prev[s] = signal.signal(s, self._handle)
        self._installed = True
        return self

    def __exit__(self, *exc) -> None:
        self._restore()

    def _restore(self) -> None:
        if self._installed:
            for s, prev in self._prev.items():
                with contextlib.suppress(Exception):
                    signal.signal(s, prev)
            self._installed = False

    # -- queries ----------------------------------------------------------

    @property
    def preempted(self) -> bool:
        """True once a shutdown signal has been received."""
        return self._event.is_set()

    @property
    def signum(self) -> int | None:
        return self._received

    def wait(self, timeout: float | None = None) -> bool:
        return self._event.wait(timeout)


# ---------------------------------------------------------------------------
# watchdog


def dump_stacks(header: str = "") -> str:
    """Per-host diagnostic snapshot: process identity, device world, and
    every Python thread's stack — what you need from EACH host to see
    which rank a stalled collective is waiting on."""
    import jax

    buf = io.StringIO()
    if header:
        buf.write(header + "\n")
    try:
        buf.write(
            f"host {dist.process_index()}/{dist.process_count()} "
            f"({jax.local_device_count()} local / {jax.device_count()} "
            "global devices)\n"
        )
    except Exception as e:  # diagnostics must never throw past themselves
        buf.write(f"device world unavailable: {e}\n")
    frames = sys._current_frames()
    threads = {t.ident: t for t in threading.enumerate()}
    for ident, frame in frames.items():
        t = threads.get(ident)
        name = t.name if t else f"thread-{ident}"
        buf.write(f"--- thread {name} ---\n")
        buf.write("".join(traceback.format_stack(frame)))
    return buf.getvalue()


class Watchdog:
    """Deadline monitor for the step loop: if :meth:`pat` is not called
    within ``deadline_s``, dump per-host diagnostics (once per stall) and
    invoke ``on_stall`` — by default logging the dump at ERROR so a hung
    collective leaves evidence on every host instead of an opaque freeze.

    Pass ``on_stall=` + a raising callable (or use :func:`stall_guard` for
    data iterators, which raises :class:`StallError` in the *consumer*)
    when the stall should abort rather than just report. The monitor is a
    daemon thread; ``close()`` (or context-manager exit) stops it.
    """

    def __init__(
        self,
        deadline_s: float,
        *,
        name: str = "step",
        on_stall: Callable[[str], None] | None = None,
        poll_s: float | None = None,
        start_armed: bool = True,
    ):
        """``start_armed=False`` defers the deadline clock until the
        first :meth:`pat` — for loops whose first iteration legitimately
        dwarfs the steady-state deadline (XLA compiling the step on a
        cold start would otherwise read as a stall)."""
        if deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        self.deadline_s = float(deadline_s)
        self.name = name
        self._on_stall = on_stall
        self._poll_s = poll_s if poll_s is not None else min(
            0.05, deadline_s / 4
        )
        self._last = time.monotonic() if start_armed else None
        self._stalled_since: float | None = None
        self.stall_count = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name=f"watchdog-{name}", daemon=True
        )
        self._thread.start()

    def pat(self) -> None:
        """Mark liveness (call once per step / per batch)."""
        self._last = time.monotonic()
        self._stalled_since = None

    def _run(self) -> None:
        while not self._stop.wait(self._poll_s):
            if self._last is None:
                continue  # not armed yet (start_armed=False, no pat)
            idle = time.monotonic() - self._last
            if idle > self.deadline_s and self._stalled_since is None:
                self._stalled_since = self._last
                self.stall_count += 1
                # tag the dump with the most recently opened trace span
                # (the monitor thread has no span stack of its own) so a
                # Perfetto trace and this event log join on span id
                from tpu_syncbn.obs import flightrec, telemetry, tracing

                span_id = tracing.latest_open_span_id()
                telemetry.count("resilience.watchdog_stalls")
                tracing.instant(
                    "watchdog_stall", watchdog=self.name,
                    idle_s=round(idle, 2),
                    **({"span_id": span_id} if span_id is not None else {}),
                )
                tag = f", trace_span={span_id}" if span_id is not None else ""
                diag = dump_stacks(
                    f"WATCHDOG: {self.name!r} stalled for {idle:.1f}s "
                    f"(deadline {self.deadline_s}s{tag})"
                )
                logger = dist.get_logger("tpu_syncbn.resilience")
                logger.error("%s", diag)
                # the stack dump says where THIS host is stuck; the
                # incident bundle says what the whole process was doing
                # in the seconds before (docs/OBSERVABILITY.md)
                flightrec.trigger("watchdog_stall", {
                    "watchdog": self.name, "idle_s": round(idle, 2),
                    "deadline_s": self.deadline_s,
                    **({"span_id": span_id} if span_id is not None
                       else {}),
                })
                if self._on_stall is not None:
                    with contextlib.suppress(Exception):
                        self._on_stall(diag)

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2)

    def __enter__(self) -> "Watchdog":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def stall_guard(
    iterator: Iterable, deadline_s: float, *, name: str = "batch"
) -> Iterator:
    """Wrap a (possibly hanging) batch iterator so the consumer NEVER
    blocks past ``deadline_s`` on one item: a fetcher thread pulls from
    the source while the consumer waits on a queue with a timeout, raising
    :class:`StallError` (with per-host stack diagnostics logged) when the
    deadline passes — a hung data worker becomes a loud, catchable fault
    at the step boundary instead of an indefinite hang.

    The fetcher prefetches at most one item. Once the consumer is done —
    StallError raised, generator closed, or the source exhausted — a stop
    flag makes the fetcher exit as soon as its (possibly still-hung)
    ``next()`` returns, rather than lingering blocked on the queue: an
    abandoned guard must not keep pulling from a source iterator the
    caller may hand to a fresh guard on retry. The one batch in flight at
    stall time is dropped with the stalled fetch; only a fetcher stuck
    inside the source forever remains (daemon — dies with the process).
    """
    import queue as _queue

    if deadline_s <= 0:
        raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
    q: Any = _queue.Queue(maxsize=1)
    DONE, ERR = object(), object()
    stop = threading.Event()

    def put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except _queue.Full:
                continue
        return False

    def fetch():
        try:
            for item in iterator:
                if not put(("ok", item)):
                    return  # consumer gone: do not touch the source again
        except BaseException as e:
            put((ERR, e))
            return
        put((DONE, None))

    t = threading.Thread(target=fetch, name=f"stall-guard-{name}",
                         daemon=True)
    t.start()
    try:
        while True:
            try:
                tag, payload = q.get(timeout=deadline_s)
            except _queue.Empty:
                from tpu_syncbn.obs import flightrec, telemetry, tracing

                span_id = tracing.latest_open_span_id()
                telemetry.count("resilience.data_stalls")
                tracing.instant(
                    "data_stall", source=name,
                    **({"span_id": span_id} if span_id is not None else {}),
                )
                tag = (f" (trace_span={span_id})"
                       if span_id is not None else "")
                diag = dump_stacks(
                    f"WATCHDOG: {name!r} fetch exceeded {deadline_s}s{tag}"
                )
                dist.get_logger("tpu_syncbn.resilience").error("%s", diag)
                flightrec.trigger("watchdog_stall", {
                    "source": name, "deadline_s": deadline_s,
                    "stall": "data_fetch",
                })
                raise StallError(
                    f"{name} fetch exceeded the {deadline_s}s watchdog "
                    "deadline"
                ) from None
            if tag is DONE:
                return
            if tag is ERR:
                raise payload
            yield payload
    finally:
        stop.set()


# ---------------------------------------------------------------------------
# retry / backoff


def backoff_delays(
    attempts: int,
    *,
    base_s: float = 1.0,
    max_s: float = 30.0,
    jitter: float = 0.25,
    key: str = "",
) -> list[float]:
    """The ``attempts - 1`` sleep durations between retries: exponential
    (``base * 2**i`` capped at ``max_s``) with ±``jitter`` fractional
    spread. Jitter is *deterministic* — keyed off ``key`` (e.g. host
    index) via CRC32, not wall-clock RNG — so retries are reproducible
    under the fault harness yet de-synchronized across hosts (the point
    of jitter: N preempted hosts must not re-storm the coordinator in
    lockstep)."""
    delays = []
    for i in range(max(0, attempts - 1)):
        d = min(max_s, base_s * (2 ** i))
        # unit-interval hash of (key, attempt): stable across runs
        u = (zlib.crc32(f"{key}:{i}".encode()) & 0xFFFFFFFF) / 0xFFFFFFFF
        delays.append(d * (1.0 + jitter * (2.0 * u - 1.0)))
    return delays


def retry_with_backoff(
    fn: Callable[[], Any],
    *,
    attempts: int = 3,
    base_s: float = 1.0,
    max_s: float = 30.0,
    jitter: float = 0.25,
    key: str = "",
    retry_on: tuple = (Exception,),
    describe: str = "operation",
    sleep: Callable[[float], None] | None = None,
) -> Any:
    """Call ``fn`` up to ``attempts`` times with :func:`backoff_delays`
    between failures; the final failure re-raises. Each retry is logged
    with the exception — a rendezvous that needed 3 tries is an incident
    worth seeing in the log even when it eventually succeeds."""
    if attempts < 1:
        raise ValueError(f"attempts must be >= 1, got {attempts}")
    if sleep is None:
        sleep = time.sleep  # late-bound: patchable via resilience.time
    delays = backoff_delays(
        attempts, base_s=base_s, max_s=max_s, jitter=jitter, key=key
    )
    logger = dist.get_logger("tpu_syncbn.resilience")
    for i in range(attempts):
        try:
            return fn()
        except retry_on as e:
            if i == attempts - 1:
                raise
            logger.warning(
                "%s failed (attempt %d/%d: %s: %s); retrying in %.2fs",
                describe, i + 1, attempts, type(e).__name__, e, delays[i],
            )
            sleep(delays[i])
    raise AssertionError("unreachable")


# ---------------------------------------------------------------------------
# orchestration


def _default_counters():
    from tpu_syncbn.obs.telemetry import CounterGroup

    # prefix="resilience": every bump mirrors into the process telemetry
    # registry (as resilience.<event>) when telemetry is enabled, so
    # recovery events ride the same export path as step/loader/checkpoint
    # metrics — while the loop's own summary() works unconditionally
    return CounterGroup("resilience")


class ResilientLoop:
    """Preemption-safe training driver over any trainer with the
    ``state_dict``/``load_state_dict``/``train_step`` surface (the
    ``DataParallel``/``GANTrainer`` contract).

    Composes the resilience primitives into the loop the examples run::

        loop = ResilientLoop(dp, ckpt_dir, ckpt_every=100)
        start = loop.resume()                  # newest VERIFIED checkpoint
        summary = loop.run(batches)            # SIGTERM-safe, NaN-guarded

    Behavior (knobs → docs/RESILIENCE.md):

    * resume: :meth:`resume` restores the newest *verified* checkpoint
      (``utils.checkpoint`` manifest fallback) and returns the step to
      continue from (0 when none exists).
    * preemption: SIGTERM/SIGINT set a flag; the loop finishes the
      in-flight step, saves a checkpoint at the boundary, and returns with
      ``summary["preempted"] = True`` — exit code stays 0, the restarted
      job resumes exactly there.
    * divergence: when the trainer was built with
      ``divergence_guard="restore_last_good"``, a step reporting a
      non-finite loss/grad (the on-device ``nonfinite`` metric) reloads
      the last verified checkpoint; ``max_restores`` bounds the
      thrash-loop (beyond it the loop raises ``FloatingPointError``).
      ``skip_step``/``halve_lr`` policies are entirely on-device and need
      no host cooperation (the loop just counts them).
    * liveness: ``step_deadline_s`` arms a :class:`Watchdog` patted every
      step; a stall dumps per-host stacks. Data stalls should be guarded
      at the iterator with :func:`stall_guard` (raises, so the loop can
      checkpoint-and-exit via the normal exception path).
    """

    #: Bounded wait for async checkpoint writes while a training failure
    #: is already propagating: long enough for any healthy write (a
    #: 204MB payload serializes in ~1s), short enough that a
    #: wedged writer (stuck filesystem) can't turn a StallError into an
    #: indefinite hang with the watchdog already disarmed.
    _EXC_FLUSH_TIMEOUT_S = 60.0

    def __init__(
        self,
        trainer,
        ckpt_dir: str,
        *,
        ckpt_every: int = 100,
        keep: int = 3,
        max_restores: int = 3,
        step_deadline_s: float | None = None,
        counters=None,
        scan_steps: int = 1,
        async_checkpoint: bool = False,
        publish_dir: str | None = None,
        publish_every: int | None = None,
        publish_keep: int = 3,
        autopilot=None,
    ):
        """``scan_steps=K > 1`` drives the fused multi-step path
        (docs/PERFORMANCE.md): ``batches`` must then yield K-stacked
        chunks (``data.device_prefetch(scan_steps=K)``) and the loop
        calls ``trainer.train_steps_batches`` once per chunk — one host
        dispatch per K steps, with preemption, checkpoint cadence, and
        divergence policies honored at chunk boundaries (the on-device
        guard still rolls back each bad step *inside* the chunk; the
        host sees the chunk's stacked ``nonfinite`` metrics afterward).
        ``step_deadline_s`` stays a per-STEP deadline: the loop arms its
        watchdog at ``step_deadline_s * scan_steps`` since it can only
        pat once per chunk.

        ``async_checkpoint=True`` routes saves through
        ``utils.checkpoint.AsyncCheckpointer``: the loop pays only the
        state snapshot; serialization + manifest + atomic write happen
        in a background thread, and the loop **flushes pending writes on
        every exit path** — the PreemptionGuard boundary checkpoint is
        durable before the process yields to SIGKILL.

        ``publish_dir`` additionally emits manifest-verified *serving*
        publications (``utils.checkpoint.publish_version``) every
        ``publish_every`` steps (default: ``ckpt_every``): a versioned
        inference tree (``{"params", "rest"}`` — BN running stats ride
        along) that a serving process hot-swaps in via
        ``serve.publish.SwapController.swap_from_publication``. Under
        ``zero=True`` the flat shards are gathered first (the durable
        cross-process path is host serialization by nature; the
        no-host-gather on-mesh path is the *in-process*
        ``swap_from_trainer``). Publications follow the checkpoint
        transport: async when ``async_checkpoint=True``.

        ``autopilot`` attaches a
        :class:`~tpu_syncbn.runtime.autopilot.Autopilot`: the loop
        drives its :meth:`~tpu_syncbn.runtime.autopilot.Autopilot.on_chunk`
        at every chunk boundary (suppressed, and recorded as
        suppressed, while a divergence rollback is recovering), mirrors
        its live ``scan_k`` into ``self.scan_steps``, and rescales the
        watchdog deadline to the live K. Feed the loop through
        :func:`~tpu_syncbn.runtime.autopilot.chunked_batches` so the
        data side follows the same K."""
        if ckpt_every < 1:
            raise ValueError(f"ckpt_every must be >= 1, got {ckpt_every}")
        if scan_steps < 1:
            raise ValueError(f"scan_steps must be >= 1, got {scan_steps}")
        if publish_every is not None and publish_every < 1:
            raise ValueError(
                f"publish_every must be >= 1, got {publish_every}"
            )
        self.trainer = trainer
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.keep = keep
        self.max_restores = max_restores
        self.step_deadline_s = step_deadline_s
        self.scan_steps = scan_steps
        self.autopilot = autopilot
        self.publish_dir = publish_dir
        self.publish_every = (
            int(publish_every) if publish_every is not None else ckpt_every
        )
        self.publish_keep = publish_keep
        self.counters = counters if counters is not None else _default_counters()
        self.step = 0
        #: True while a divergence rollback is in flight (restore issued,
        #: no finite step completed since) — surfaced on /readyz via
        #: :meth:`readiness` so a balancer stops routing to a host that
        #: is busy recovering state.
        self.recovering = False
        self._guard: PreemptionGuard | None = None
        self._async = None
        if async_checkpoint:
            from tpu_syncbn.utils.checkpoint import AsyncCheckpointer

            self._async = AsyncCheckpointer(keep=keep)
        self._log = dist.get_logger("tpu_syncbn.resilience")

    # -- checkpoint plumbing ----------------------------------------------

    def flush_checkpoints(self, timeout: float | None = None) -> bool:
        """Block until async checkpoint writes (if any) are durable —
        called on every ``run()`` exit path, and before any read of the
        checkpoint directory (resume/restore), so a pending write can
        neither be lost to an exit nor raced by a load. Returns False
        when ``timeout`` expired with writes still in flight (the
        directory must then NOT be trusted as current)."""
        if self._async is not None:
            return self._async.flush(timeout)
        return True

    def close(self) -> None:
        """Flush and stop the async checkpoint worker (no-op without
        ``async_checkpoint=True``). Idempotent; a loop the caller keeps
        re-running can stay open, but one built per restart attempt
        should be closed (or used as a context manager) so worker
        threads don't accumulate."""
        if self._async is not None:
            self._async.close()

    def __enter__(self) -> "ResilientLoop":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def readiness(self) -> tuple[bool, dict]:
        """The loop's ``/readyz`` contribution (registered as the
        ``train`` hook while :meth:`run` is active): not ready once
        preemption has been signaled (the process is about to
        checkpoint-and-exit) or while a divergence rollback is in
        flight. The detail block carries the live step counter, so a
        probe can also see *where* the loop is."""
        guard = self._guard
        preempted = bool(guard.preempted) if guard is not None else False
        ok = not preempted and not self.recovering
        return ok, {
            "step": self.step,
            "preempted": preempted,
            "recovering": self.recovering,
        }

    def resume(self) -> int:
        """Restore the newest verified checkpoint (if any); returns the
        step training should continue from."""
        from tpu_syncbn.parallel.trainer import resume_latest

        self.flush_checkpoints()
        self.step = resume_latest(self.trainer, self.ckpt_dir)
        if self.step:
            self.counters.bump("resumes")
        return self.step

    def save(self) -> None:
        from tpu_syncbn.utils import checkpoint as ckpt

        if self._async is not None:
            self._async.save(
                self.ckpt_dir, self.step, self.trainer.state_dict(),
                keep=self.keep,
            )
        else:
            ckpt.save_checkpoint(
                self.ckpt_dir, self.step, self.trainer.state_dict(),
                keep=self.keep,
            )
        self.counters.bump("checkpoints")

    def publish(self) -> None:
        """Emit a manifest-verified serving publication of the current
        params at ``publish_dir``, versioned by the step counter (no-op
        without ``publish_dir``). The tree is the inference pair
        ``{"params", "rest"}``; under ZeRO the flat shards are gathered
        into the full pytree first (durable host path — the on-mesh
        redistribution serves the in-process swap instead)."""
        if self.publish_dir is None:
            return
        from tpu_syncbn.utils import checkpoint as ckpt

        trainer = self.trainer
        if getattr(trainer, "zero", False):
            from tpu_syncbn.parallel.zero import unshard_params

            params = unshard_params(trainer._layout, trainer._param_store)
        else:
            params = trainer._param_store
        tree = {"params": params, "rest": getattr(trainer, "rest", {})}
        if self._async is not None:
            self._async.publish(
                self.publish_dir, self.step, tree, keep=self.publish_keep,
            )
        else:
            ckpt.publish_version(
                self.publish_dir, self.step, tree,
                keep=self.publish_keep, step=self.step,
            )
        self.counters.bump("publishes")

    def _restore_last_good(self) -> None:
        from tpu_syncbn.parallel.trainer import resume_latest
        from tpu_syncbn.utils import checkpoint as ckpt

        self.flush_checkpoints()
        if not ckpt.available_steps(self.ckpt_dir):
            # nothing durable yet (divergence before the first save):
            # there is no state to restore — but the on-device guard
            # already rolled the bad update back, so degrading to
            # skip-step semantics (step counter untouched) is safe
            self.counters.bump("divergence_skips_without_checkpoint")
            self._log.warning(
                "non-finite loss/grads at step %d with no checkpoint to "
                "restore; on-device guard already skipped the update — "
                "continuing", self.step,
            )
            return
        restored = resume_latest(self.trainer, self.ckpt_dir)
        # the restored residual (compressed-collective error feedback)
        # encodes quantization error of the unwound trajectory — zero it
        # so the recovered run doesn't replay stale updates; an ordinary
        # resume (no divergence) keeps the checkpointed residual
        reset = getattr(self.trainer, "reset_compression_residual", None)
        if callable(reset):
            reset()
        self.counters.bump("divergence_restores")
        # not-ready until a finite step lands on the restored state
        # (cleared in run(); read by /readyz through readiness())
        self.recovering = True
        # tag the rollback with the current trace span so the Perfetto
        # timeline and this log line correlate (same id in both)
        from tpu_syncbn.obs import flightrec, tracing

        span_id = tracing.latest_open_span_id()
        tracing.instant(
            "divergence_restore", step=self.step, restored_step=restored,
            **({"span_id": span_id} if span_id is not None else {}),
        )
        self._log.warning(
            "non-finite loss/grads at step %d: restored last good "
            "checkpoint (step %d)%s",
            self.step, restored,
            f" (trace_span={span_id})" if span_id is not None else "",
        )
        # the bundle holds the step monitors from the steps BEFORE the
        # blow-up — the evidence a post-mortem of the divergence needs
        flightrec.trigger("divergence_restore", {
            "step": self.step, "restored_step": restored,
            **({"span_id": span_id} if span_id is not None else {}),
        })
        self.step = restored

    # -- the loop ---------------------------------------------------------

    def run(self, batches: Iterable, *, max_steps: int | None = None) -> dict:
        """Drive ``trainer.train_step`` over ``batches`` (or
        ``trainer.train_steps_batches`` over K-stacked chunks when
        ``scan_steps=K > 1``) with preemption, divergence, and liveness
        handling. Returns a summary dict (``steps``, ``preempted``, plus
        the counter snapshot).

        Chunked mode semantics (docs/PERFORMANCE.md): host policies fire
        at chunk boundaries — a SIGTERM landing mid-chunk lets the
        in-flight chunk finish (its K steps are one compiled program),
        then checkpoints and exits; ``ckpt_every`` saves whenever the
        step counter crosses a multiple; ``max_steps`` is checked before
        each chunk, so a run may overshoot it by at most K-1 steps. Any
        async checkpoint writes are flushed on every exit path."""
        import numpy as _np

        from tpu_syncbn.obs import (
            flightrec, numerics as obs_numerics, server as obs_server,
            telemetry,
        )
        from tpu_syncbn.parallel.collectives import DispatchWireTally

        policy = getattr(self.trainer, "divergence_guard", None)
        scanned = self.scan_steps > 1
        preempted = False
        # live monitoring (docs/OBSERVABILITY.md "Live monitoring"):
        # with TPU_SYNCBN_METRICS_PORT set this run answers /metrics,
        # /healthz (step heartbeat below), /readyz (the `train` hook)
        obs_server.start_from_env()
        # flight recorder (docs/OBSERVABILITY.md "Incidents"): with
        # TPU_SYNCBN_FLIGHTREC set this run keeps bounded rings of
        # recent spans/monitors and dumps an incident bundle on a
        # divergence restore, watchdog stall, SLO alert, or /incidentz
        flightrec.install_from_env()
        # memory watermarks (docs/OBSERVABILITY.md "Memory & compile"):
        # with TPU_SYNCBN_MEMWATCH set this run samples device/host
        # memory in the background; pinned-contract pressure dumps a
        # mem_pressure bundle before the allocator OOMs the loop
        from tpu_syncbn.obs import memwatch as obs_memwatch

        obs_memwatch.install_from_env()
        obs_server.register_readiness("train", self.readiness)
        wire_tally = DispatchWireTally()
        # numerics drift/compression telemetry (docs/OBSERVABILITY.md
        # "Numerics & drift"): publishes each step's numerics monitors
        # into the registry once their device values settle (is_ready
        # probe — never a forced host sync on the loop) and fires the
        # numerics_drift incident trigger on a threshold crossing
        numerics_pub = obs_numerics.NumericsPublisher()
        try:
            with contextlib.ExitStack() as stack:
                guard = stack.enter_context(PreemptionGuard())
                self._guard = guard
                watchdog = None
                if self.step_deadline_s is not None:
                    # armed at the first pat: the first step's XLA compile
                    # legitimately dwarfs the steady-state deadline.
                    # Chunked mode pats once per K-step chunk, so the
                    # per-STEP deadline the caller configured scales by K
                    # — a healthy chunk must not read as a stall. The
                    # deadline is recomputed from the LIVE K at every
                    # chunk boundary below: a mid-run K change (the
                    # autopilot's actuator, or manual retuning of
                    # self.scan_steps) must not leave a stale stall
                    # threshold.
                    watchdog = stack.enter_context(
                        Watchdog(self.step_deadline_s * self.scan_steps,
                                 name="train-step", start_armed=False)
                    )
                from tpu_syncbn.obs import stepstats

                steps_run = 0
                # explicit next() so the wait-for-data seam is measurable:
                # each blocking fetch is a "data_wait" span + histogram
                # sample, each step (or fused chunk) a span
                for batch in stepstats.instrumented_batches(batches):
                    if max_steps is not None and steps_run >= max_steps:
                        break
                    if scanned:
                        with stepstats.timed_span(
                            "scan_chunk", "step.chunk_time_s",
                            step=self.step + 1,
                        ):
                            out = self.trainer.train_steps_batches(batch)
                        k = int(out.loss.shape[0])
                    else:
                        with stepstats.timed_span("step", "step.time_s",
                                                  step=self.step + 1):
                            out = self.trainer.train_step(batch)
                        k = 1
                    self.step += k
                    steps_run += k
                    if watchdog is not None:
                        watchdog.pat()
                    # step heartbeat: /healthz reads the age of this
                    # beat; the gauge gives scrapers the live position
                    obs_server.HEARTBEATS.beat("train")
                    telemetry.set_gauge("train.step", self.step)
                    # step ring: async device scalars recorded as-is
                    # (no host sync here; scalarized at dump time)
                    flightrec.record_step(
                        self.step, metrics=out.metrics,
                        monitors=getattr(out, "monitors", None),
                    )
                    mon = getattr(out, "monitors", None)
                    if scanned and isinstance(mon, dict) and mon:
                        # chunk outputs are (K,)-stacked: publish the
                        # chunk-final slice (lazy device-side indexing,
                        # no host sync)
                        mon = {name: v[-1] for name, v in mon.items()}
                    numerics_pub.publish(self.step, mon)
                    wire_tally.after_dispatch(k)
                    if policy is not None:
                        # scalar for a single step, (K,)-stacked for a
                        # chunk: the sum is the count of skipped steps
                        nonfinite = int(_np.sum(_np.asarray(
                            out.metrics.get("nonfinite", 0.0)
                        )))
                        if nonfinite == 0:
                            # a finite step on (possibly restored) state:
                            # the rollback, if any, is complete — ready
                            self.recovering = False
                        if nonfinite > 0:
                            self.counters.bump("nonfinite_steps", nonfinite)
                            if policy == "restore_last_good":
                                if (self.counters.count("divergence_restores")
                                        >= self.max_restores):
                                    raise FloatingPointError(
                                        "divergence persisted through "
                                        f"{self.max_restores} "
                                        "restore_last_good recoveries — "
                                        "refusing to thrash"
                                    )
                                self._restore_last_good()
                                if self.autopilot is not None:
                                    # the guard owns the process during
                                    # a rollback: the policy step is
                                    # suppressed (and recorded as such)
                                    self.autopilot.on_chunk(
                                        step=self.step, k=k,
                                        recovering=True,
                                    )
                                if guard.preempted:
                                    # the restored state IS the last durable
                                    # checkpoint — exit now rather than burn
                                    # grace-window time on another step
                                    preempted = True
                                    self._log.warning(
                                        "preempted during divergence "
                                        "recovery at step %d; state already "
                                        "durable; exiting cleanly", self.step,
                                    )
                                    break
                                continue
                    if self.autopilot is not None:
                        # chunk-boundary policy step: the only place
                        # knobs turn. The loop mirrors the live K so
                        # max_steps/watchdog accounting follows the
                        # controller; the data side follows through
                        # autopilot.chunked_batches.
                        self.autopilot.on_chunk(
                            step=self.step, k=k,
                            recovering=self.recovering,
                        )
                        if scanned:
                            self.scan_steps = max(
                                1, int(self.autopilot.scan_k)
                            )
                    if (watchdog is not None
                            and self.step_deadline_s is not None):
                        # stale-deadline fix: recompute per chunk from
                        # the current K instead of trusting the value
                        # captured at construction
                        watchdog.deadline_s = (
                            self.step_deadline_s * max(1, self.scan_steps)
                        )
                    if guard.preempted:
                        self.save()
                        preempted = True
                        self._log.warning(
                            "preemption checkpoint written at step %d; "
                            "exiting cleanly", self.step,
                        )
                        break
                    if (self.step // self.ckpt_every
                            != (self.step - k) // self.ckpt_every):
                        self.save()
                    if (self.publish_dir is not None
                            and self.step // self.publish_every
                            != (self.step - k) // self.publish_every):
                        self.publish()
        except BaseException:
            # async writes still get their durability chance, but a
            # flush failure must NOT replace the loop's primary failure
            # (a FloatingPointError/StallError caller handler has to see
            # its exception type), and a wedged writer must not convert
            # it into an indefinite hang — bounded wait, log, propagate
            try:
                if not self.flush_checkpoints(
                        timeout=self._EXC_FLUSH_TIMEOUT_S):
                    self._log.error(
                        "async checkpoint flush still pending after %.0fs "
                        "while a training failure was propagating; "
                        "abandoning the write (checkpoint directory may "
                        "be stale)", self._EXC_FLUSH_TIMEOUT_S,
                    )
            except Exception:
                self._log.exception(
                    "async checkpoint flush failed while a training "
                    "failure was already propagating"
                )
            raise
        finally:
            # the hook must not outlive the loop run: a probe hitting a
            # finished (or crashed) loop should see "no train check",
            # not a stale ready/not-ready claim — and the same for the
            # step heartbeat, which would otherwise read as a stale
            # liveness source and 503 every later /healthz probe
            obs_server.unregister_readiness("train")
            obs_server.HEARTBEATS.clear("train")
            self._guard = None
            try:
                # non-blocking tail drain: publish whatever settled. A
                # BLOCKING flush here could hang forever on the one exit
                # path that matters most (a watchdog stall = a device
                # value that never becomes ready); the clean-exit flush
                # below gets the rest
                numerics_pub.publish(self.step, None)
            except Exception:
                self._log.exception(
                    "numerics publisher drain failed on loop exit"
                )
        # async writes become durable before control leaves the loop — on
        # the preemption path this runs inside the grace window, and a
        # flush error DOES raise here: returning {'preempted': True}
        # over a failed boundary write would claim durability it lacks
        self.flush_checkpoints()
        # clean exit: the device chain has settled (the loop's last step
        # completed), so the blocking numerics drain is safe here and the
        # final steps' drift evidence reaches the registry
        numerics_pub.flush()
        return {
            "steps": steps_run,
            "step": self.step,
            "preempted": preempted,
            **self.counters.summary(),
        }
