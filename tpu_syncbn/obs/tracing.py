"""Nestable wall-clock spans in Chrome trace-event format.

A :class:`Tracer` records *complete* events (``ph: "X"``) with
microsecond timestamps and durations; :meth:`Tracer.save` writes the
``{"traceEvents": [...]}`` JSON object that ``chrome://tracing`` and
Perfetto (https://ui.perfetto.dev) open directly
(docs/OBSERVABILITY.md has the how-to).

Span identity is the correlation currency: every span gets a
process-unique integer id, carried in the event's ``args.span_id`` (and
``args.parent_id`` for nesting). The resilience layer stamps the same id
into watchdog stall dumps and divergence-restore log lines
(:func:`latest_open_span_id`), so a RESILIENCE event log and a Perfetto
timeline can be joined on it.

Two ways on. :func:`install` makes a tracer the process tracer until
:func:`uninstall` (``ResilientLoop``, the flight recorder). With none
installed, spans record for as long as a ``jax.profiler`` capture runs
in this process (:func:`capture_active`): the first span site that sees
the capture makes a :class:`RingTracer`, the *capture tracer*, which
stays readable through :func:`last_capture` after the capture ends and
is replaced when the next capture starts. A device profile of this
program — ``chipbench``'s traced run, ``POST /profilez``,
``utils.profiler_trace`` — so comes with the host's own account of the
same seconds, on the clock (``time.perf_counter``) that the profile's
reduction aligns with the device trace.

Like telemetry, the disabled path is near-free: with no tracer installed
and no capture running, the module-level :func:`span` returns a shared
``nullcontext`` after two attribute tests — no clock read, no lock,
nothing recorded or kept (the call's own keyword dict is all it makes).

A span records its wall time and its thread's CPU time (``args.cpu_us``,
from ``time.thread_time``): a span whose wall time is far above its CPU
time was blocked (the GIL, a queue, the runtime); one whose two times
agree was computing.

One record is NOT behind that switch: the garbage collector's pauses
(:func:`watch_collector`, which ``runtime.initialize()`` calls). A
collection holds the interpreter lock, so no thread of the process runs
Python while it lasts, a full one for a tenth of a second a few times
in twenty: a span that records only while a capture of a second runs
would almost never hold one. The hook costs two clock reads and a
``deque.append`` a collection; :func:`collector_pauses` returns what it
kept, on the spans' clock, and :meth:`Tracer.save` lays it into the file
as a track of its own.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Any

_NULL = contextlib.nullcontext()


class Tracer:
    """Collects Chrome trace events in memory; thread-safe (each thread
    keeps its own span stack, event append is locked)."""

    def __init__(self):
        #: ``time.perf_counter()`` at construction: every event's ``ts``
        #: is microseconds since then (:meth:`spans` adds it back)
        self.t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._tls = threading.local()
        # insertion-ordered map of currently-open span ids → name; the
        # newest entry is what a watchdog thread should correlate with
        self._open: dict[int, str] = {}
        self._next_id = 1
        self.events: list[dict] = []

    # -- internals --------------------------------------------------------

    def _now_us(self) -> float:
        return (time.perf_counter() - self.t0) * 1e6

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _emit(self, event: dict) -> None:
        with self._lock:
            self.events.append(event)

    # -- recording --------------------------------------------------------

    def begin(self, name: str, **args) -> tuple:
        """Open a span on this thread and return its token for
        :meth:`end`. For a span that cannot be a ``with`` block (a
        generator that must close it before its ``yield``); everything
        else uses :meth:`span`. ``token[0]`` is the span id."""
        st = self._stack()
        parent = st[-1] if st else None
        with self._lock:
            sid = self._next_id
            self._next_id += 1
            self._open[sid] = name
        st.append(sid)
        # the CPU clock is read inside the wall clock's interval, so a
        # span's CPU time never exceeds its wall time
        t0 = time.perf_counter()
        return sid, name, parent, args, time.thread_time(), t0

    def end(self, token: tuple, **more) -> None:
        """Close the span ``token`` opened on this thread and record its
        complete event; ``more`` joins the ``args`` given at
        :meth:`begin` (what is known only at the end)."""
        cpu1 = time.thread_time()
        t1 = time.perf_counter()
        sid, name, parent, args, cpu0, t0 = token
        st = self._stack()
        if st and st[-1] == sid:
            st.pop()
        ev_args: dict = {"span_id": sid}
        if parent is not None:
            ev_args["parent_id"] = parent
        ev_args["cpu_us"] = round((cpu1 - cpu0) * 1e6, 3)
        ev_args.update(args)
        ev_args.update(more)
        event = {
            "name": name,
            "ph": "X",
            "ts": round((t0 - self.t0) * 1e6, 3),
            "dur": round((t1 - t0) * 1e6, 3),
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "cat": "tpu_syncbn",
            "args": ev_args,
        }
        with self._lock:
            self._open.pop(sid, None)
            self.events.append(event)

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Record a complete event around the block; yields the span id.
        Nest freely (including across threads — each thread nests its own
        stack). ``args`` must be JSON-serializable. Besides them the
        event carries ``span_id``, ``parent_id`` (the span open on this
        thread when this one began) and ``cpu_us``, this thread's CPU
        time inside the block."""
        token = self.begin(name, **args)
        try:
            yield token[0]
        finally:
            self.end(token)

    def instant(self, name: str, **args) -> None:
        """Record an instant event (``ph: "i"``) — a point-in-time marker
        (watchdog stall, divergence restore) on the timeline."""
        self._emit({
            "name": name,
            "ph": "i",
            "s": "t",  # thread-scoped marker
            "ts": round(self._now_us(), 3),
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "cat": "tpu_syncbn",
            "args": dict(args),
        })

    def _flow(self, ph: str, name: str, flow_id: int, extra: dict,
              args: dict) -> None:
        event = {
            "name": name,
            "ph": ph,
            "id": int(flow_id),
            "ts": round(self._now_us(), 3),
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "cat": "tpu_syncbn",
            "args": dict(args),
        }
        event.update(extra)
        self._emit(event)

    def flow_start(self, name: str, flow_id: int, **args) -> None:
        """Open a flow arrow (``ph: "s"``): Perfetto draws an arrow from
        the slice enclosing this timestamp on this thread to wherever the
        matching :meth:`flow_end` lands (same ``name`` + ``flow_id``).
        The serving stack uses request ids as flow ids, so a request's
        enqueue span and the batch span that eventually answered it are
        visually linked in the trace."""
        self._flow("s", name, flow_id, {}, args)

    def flow_end(self, name: str, flow_id: int, **args) -> None:
        """Close a flow arrow (``ph: "f"``, ``bp: "e"`` — bind to the
        enclosing slice, so the arrow terminates at the span currently
        open on this thread rather than at a bare point)."""
        self._flow("f", name, flow_id, {"bp": "e"}, args)

    def recent_events(self, limit: int | None = None) -> list[dict]:
        """The newest ``limit`` recorded events (all when ``None``) —
        the flight recorder's span-ring read: a self-contained,
        Perfetto-loadable slice of recent activity without writing a
        trace file."""
        with self._lock:
            events = list(self.events)
        if limit is not None and len(events) > limit:
            events = events[-limit:]
        return events

    def spans(self, name: str | None = None) -> list[tuple]:
        """The recorded complete events as ``(name, t0_s, t1_s, cpu_s,
        tid, args)``, oldest first, ``t0_s``/``t1_s`` in absolute
        ``time.perf_counter()`` seconds — the clock and the form of a
        host span that ``chipbench/trace_reduce.align`` lays onto a
        device trace. ``name`` keeps only the spans of that name."""
        with self._lock:
            events = list(self.events)
        out = []
        for ev in events:
            if ev["ph"] != "X" or (name is not None and ev["name"] != name):
                continue
            t0 = self.t0 + ev["ts"] / 1e6
            out.append((ev["name"], t0, t0 + ev["dur"] / 1e6,
                        ev["args"].get("cpu_us", 0.0) / 1e6, ev["tid"],
                        ev["args"]))
        return out

    # -- queries ----------------------------------------------------------

    def current_span_id(self) -> int | None:
        """The innermost open span on THIS thread, or None."""
        st = getattr(self._tls, "stack", None)
        return st[-1] if st else None

    def latest_open_span_id(self) -> int | None:
        """The most recently opened, still-open span in ANY thread — what
        a watchdog/monitor thread tags its diagnostics with (its own
        thread-local stack is empty by construction)."""
        with self._lock:
            if not self._open:
                return None
            return next(reversed(self._open))

    # -- output -----------------------------------------------------------

    def save(self, path: str) -> str:
        """Write the Chrome trace JSON object. Adds process metadata so
        Perfetto labels the track with the host index when the
        distributed runtime can answer (never initializes a backend to
        ask), and the collector's pauses that began in this tracer's
        lifetime as ``gc`` slices on a track named ``collector``
        (:func:`collector_pauses`): a slice there stands over the spans
        of every thread that the collection stopped. The file alone
        holds them; :attr:`events`, :meth:`spans` and
        :meth:`recent_events` are the span sites' record."""
        meta: list[dict] = []
        try:
            # only ask jax for the host index if a backend is ALREADY
            # live: jax.process_index() would otherwise initialize one,
            # and a trace writer must never touch a possibly-hung plugin
            from jax._src import xla_bridge

            if xla_bridge.backends_are_initialized():
                import jax

                host = int(jax.process_index())
                meta.append({
                    "name": "process_name", "ph": "M", "pid": os.getpid(),
                    "args": {"name": f"tpu_syncbn host {host}"},
                })
        except Exception:
            pass
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        with self._lock:
            events = meta + list(self.events)
        events += self._collector_track()
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
        return path

    def _collector_track(self) -> list[dict]:
        pauses = collector_pauses(self.t0)
        if not pauses:
            return []
        pid = os.getpid()
        track: list[dict] = [{
            "name": "thread_name", "ph": "M", "pid": pid,
            "tid": COLLECTOR_TID, "args": {"name": "collector"},
        }]
        for t0, t1, generation, collected in pauses:
            track.append({
                "name": "gc",
                "ph": "X",
                "ts": round((t0 - self.t0) * 1e6, 3),
                "dur": round((t1 - t0) * 1e6, 3),
                "pid": pid,
                "tid": COLLECTOR_TID,
                "cat": "tpu_syncbn",
                "args": {"generation": generation, "collected": collected},
            })
        return track


class RingTracer(Tracer):
    """A :class:`Tracer` whose event store is a bounded ring: the newest
    ``capacity`` events survive, older ones fall off. This is the
    always-on form the flight recorder installs
    (:mod:`tpu_syncbn.obs.flightrec`) — span recording with memory
    bounded by construction, so it can run for days and still hold the
    seconds *before* an incident. :meth:`Tracer.save` and
    :meth:`Tracer.recent_events` work unchanged (they copy the ring)."""

    def __init__(self, capacity: int = 2048, **kwargs):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        super().__init__(**kwargs)
        self.capacity = int(capacity)
        # deque.append matches the list API every recording path uses;
        # maxlen makes eviction O(1) and allocation-free
        self.events = deque(maxlen=self.capacity)  # type: ignore[assignment]


# ---------------------------------------------------------------------------
# the process tracer: installed, or made for a running profiler capture


_installed: Tracer | None = None
_install_lock = threading.Lock()

#: events the capture tracer keeps: some ten spans a step, so minutes
CAPTURE_CAPACITY = 16_384
_capture: RingTracer | None = None  # the newest capture's tracer
_capture_session = None  # the profiler session it was made for, until
#                          that session has been seen to have ended
_profile_state = None  # jax's, once its profiler module is imported


def install(tracer: Tracer | None = None) -> Tracer:
    """Install ``tracer`` (or a fresh one) as the process tracer that the
    module-level :func:`span`/:func:`instant` record into. Returns it."""
    global _installed
    with _install_lock:
        if tracer is None:
            tracer = Tracer()
        _installed = tracer
        return tracer


def uninstall() -> Tracer | None:
    """Remove and return the installed tracer (its events stay intact)."""
    global _installed
    with _install_lock:
        t, _installed = _installed, None
        return t


def _profile_session():
    """The ``jax.profiler`` capture running in this process, or None.
    jax 0.9.0 has no public query: this is the one place that reads
    ``jax._src.profiler._profile_state`` (tests/test_obs.py goes red
    when it moves). Never imports jax: a process that has not imported
    its profiler has no capture."""
    global _profile_state
    state = _profile_state
    if state is None:
        mod = sys.modules.get("jax._src.profiler")
        state = _profile_state = getattr(mod, "_profile_state", None)
    return getattr(state, "profile_session", None)


def capture_active() -> bool:
    """Whether a ``jax.profiler`` capture is running in this process
    (``start_trace``/``stop_trace``, ``utils.profiler_trace``,
    ``POST /profilez``) — seen from any thread."""
    return _profile_session() is not None


def last_capture() -> RingTracer | None:
    """The tracer of the newest profiler capture during which a span
    site ran with no tracer installed: still recording while that
    capture runs, readable after it (``.spans()``, ``.save(path)``),
    replaced when a span site sees the next capture. None before the
    first."""
    return _capture


def get() -> Tracer | None:
    """The tracer spans record into now: the installed one, else the
    capture tracer while a profiler capture runs, else None."""
    global _capture, _capture_session
    t = _installed
    if t is not None:
        return t
    session = _profile_session()
    if session is _capture_session:
        return _capture if session is not None else None
    # a capture started or ended since the last span site: once each
    with _install_lock:
        session = _profile_session()
        if session is not _capture_session:
            # the ring first, the session last: a thread on the
            # lock-free path above that sees the new session must find
            # its ring already there. Holding the running session keeps
            # its identity from being handed to the next one; it is let
            # go of when it has ended
            if session is not None:
                _capture = RingTracer(CAPTURE_CAPACITY)
            _capture_session = session
    return _capture if session is not None else None


def span(name: str, **args):
    """Context manager: a span on the process tracer (:func:`get`), or a
    shared no-op context when tracing is off."""
    t = get()
    if t is None:
        return _NULL
    return t.span(name, **args)


def instant(name: str, **args) -> None:
    t = get()
    if t is not None:
        t.instant(name, **args)


def current_span_id() -> int | None:
    t = get()
    return t.current_span_id() if t is not None else None


def latest_open_span_id() -> int | None:
    t = get()
    return t.latest_open_span_id() if t is not None else None


# ---------------------------------------------------------------------------
# the collector's pauses: recorded from watch_collector() on, tracer or not


#: pauses kept: about one collection a step (nearly all of generation 0),
#: so more than a benchmark window of them and hours of the full ones
PAUSE_CAPACITY = 8192
#: the ``tid`` of the ``collector`` track in a saved file (no thread's:
#: ``threading.get_ident()`` is an address)
COLLECTOR_TID = 1
_pauses: deque = deque(maxlen=PAUSE_CAPACITY)
_pause_t0 = 0.0  # the clock at the ``start`` of the running collection


def _on_collection(phase: str, info: dict) -> None:
    """The ``gc.callbacks`` hook. Collections do not nest (the
    interpreter holds a ``collecting`` flag), so one slot holds the
    start; the tuple is all it makes."""
    global _pause_t0
    if phase == "start":
        _pause_t0 = time.perf_counter()
    else:
        _pauses.append((_pause_t0, time.perf_counter(),
                        info["generation"], info["collected"]))


def watch_collector() -> None:
    """Record every garbage collection of this process from now on
    (idempotent). Not behind the capture switch: see the module's
    docstring."""
    with _install_lock:
        if _on_collection not in gc.callbacks:
            gc.callbacks.append(_on_collection)


def collector_pauses(since: float = 0.0) -> list[tuple]:
    """The recorded collections that began at or after ``since`` as
    ``(t0_s, t1_s, generation, collected)``, oldest first, in absolute
    ``time.perf_counter()`` seconds: the clock of :meth:`Tracer.spans`.
    No thread of the process ran Python from ``t0_s`` to ``t1_s``.
    The newest :data:`PAUSE_CAPACITY` are kept; empty until
    :func:`watch_collector` has run."""
    # one C call copies the ring: the hook cannot append in the middle of
    # it (a collection starts between two bytecodes), and takes no lock
    return [p for p in tuple(_pauses) if p[0] >= since]


# ---------------------------------------------------------------------------
# loading / validation


def load_trace(path: str) -> list[dict]:
    """Parse a Chrome trace file (object-with-``traceEvents`` or bare
    array form) and return its event list."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict):
        events = doc.get("traceEvents")
        if not isinstance(events, list):
            raise ValueError(
                f"{path!r} is JSON but has no traceEvents list"
            )
        return events
    if isinstance(doc, list):
        return doc
    raise ValueError(f"{path!r} is not a Chrome trace (dict or list)")


def validate_trace(events: list) -> list[dict]:
    """Minimal Chrome trace-event validation: every event is a dict with
    a name, a phase, and a numeric ``ts``. Returns the events; raises
    ``ValueError`` on drift."""
    if not isinstance(events, list):
        raise ValueError("trace events must be a list")
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise ValueError(f"trace event {i} is not a dict")
        if not isinstance(ev.get("name"), str):
            raise ValueError(f"trace event {i} has no name")
        if ev.get("ph") not in ("X", "B", "E", "i", "I", "M", "C",
                                "s", "t", "f"):
            raise ValueError(f"trace event {i} has unknown phase {ev.get('ph')!r}")
        if ev["ph"] != "M" and not isinstance(ev.get("ts"), (int, float)):
            raise ValueError(f"trace event {i} has no numeric ts")
        if ev["ph"] == "X" and not isinstance(ev.get("dur"), (int, float)):
            raise ValueError(f"complete event {i} has no numeric dur")
        if ev["ph"] in ("s", "t", "f") and not isinstance(
                ev.get("id"), (int, str)):
            raise ValueError(f"flow event {i} has no id")
    return events
