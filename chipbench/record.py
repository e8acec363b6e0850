"""The benchmark's own spans and counters, on the host's clock.

Spans are recorded from the benchmark's files, around its calls into
each layer of the program; nothing here reads the program's own
telemetry. They are kept in memory.
"""

from __future__ import annotations

import contextlib
import time

clock = time.perf_counter

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Spans:
    def __init__(self):
        self.spans: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = clock()
        try:
            yield
        finally:
            self.spans.append((name, t0, clock()))

    def durations(self, name: str, window: tuple[float, float]) -> list[float]:
        """Seconds of every ``name`` span that started inside ``window``."""
        lo, hi = window
        return [t1 - t0 for n, t0, t1 in self.spans
                if n == name and lo <= t0 <= hi]


class Counters:
    """``jax.monitoring`` events with the time each arrived: persistent
    cache hits and misses (a copy of ``chip_smoke.cache_events``), and
    every backend compile or cache load of a program (jax records the
    same duration event around both)."""

    def __init__(self):
        self.events: list[tuple[str, float]] = []

    def _on_event(self, event, **_):
        if event in (CACHE_MISS_EVENT, CACHE_HIT_EVENT):
            self.events.append((event, clock()))

    def _on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.events.append((event, clock()))

    def __enter__(self):
        import jax

        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_listener(self._on_event)
        jax.monitoring.unregister_event_duration_listener(self._on_duration)

    def count(self, event: str, window: tuple[float, float] | None = None) -> int:
        return sum(1 for e, t in self.events if e == event
                   and (window is None or window[0] <= t <= window[1]))
