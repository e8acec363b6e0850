"""Per-layer metrics of the runtime: the garbage collector's pauses in
the measured window, and the window's late steps beside them.

The program records every collection of the process from
``runtime.initialize()`` on (``tpu_syncbn.obs.tracing.watch_collector``),
tracer or not, on the clock of ``record.clock``; while a collection
lasts no thread of the process runs Python, the loader's workers among
them. The readers here look at the window of the traced run, which is
the same loop with the profiler still off, not at the traced slice after
it: a full collection comes a few times in a window and almost never in
a slice. A program without ``collector_pauses`` (an older commit) reads
as nothing and the three metrics that need the record are left out of
the line; a record that is there and empty reads 0. ``late_steps`` needs
the benchmark's own ``observe_loss`` spans alone.
"""

import statistics

# a late step: longer than the window's median step by more than both.
# RetinaNet's single-step 95th percentile sits 0.3 ms over its median and
# the host's clock is good to 0.5 ms; Ouro's late steps were 28 ms on 774
LATE_MS = 2.0
LATE_SHARE = 0.02
# a pause shorter than this explains no late step
EXPLAINS_MS = 1.0
# the window's edges are completion times, read just after the span ends
EDGE_S = 1e-3


def window_pauses(run: dict):
    """The program's ``(t0_s, t1_s, generation, collected)`` records
    that touch the window, oldest first; None without the record."""
    from tpu_syncbn.obs import tracing

    read = getattr(tracing, "collector_pauses", None)
    if read is None:
        return None
    lo, hi = run["loop"]["window"]
    return [p for p in read() if p[1] > lo and p[0] < hi]


def ms_per_step(run: dict):
    """Summed duration of the window's pauses, every generation, clipped
    at the window's edges, over the window's steps."""
    pauses = window_pauses(run)
    if pauses is None:
        return None
    lo, hi = run["loop"]["window"]
    stood_still = sum(min(t1, hi) - max(t0, lo) for t0, t1, *_ in pauses)
    return 1e3 * stood_still / run["loop"]["steps"]


def max_ms(run: dict):
    """The longest single pause that began inside the window."""
    pauses = window_pauses(run)
    if pauses is None:
        return None
    lo = run["loop"]["window"][0]
    return 1e3 * max((t1 - t0 for t0, t1, *_ in pauses if t0 >= lo),
                     default=0.0)


def late_intervals(run: dict) -> list[tuple[float, float]]:
    """The window's single-step intervals ``(t0_s, t1_s)``, between the
    ends of consecutive ``observe_loss`` spans that end inside it, that
    are late by the rule above."""
    lo, hi = run["loop"]["window"]
    ends = [t1 for name, _, t1 in run["spans"].spans
            if name == "observe_loss" and lo - EDGE_S <= t1 <= hi + EDGE_S]
    steps = list(zip(ends, ends[1:]))
    if not steps:
        return []
    median = statistics.median(b - a for a, b in steps)
    over = max(LATE_MS / 1e3, LATE_SHARE * median)
    return [(a, b) for a, b in steps if b - a > median + over]


def late_steps(run: dict):
    return len(late_intervals(run))


def late_steps_outside(run: dict):
    """The late steps whose interval overlaps no recorded pause of
    ``EXPLAINS_MS`` or more: what the collector does not explain."""
    pauses = window_pauses(run)
    if pauses is None:
        return None
    long = [(t0, t1) for t0, t1, *_ in pauses
            if t1 - t0 >= EXPLAINS_MS / 1e3]
    return sum(1 for a, b in late_intervals(run)
               if not any(t0 < b and t1 > a for t0, t1 in long))
