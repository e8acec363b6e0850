"""The training loop that is measured.

Steps are dispatched back to back; the completion of step i is observed
by fetching its loss (a host ``float``, which cannot exist before the
step has run) once step i+2 has been dispatched. With that lag of two
the device always has work queued, and host and device are never made
to take turns: it is what a user who logs the loss sees.

The window opens on the observed completion of the last warm-up step
and closes on the fetched loss of the last step dispatched inside it.

* ``img_s_chip``: images of the steps in the window / window seconds /
  chips: all the work over all the time.
* ``step_ms_p95``: the 95th percentile, over every run of ``span_steps``
  consecutive steps in the window, of that run's time per step. The
  host's clock is good to some half a millisecond, so a single step of
  25-50 ms is not timed by itself: ``span_steps`` is set in the
  workload's file so that a run of steps spans 250 ms or more. The
  single-step intervals' median and 95th percentile are printed as
  observations.

A workload's file lists which of the two it reports (``end_to_end``):
where the program's loader threads set the pace, the tail swings by more
between runs of the same code than any bound admits, and it stays an
observation on the earlier line.

A traced run measures the same window and then keeps the same loop
going for a few steps more with the profiler on, so that the capture
sees the steady loop and its cost stays outside the window.
"""

from __future__ import annotations

import collections
import math
import statistics

from chipbench.record import clock

END_TO_END = {"img_s_chip": "img/s/chip", "step_ms_p95": "ms"}
LAG = 2
TRACE_RAMP_STEPS = 4  # executions left out after the profiler starts


def percentile(values: list[float], q: float) -> float:
    """Linear-interpolated percentile (numpy's default rule)."""
    s = sorted(values)
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def run(step, batches, *, seconds: float, wl: dict, spans, tracer=None,
        on_open=lambda: None) -> dict:
    """``step(batch)`` dispatches one training step and returns its
    loss, still on the device. ``on_open`` is called once, when the
    window opens (the end of set-up)."""
    pending: collections.deque = collections.deque()
    done: list[tuple[float, float]] = []  # (completion time, loss) by step

    def dispatch_one():
        with spans.span("input_wait"):
            batch = next(batches)
        with spans.span("dispatch"):
            pending.append(step(batch))

    def observe_one():
        loss = pending.popleft()
        with spans.span("observe_loss"):
            value = float(loss)
        done.append((clock(), value))

    warmup = wl["warmup_steps"]
    t_open = None
    while t_open is None or clock() - t_open < seconds:
        dispatch_one()
        if len(pending) > LAG:
            observe_one()
        if t_open is None and len(done) >= warmup:
            t_open = done[warmup - 1][0]
            on_open()
    while pending:
        observe_one()
    t_close = done[-1][0]
    in_window = len(done) - warmup
    if in_window < 1:
        raise RuntimeError("no step completed inside the window")

    traced_from = len(done)  # the device is idle: every later step is traced
    if tracer is not None and tracer.start():
        for _ in range(wl["trace_steps"] + TRACE_RAMP_STEPS):
            dispatch_one()
            if len(pending) > LAG:
                observe_one()
        while pending:
            observe_one()
        tracer.stop()

    times = [t for t, _ in done[warmup - 1:warmup + in_window]]
    losses = [v for _, v in done[warmup:warmup + in_window]]
    single = [b - a for a, b in zip(times, times[1:])]
    g = min(wl["span_steps"], in_window)
    spanned = [(b - a) / g for a, b in zip(times, times[g:])]
    images = in_window * wl["per_chip_batch"] * wl["chips"]
    return {
        "window": (t_open, t_close),
        "window_s": t_close - t_open,
        "steps": in_window,
        "traced_completions": [t for t, _ in done[traced_from:]],
        "all_losses": [v for _, v in done],
        "metrics": {
            "img_s_chip": images / (t_close - t_open) / wl["chips"],
            "step_ms_p95": 1e3 * percentile(spanned, 95),
        },
        "observations": {
            "steps_in_window": in_window,
            "span_steps": g,
            "span_samples": len(spanned),
            "span_ms_median": 1e3 * statistics.median(spanned),
            "step_ms_median": 1e3 * statistics.median(single),
            "step_ms_p95_single": 1e3 * percentile(single, 95),
            "step_ms_max": 1e3 * max(single),
            "loss_open": losses[0],
            "loss_close": losses[-1],
        },
    }
