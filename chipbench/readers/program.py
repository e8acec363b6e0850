"""Per-layer metrics from the program's own spans.

The program (``tpu_syncbn``) names its own work: its span sites record
into ``tpu_syncbn.obs.tracing`` for as long as a ``jax.profiler``
capture runs in the process, which in a traced run is the slice of
steps after the window (``loops/train.py``). Nothing here switches
anything on; ``tracing.last_capture()`` is that capture's tracer, and
its spans are on the clock of ``record.clock``. The span sites:
``loader.build`` and, inside it, ``loader.collate`` on the loader's
worker threads and ``loader.fetch`` on the consumer's
(``data/loader.py``), ``data_wait`` and ``h2d`` in ``device_prefetch``,
``train_step`` in ``DataParallel.train_step``. A program without these
(an older commit), a run without a capture and a span that was never
recorded all read as nothing: the metric is left out of the line.
"""

# the traced slice's first executions are left out: starting the profiler
# stalls the host
from chipbench.loops.train import TRACE_RAMP_STEPS as RAMP


def spans(run: dict, span: str) -> list:
    """The capture's ``span`` spans ``(name, t0_s, t1_s, cpu_s, tid,
    args)`` that start after the ramp: after the fourth traced
    completion when there are more than eight, else after the first;
    all of the capture's when none starts after the cut."""
    from tpu_syncbn.obs import tracing

    completions = run["loop"]["traced_completions"]
    last_capture = getattr(tracing, "last_capture", None)
    capture = last_capture() if last_capture and completions else None
    if capture is None:
        return []
    found = capture.spans(span)
    cut = completions[RAMP - 1] if len(completions) > 2 * RAMP else completions[0]
    return [s for s in found if s[1] > cut] or found


def mean_ms(run: dict, *, span: str, clock: str = "wall"):
    """Mean duration of the program's ``span`` spans in milliseconds:
    on the wall's clock, or the CPU time of the span's own thread."""
    found = spans(run, span)
    if not found:
        return None
    if clock == "cpu":
        return 1e3 * sum(s[3] for s in found) / len(found)
    return 1e3 * sum(s[2] - s[1] for s in found) / len(found)


def mean_arg(run: dict, *, span: str, arg: str):
    """Mean of the numeric ``args`` field ``arg`` over the same spans."""
    values = [s[5][arg] for s in spans(run, span)
              if isinstance(s[5].get(arg), (int, float))]
    return sum(values) / len(values) if values else None
