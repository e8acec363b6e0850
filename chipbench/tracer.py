"""A profiler capture that cannot take the run down.

The profiler is on only for a short slice of steps, without the Python
tracer and, on the TPU, without the host tracer: with it on at any
level the runtime's own two host threads write a million events a
second (5 steps: 40 MB and 8 s to stop; 28 steps: 393 MB, the loop
slowed fivefold and a minute and a half to stop and read). The device's
planes do not need it. Every step from ``start_trace`` to the last
reduction is guarded: a failure is printed to stderr with its traceback
and costs the metrics that needed the trace, nothing else.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import traceback

from chipbench import trace_reduce


def _guarded(what: str, fn, *args):
    try:
        return fn(*args)
    except Exception:  # the boundary that must keep the run alive
        print(f"chipbench: trace {what} failed", file=sys.stderr)
        traceback.print_exc()
        return None


class Tracer:
    def __init__(self):
        self.dir: str | None = None
        self.running = False

    def start(self) -> bool:
        def go():
            import jax

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            # the CPU backend's operations are host events: the CPU
            # rehearsal needs the host tracer to see any
            options.host_tracer_level = int(jax.default_backend() == "cpu")
            self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(self.dir, profiler_options=options)
            return True

        self.running = bool(_guarded("start", go))
        return self.running

    def stop(self) -> None:
        if self.running:
            import jax

            self.running = False
            _guarded("stop", jax.profiler.stop_trace)

    def reduce(self, host_spans: list, completions: list) -> dict | None:
        """The reduced trace, or None; removes the capture's files.
        ``host_spans`` and ``completions`` are on the host's clock (see
        ``trace_reduce.align``)."""
        if self.dir is None:
            return None

        def go():
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                raise FileNotFoundError(f"no .xplane.pb under {self.dir}")
            print(f"chipbench: trace file {os.path.getsize(files[0])} bytes",
                  file=sys.stderr)
            trace = trace_reduce.load(files[0])
            trace["host_spans"] = trace_reduce.align(trace, host_spans,
                                                     completions)
            return trace_reduce.reduce(trace)

        try:
            return _guarded("reduction", go)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None
