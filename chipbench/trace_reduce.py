"""From a profiler trace to numbers.

``load`` turns an ``.xplane.pb`` into plain data with nothing but JAX
(``jax.profiler.ProfileData``); ``reduce`` turns that into the
quantities the per-layer readers and ``device.busy_s`` take. What the
v5e's trace looks like (seen on the chip before this was written):

* one plane per chip, ``/device:TPU:<n>``, with the lines ``XLA
  Modules`` (one event per execution of a compiled program), ``XLA
  Ops`` (one event per operation, about 3,900 a ResNet-50 step, named
  by their whole HLO text: ``%fusion.12 = bf16[...] fusion(...)``),
  ``Async XLA Ops`` (copy-start/done pairs that overlap compute) and
  ``Steps``;
* the host's threads as lines of ``/host:CPU``, when the host tracer is
  on. On the TPU it is off (see ``tracer.py``), so the benchmark's own
  spans are laid onto the trace's clock by ``align``.

Events are in nanoseconds from the start of the profile. Busy time is
the union of the ``XLA Ops`` intervals: the asynchronous copies run
beside the compute and are not counted again. The slice that is reduced
runs from the start of one execution of the step program to the start
of a later one, so it holds whole steps, their idle gaps included; the
first executions after the profiler starts are left out, because
starting it stalls the host.
"""

from __future__ import annotations

import collections

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# The CPU backend has no device plane: its executor threads stand in for
# one in the CPU rehearsal, so that the same reduction runs there.
CPU_OPS_LINE_PREFIX = "tf_XLAPjRtCpuClient"


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> dict:
    """``{"devices": {index: {"ops": [[name, start_ns, dur_ns], ...],
    "modules": [...]}}}``, each list sorted by start."""
    import jax

    profile = jax.profiler.ProfileData.from_file(path)
    devices: dict[int, dict] = {}
    cpu_ops: list = []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            index = int(plane.name.rsplit(":", 1)[1])
            dev = devices.setdefault(index, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = _events(line)
                elif line.name == MODULES_LINE:
                    dev["modules"] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if line.name.startswith(CPU_OPS_LINE_PREFIX):
                    cpu_ops += [e for e in _events(line) if e[2] > 0
                                and not e[0].startswith("Threadpool")]
    if not devices and cpu_ops:
        devices[0] = {"ops": sorted(cpu_ops, key=lambda e: e[1]),
                      "modules": []}
    return {"devices": devices}


def _events(line) -> list:
    return sorted(
        ([short_name(e.name), float(e.start_ns), float(e.duration_ns)]
         for e in line.events),
        key=lambda e: e[1],
    )


def busy_intervals(ops: list, lo: float, hi: float) -> list[tuple]:
    """Union of the operations' intervals, clipped to [lo, hi]."""
    merged: list[list[float]] = []
    for _, start, dur in ops:
        a, b = max(start, lo), min(start + dur, hi)
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def step_executions(dev: dict) -> list:
    """The executions of the step program: the module that ran most."""
    modules = dev["modules"]
    if not modules:
        return []
    name = collections.Counter(m[0] for m in modules).most_common(1)[0][0]
    return [m for m in modules if m[0] == name]


def align(trace: dict, host_spans: list, completions: list) -> list:
    """The benchmark's spans ``(name, t0, t1)``, seconds on the host's
    clock, as ``[name, start_ns, dur_ns]`` on the trace's clock.

    ``completions`` are the host times at which the loss of each traced
    step was fetched, in step order. A loss cannot be fetched before its
    step has ended on the device, and while the device is the slower
    side the fetch returns within a device-to-host copy of that end: so
    the smallest difference between a fetch and the end of the same
    step's execution in the trace is the offset between the two clocks,
    good to that copy's latency (some 0.1 ms). No step executions in the
    trace (the CPU rehearsal), or a count that does not match: no spans,
    and every idle gap is labelled ``other``."""
    devices = trace["devices"]
    runs = step_executions(devices[min(devices)]) if devices else []
    if not runs or len(runs) != len(completions):
        return []
    offset = min(t * 1e9 - (start + dur)
                 for t, (_, start, dur) in zip(completions, runs))
    lo = runs[0][1] + offset
    return [[name, t0 * 1e9 - offset, (t1 - t0) * 1e9]
            for name, t0, t1 in host_spans if t1 * 1e9 >= lo]


def step_slice(dev: dict, skip: int) -> tuple[float, float, int] | None:
    """(start, end, whole steps) of the slice to reduce on one device:
    from the start of execution ``skip`` of the step program (the
    module that ran most often) to the start of its last execution.
    Without module events (the CPU rehearsal) the operations' extent
    counts as one step."""
    starts = [m[1] for m in step_executions(dev)]
    if starts:
        if len(starts) - skip < 2:
            skip = 0
        if len(starts) - skip < 2:
            return None
        return starts[skip], starts[-1], len(starts) - skip - 1
    if dev["ops"]:
        first, last = dev["ops"][0], max(dev["ops"], key=lambda e: e[1] + e[2])
        return first[1], last[1] + last[2], 1
    return None


def label_gap(a: float, b: float, host_spans: list) -> str:
    """The benchmark span that covers most of the idle gap [a, b]."""
    best, best_overlap = "other", 0.0
    for name, start, dur in host_spans:
        overlap = min(b, start + dur) - max(a, start)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def reduce(trace: dict, *, skip_steps: int = 4, top: int = 10,
           gaps: int = 5) -> dict | None:
    """Busy and idle time per device over whole steps, the operations
    that took most time and the longest idle gaps on device 0. None when
    the trace holds no device operations."""
    per_device = {}
    for index, dev in sorted(trace["devices"].items()):
        bounds = step_slice(dev, skip_steps)
        if bounds is None:
            continue
        lo, hi, steps = bounds
        busy = busy_intervals(dev["ops"], lo, hi)
        per_device[index] = {
            "lo": lo, "hi": hi, "steps": steps, "intervals": busy,
            "busy_ns": sum(b - a for a, b in busy),
        }
    if not per_device or not any(d["busy_ns"] for d in per_device.values()):
        return None
    first = min(per_device)
    d0 = per_device[first]
    lo, hi = d0["lo"], d0["hi"]
    in_slice = [e for e in trace["devices"][first]["ops"]
                if lo <= e[1] < hi]
    by_op = collections.Counter()
    for name, _, dur in in_slice:
        by_op[name] += dur
    idle = [(b0, a1) for (_, b0), (a1, _) in
            zip(d0["intervals"], d0["intervals"][1:])]
    if d0["intervals"]:
        idle = ([(lo, d0["intervals"][0][0])] + idle
                + [(d0["intervals"][-1][1], hi)])
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:gaps]
    return {
        "window_s": (hi - lo) / 1e9,
        "steps": d0["steps"],
        "busy_s": sum(d["busy_ns"] for d in per_device.values())
        / len(per_device) / 1e9,
        "busy_s_device0": d0["busy_ns"] / 1e9,
        "devices": len(per_device),
        "allreduce_s_device0": sum(
            dur for name, _, dur in in_slice if name.startswith("all-reduce")
        ) / 1e9,
        "device_ops": [[n, ns / 1e9] for n, ns in by_op.most_common(top)],
        "idle_gaps": [
            [label_gap(a, b, trace.get("host_spans", [])), (b - a) / 1e9]
            for a, b in longest if b > a
        ],
    }
