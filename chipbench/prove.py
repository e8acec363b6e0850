"""Prove a cell the way the driver will check it. Never touches JAX
itself: every run is a fresh process of the benchmark's command, so
each gets the chip to itself.

    python3 chipbench/prove.py --workload <name> [--sets 2] [--runs 6] [--seconds S]

For each set: ``--trace 0`` with the first seed (in the first set of a
fresh checkout this is the run that compiles), ``--trace 1`` with the
same seed, then ``--trace 0`` with the remaining seeds; both sets use
the same seeds. Every run must exit 0 and end in one JSON line with
exactly the contract's keys. Prints, per end-to-end metric, each set's
median and spread (the distance between the quartiles that
``statistics.quantiles(values, n=4)`` gives, as a share of the median)
and the bound the contract's rule of five times the wider spread would
give; ``setup_s`` leaves out each set's first run. The whole record goes
to ``chiprun_out/prove-<workload>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
SEEDS = [2147483659, 1, 20260928, 3141592653, 77, 1234567891,
         4000000007, 42, 987654321, 2718281828]


def one_run(command: list, workload: str, seed: int, seconds: float,
            trace: int) -> dict:
    t0 = time.perf_counter()
    proc = subprocess.run(
        command + ["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True, timeout=1500,
    )
    wall = time.perf_counter() - t0
    lines = proc.stdout.strip().splitlines()
    record = {"seed": seed, "trace": trace, "rc": proc.returncode,
              "wall_s": wall, "stderr_tail": proc.stderr[-1500:]}
    if proc.returncode != 0 or not lines:
        raise SystemExit(f"run failed: {record}\n{proc.stdout[-3000:]}")
    last = json.loads(lines[-1])
    allowed = KEYS | ({"breakdown"} if trace else set())
    if not KEYS <= set(last) <= allowed:
        raise SystemExit(f"last line has keys {sorted(last)}")
    want = DEVICE_KEYS | ({"busy_s", "window_s"} if trace else set())
    if set(last["device"]) != want:
        raise SystemExit(f"device has keys {sorted(last['device'])}")
    if not last["correct"]:
        raise SystemExit(f"not correct: {lines[-2:]}")
    record["last"] = last
    record["observations"] = [json.loads(x) for x in lines[:-1]
                              if x.startswith("{")]
    print(json.dumps({k: record[k] for k in ("seed", "trace", "wall_s")}
                     | {"metrics": {k: v["value"] for k, v
                                    in last["metrics"].items()},
                        "device": last["device"]}), flush=True)
    return record


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    seconds = args.seconds or benchmark["run_seconds"]
    seeds = SEEDS[:args.runs]

    def run(seed: int, trace: int) -> dict:
        return one_run(benchmark["command"], args.workload, seed, seconds,
                       trace)

    sets = [[run(seeds[0], 0), run(seeds[0], 1)]
            + [run(s, 0) for s in seeds[1:]] for _ in range(args.sets)]
    summary = {}
    for name in sets[0][0]["last"]["metrics"]:
        per_set = []
        for runs in sets:
            values = [r["last"]["metrics"][name]["value"]
                      for r in runs if r["trace"] == 0]
            if name == "setup_s":
                values = values[1:]
            per_set.append({"median": statistics.median(values),
                            "spread": spread(values) if len(values) > 1
                            else None, "values": values})
        widest = max((s["spread"] for s in per_set if s["spread"] is not None),
                     default=None)
        summary[name] = {"sets": per_set, "widest_spread": widest,
                         "bound_by_rule": None if widest is None
                         else max(0.01, 5 * widest)}
    out = {"workload": args.workload, "seconds": seconds, "seeds": seeds,
           "summary": summary, "sets": sets}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"prove-{args.workload}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"summary": {
        k: {"medians": [s["median"] for s in v["sets"]],
            "spreads": [s["spread"] for s in v["sets"]],
            "bound_by_rule": v["bound_by_rule"]}
        for k, v in summary.items()}}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
