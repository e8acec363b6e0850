"""The benchmark's one command.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by name:
``workloads/<name>.json`` names ``configs/<config>.json``; the
configuration's ``family`` names ``families/<family>.py``, the
workload's ``input.mode`` names ``inputs/<mode>.py`` and its ``loop``
names ``loops/<loop>.py``; every file in ``metrics/`` is a per-layer
metric whose ``reader`` is a function in ``readers/``. See README.md.

The last line of standard output is the result; earlier lines are
observations, one JSON object each. With ``--trace 0`` the metrics are
the loop's end-to-end ones and ``setup_s``; with ``--trace 1`` the
per-layer ones. Without a TPU holding the chips the cell asks for the
command fails and prints no result, unless ``JAX_PLATFORMS=cpu`` chose
the CPU by name: then it runs the ``rehearsal`` sizes of the two files
and says ``"platform": "cpu"``.
"""

from __future__ import annotations

import time

_PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import correct, record, tracer as tracing  # noqa: E402


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def rehearsal(sizes: dict) -> dict:
    """A workload's or configuration's file with its ``rehearsal`` sizes
    laid over it: what the CPU runs."""
    return {**sizes, **sizes["rehearsal"]}


def lookup(obj: dict, dotted: str):
    for key in dotted.split("."):
        obj = obj[key]
    return obj


def per_layer_metrics(run: dict) -> dict:
    """Every metric file that applies to this cell, read by its reader;
    a reader that finds nothing to read leaves its metric out."""
    out = {}
    for path in sorted(glob.glob(os.path.join(HERE, "metrics", "*.json"))):
        with open(path) as f:
            m = json.load(f)
        if run["wl"]["loop"] not in m["loops"]:
            continue
        if any(lookup(run["wl"], k) not in allowed
               for k, allowed in m.get("when", {}).items()):
            continue
        module, fn = m["reader"].rsplit(".", 1)
        reader = getattr(
            importlib.import_module("chipbench.readers." + module), fn)
        value = reader(run, **m.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_record(devices) -> dict:
    """The devices as JAX reports them. The peak is the allocator's peak
    of live buffers plus its peak of reserved program scratch: on the
    TPU ``peak_bytes_in_use`` leaves the compiled step's temporaries
    out (they are ``bytes_reserved``; free = limit - in use - reserved)."""
    import jax

    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": jax.device_count(), "memory_peak_bytes": max(peaks)}


def main(argv=None, *, t0: float | None = None) -> int:
    t0 = record.clock() if t0 is None else t0
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    phases = {}  # seconds since process start at the end of each set-up phase

    def mark(name: str) -> None:
        phases[name] = record.clock() - t0

    # the package's loggers default to stdout; stdout is the result channel
    os.environ.setdefault("TPU_SYNCBN_LOG_STREAM", "stderr")
    import jax
    import numpy as np

    from tpu_syncbn import parallel, runtime
    from tpu_syncbn.runtime import probe

    wl = load_json("workloads", args.workload + ".json")
    cfg = load_json("configs", wl["config"] + ".json")
    mark("imports")
    backend = probe.ensure_backend(wl["chips"])  # raises without the chips
    mark("backend")
    if backend.platform == "cpu":
        wl, cfg = rehearsal(wl), rehearsal(cfg)

    with record.Counters() as counters:
        runtime.initialize()  # compile cache: $JAX_COMPILATION_CACHE_DIR
        #                       or <checkout>/.jax_cache
        mesh = runtime.data_parallel_mesh(wl["chips"])
        family = importlib.import_module("chipbench.families." + cfg["family"])
        inputs = importlib.import_module("chipbench.inputs." + wl["input"]["mode"])
        loop = importlib.import_module("chipbench.loops." + wl["loop"])

        model_seed, input_seed = np.random.SeedSequence(args.seed).spawn(2)
        # the seed is an argument of the init program, not a constant in
        # it: another seed is the same program, so the cache still hits
        key = jax.random.key(int(model_seed.generate_state(1)[0] >> 1))
        model = family.build_model(cfg, key)
        mark("model")
        dp = parallel.DataParallel(
            model, family.optimizer(cfg, wl["per_chip_batch"] * wl["chips"]),
            family.loss_fn, mesh=mesh,
        )
        mark("trainer")
        batches, close_input = inputs.make(family, cfg, wl, dp, input_seed)
        try:
            first_batch = next(batches)
            mark("input")
            errors, first_loss = correct.first_step(dp, family, cfg,
                                                    first_batch, mark)
            stats_before = correct.running_stats(dp.rest)
            spans = record.Spans()
            tracer = tracing.Tracer() if args.trace else None
            result = loop.run(
                lambda batch: dp.train_step(batch).loss, batches,
                seconds=args.seconds, wl=wl, spans=spans,
                tracer=tracer,
                on_open=lambda: mark("window_open"),
            )
        finally:
            close_input()
        stats_after = correct.running_stats(dp.rest)

    checked = correct.verdict(errors, [first_loss] + result["all_losses"],
                              stats_before, stats_after)
    device = device_record(list(mesh.devices.flat))
    emit({"observations": result["observations"], "first_loss": first_loss,
          "check": checked, "window_s": result["window_s"],
          "setup_phases": phases,
          "traced_steps": len(result["traced_completions"]),
          "cache": {"hits": counters.count(record.CACHE_HIT_EVENT),
                    "misses": counters.count(record.CACHE_MISS_EVENT)},
          "memory_stats": mesh.devices.flat[0].memory_stats()})

    line = {"correct": checked["correct"], "attempted": result["steps"],
            "failed": checked["failed"]}
    if args.trace:
        trace = tracer.reduce(spans.spans, result["traced_completions"])
        run = {"wl": wl, "cfg": cfg, "family": family, "spans": spans,
               "counters": counters, "loop": result, "trace": trace,
               "device": device, "peaks": load_json("peaks.json")}
        line["metrics"] = per_layer_metrics(run)
        if trace:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            line["breakdown"] = {"device_ops": trace["device_ops"],
                                 "idle_gaps": trace["idle_gaps"]}
    else:
        line["metrics"] = {
            name: {"value": result["metrics"][name],
                   "unit": loop.END_TO_END[name]}
            for name in wl["end_to_end"]
        }
        line["metrics"]["setup_s"] = {"value": phases["window_open"],
                                      "unit": "s"}
    line["device"] = device
    emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main(t0=_PROCESS_T0))
