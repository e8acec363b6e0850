"""The program's own spans in the benchmark, rehearsed on the CPU.

A traced run's profiler capture switches the program's span sites on
(``tpu_syncbn.obs.tracing``); ``chipbench/readers/program.py`` reads
them after the loop. Nothing in ``chipbench/`` switches anything on, and
an untraced run records nothing. Also here: the names the compiled
step of both configurations carries.
"""

from __future__ import annotations

import glob
import importlib
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "chipbench")
LOADER_CELLS = ["r50-train-b128", "retinanet-train-b2"]
RESIDENT_CELL = "r50-train-b128x4-resident"  # no loader in the process
LOADER_METRICS = {"data_wait_ms", "h2d_ms", "loader_build_ms",
                  "loader_build_cpu_ms", "loader_collate_ms",
                  "loader_queue_depth"}
TRAINER_METRICS = {"train_step_ms", "train_step_cpu_ms"}
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def benchmark_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell(capsys, workload: str, trace: int, seed: int = 3000000019):
    from chipbench import run

    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "1", "--trace", str(trace)])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), [json.loads(x) for x in out[:-1]]


@pytest.fixture
def no_earlier_capture(monkeypatch):
    """Other tests of this process have made captures: forget them, as
    a fresh process of the benchmark's command has none."""
    from tpu_syncbn.obs import tracing

    monkeypatch.setattr(tracing, "_capture", None)
    monkeypatch.setattr(tracing, "_capture_session", None)
    return tracing


@pytest.fixture
def benchmark_spans(monkeypatch):
    """The run's ``record.Spans`` objects, which ``run.main`` keeps to
    itself."""
    from chipbench import record

    made = []

    class Kept(record.Spans):
        def __init__(self):
            super().__init__()
            made.append(self)

    monkeypatch.setattr(record, "Spans", Kept)
    return made


def inside(inner, outers) -> bool:
    """Whether the program's span lies within one of the benchmark's
    (both on ``time.perf_counter``; the program keeps nanoseconds)."""
    return any(t0 - 1e-6 <= inner[1] and inner[2] <= t1 + 1e-6
               for _, t0, t1 in outers)


# -- the traced run reads the program's spans ---------------------------------


@pytest.mark.parametrize("workload", LOADER_CELLS)
def test_traced_loader_cell_prints_the_program_span_metrics(
        capsys, no_earlier_capture, benchmark_spans, workload):
    tracing = no_earlier_capture
    rc, line, earlier = run_cell(capsys, workload, trace=1)
    assert rc == 0 and line["correct"] is True
    got = line["metrics"]
    assert LOADER_METRICS | TRAINER_METRICS <= set(got)
    units = {m["name"]: m["unit"] for m in benchmark_json()["per_layer"]}
    for name in LOADER_METRICS | TRAINER_METRICS:
        assert got[name]["unit"] == units[name]
        assert got[name]["value"] >= 0
    assert got["loader_build_cpu_ms"]["value"] <= got["loader_build_ms"]["value"]
    assert got["loader_collate_ms"]["value"] <= got["loader_build_ms"]["value"]
    assert got["train_step_cpu_ms"]["value"] <= got["train_step_ms"]["value"]
    assert 0 < got["train_step_ms"]["value"]

    # The inside view is inside the outside view. The line's own numbers
    # cannot say so on the CPU (input_wait_ms and dispatch_ms are means
    # over the window, the program's over the traced slice after it, and
    # the CPU's profiler slows that slice), so compare span with span:
    # every data_wait + h2d of the capture lies in one of the benchmark's
    # input_wait spans, every train_step in one of its dispatch spans.
    assert tracing.get() is None  # the capture has ended
    ring = tracing.last_capture()
    (spans,) = benchmark_spans
    outer = {name: [s for s in spans.spans if s[0] == name]
             for name in ("input_wait", "dispatch")}
    traced = earlier[-1]["traced_steps"]
    steps = ring.spans("train_step")
    assert len(steps) == traced
    assert [s[5]["step"] for s in steps] == list(
        range(steps[0][5]["step"], steps[0][5]["step"] + traced))
    assert all(inside(s, outer["dispatch"]) for s in steps)
    waits, puts = ring.spans("data_wait"), ring.spans("h2d")
    assert len(waits) == len(puts) == traced
    assert all(inside(s, outer["input_wait"]) for s in waits + puts)
    # a step's data_wait + h2d is not longer than the input_wait it is in
    for wait, put in zip(waits, puts):
        (around,) = [o for o in outer["input_wait"]
                     if inside(wait, [o]) and inside(put, [o])]
        assert (wait[2] - wait[1]) + (put[2] - put[1]) <= \
            around[2] - around[1] + 1e-6
    assert all(s[5]["bytes"] > 0 for s in puts)
    fetches = [f for f in ring.spans("loader.fetch") if "seq" in f[5]]
    assert [f[5]["seq"] for f in fetches] == list(
        range(fetches[0][5]["seq"], fetches[0][5]["seq"] + len(fetches)))
    builds = ring.spans("loader.build")
    assert builds and all(b[4] != steps[0][4] for b in builds)  # workers


def test_untraced_run_records_nothing(capsys, no_earlier_capture):
    tracing = no_earlier_capture
    rc, line, _ = run_cell(capsys, "r50-train-b128", trace=0)
    assert rc == 0 and line["correct"] is True
    assert tracing.get() is None
    assert tracing.last_capture() is None
    assert not (LOADER_METRICS | TRAINER_METRICS) & set(line["metrics"])


# -- the reader ---------------------------------------------------------------


def hand_made_run(monkeypatch, n_completions: int):
    """A capture with one ``train_step`` span starting 0.5 s before each
    of ``n_completions`` completions one second apart, of 10 ms, 20 ms,
    ... wall and half that on the CPU."""
    from tpu_syncbn.obs import tracing

    ring = tracing.RingTracer(64)
    ring.t0 = 99.5
    for i in range(n_completions):
        ring.events.append({
            "name": "train_step", "ph": "X", "ts": i * 1e6,
            "dur": (i + 1) * 1e4, "pid": 1, "tid": 7, "cat": "tpu_syncbn",
            "args": {"span_id": i + 1, "cpu_us": (i + 1) * 5e3, "step": i,
                     "note": "text"}})
    ring.instant("marker")
    monkeypatch.setattr(tracing, "_capture", ring)
    completions = [100.0 + i for i in range(n_completions)]
    return {"loop": {"traced_completions": completions}}


def test_reader_means_the_spans_after_the_ramp(monkeypatch):
    from chipbench.readers import program

    # twelve completions: the spans after the fourth, the 5th..12th
    run = hand_made_run(monkeypatch, 12)
    assert program.mean_ms(run, span="train_step") == pytest.approx(
        10.0 * sum(range(5, 13)) / 8)
    assert program.mean_ms(run, span="train_step", clock="cpu") == \
        pytest.approx(5.0 * sum(range(5, 13)) / 8)
    assert program.mean_arg(run, span="train_step", arg="step") == \
        pytest.approx(sum(range(4, 12)) / 8)
    # eight completions (the rehearsal): the spans after the first
    run = hand_made_run(monkeypatch, 8)
    assert program.mean_ms(run, span="train_step") == pytest.approx(
        10.0 * sum(range(2, 9)) / 7)
    # no span starts after the cut: all of the capture's
    run = hand_made_run(monkeypatch, 8)
    run["loop"]["traced_completions"] = [1e9]
    assert program.mean_ms(run, span="train_step") == pytest.approx(45.0)


def test_reader_finds_nothing_where_there_is_nothing(monkeypatch):
    from chipbench.readers import program
    from tpu_syncbn.obs import tracing

    run = hand_made_run(monkeypatch, 12)
    assert program.mean_ms(run, span="no_such_span") is None
    assert program.mean_arg(run, span="train_step", arg="no_such") is None
    assert program.mean_arg(run, span="train_step", arg="note") is None
    # the profiler never started: no traced step, whatever an earlier
    # capture of the process left behind
    assert program.mean_ms({"loop": {"traced_completions": []}},
                           span="train_step") is None
    # no capture at all
    monkeypatch.setattr(tracing, "_capture", None)
    assert program.mean_ms(run, span="train_step") is None
    # a program from before the switch (the parent commit, on which the
    # driver runs these files too) has no last_capture
    monkeypatch.delattr(tracing, "last_capture")
    assert program.mean_ms(run, span="train_step") is None
    assert program.mean_arg(run, span="train_step", arg="step") is None


# -- a cell with no loader in the process -------------------------------------


def test_traced_resident_cell_reads_the_trainer_spans_alone(
        capsys, no_earlier_capture):
    rc, line, earlier = run_cell(capsys, RESIDENT_CELL, trace=1)
    assert rc == 0 and earlier[-1]["traced_steps"] > 4
    assert line["correct"] is True
    want = {m["name"] for m in benchmark_json()["per_layer"]
            if RESIDENT_CELL in m.get("workloads", [RESIDENT_CELL])}
    # the input layer's metrics are not listed for it, nor printed
    assert TRAINER_METRICS <= want and not LOADER_METRICS & want
    # the CPU has no peak, and its trace not always an all-reduce by name
    assert want - {"mfu", "allreduce_ms"} <= set(line["metrics"]) <= want
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    ring = no_earlier_capture.last_capture()
    assert {s[0] for s in ring.spans()} == {"train_step"}


# -- metric files and BENCHMARK.json -------------------------------------------


def test_every_metric_file_has_its_per_layer_entry_and_the_reverse():
    listed = {m["name"]: m for m in benchmark_json()["per_layer"]}
    files = {}
    for path in glob.glob(os.path.join(BENCH, "metrics", "*.json")):
        with open(path) as f:
            files[os.path.basename(path)[:-len(".json")]] = json.load(f)
    assert set(files) == set(listed)
    cells = {}
    for w in benchmark_json()["workloads"]:
        with open(os.path.join(BENCH, "workloads", w["name"] + ".json")) as f:
            cells[w["name"]] = json.load(f)
    for name in LOADER_METRICS | TRAINER_METRICS:
        m = files[name]
        assert m["name"] == name and m["source"] == "program_span"
        assert m["moves"] == "img_s_chip" and m["loops"] == ["train"]
        module, fn = m["reader"].rsplit(".", 1)
        assert module == "program"
        assert callable(getattr(importlib.import_module(
            "chipbench.readers.program"), fn))
        # the file's condition picks the cells BENCHMARK.json lists
        picked = [c for c, wl in cells.items() if all(
            wl["input"]["mode"] in allowed
            for allowed in m.get("when", {}).values())]
        assert sorted(picked) == sorted(listed[name].get("workloads", cells))
    assert {n for n in files if "when" in files[n]
            and "input.mode" in files[n]["when"]} == \
        LOADER_METRICS | {"input_wait_ms"}


# -- the names in the compiled step ---------------------------------------------

# a transformation wraps the outermost scope: jvp(layer1)/block0/syncbn/...
# is the forward pass, transpose(jvp(layer1))/block0/... the backward one
SCOPES = {
    "resnet50-syncbn": [
        "forward_backward/", "grad_allreduce/", "optimizer/", "monitors/",
        "jvp(stem)/syncbn/stats/", "jvp(stem)/syncbn/psum/",
        "jvp(stem)/syncbn/normalize/", "jvp(layer1)/block0/syncbn/psum/",
        "jvp(layer4)/block0/", "jvp(fc)/",
        "transpose(jvp(layer4))/block0/syncbn/normalize/",
    ],
    "retinanet-r50-fpn-syncbn": [
        "forward_backward/", "grad_allreduce/", "optimizer/", "monitors/",
        "jvp(stem)/syncbn/psum/", "jvp(layer2)/block0/", "jvp(fpn)/",
        "jvp(head_cls)/", "jvp(head_box)/", "jvp(vmap(anchors_match))/",
        "jvp(vmap(focal))/", "jvp(vmap(smooth_l1))/",
        "transpose(jvp(head_cls))/", "transpose(jvp(vmap(focal)))/",
    ],
}


@pytest.mark.parametrize("config", sorted(SCOPES))
def test_lowered_train_step_carries_the_scope_names(config):
    import jax
    import numpy as np

    from tpu_syncbn import parallel, runtime

    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        cfg = json.load(f)
    cfg = {**cfg, **cfg["rehearsal"]}
    family = importlib.import_module("chipbench.families." + cfg["family"])
    model = family.build_model(cfg, jax.random.key(5))
    dp = parallel.DataParallel(model, family.optimizer(cfg, 2),
                               family.loss_fn,
                               mesh=runtime.data_parallel_mesh(1))
    pool = family.make_pool(cfg, 2, np.random.default_rng(5))
    batch = jax.device_put(family.transform(cfg)(pool), dp.batch_sharding)
    text = dp.lowered_train_step(batch).as_text(debug_info=True)
    for scope in SCOPES[config]:
        assert scope in text, scope
