"""The runtime's four window metrics (``chipbench/readers/pauses.py``):
the garbage collector's pauses as the program recorded them, and the
window's late steps beside them.

The readers are held to hand-made runs with known times first, then to
one CPU rehearsal of a cell. Nothing here edits or switches anything:
the program records collections from ``runtime.initialize()`` on.
"""

from __future__ import annotations

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
METRICS = {"gc_pause_ms_per_step", "gc_pause_max_ms", "late_steps",
           "late_steps_outside_gc"}
LO, HI = 100.0, 120.0  # the hand-made window, seconds on the spans' clock


class Spans:
    """What the readers use of ``record.Spans``."""

    def __init__(self, spans):
        self.spans = spans


def hand_made(step_s: list[float], *, first_end: float = LO,
              window=(LO, HI)) -> dict:
    """A run whose ``observe_loss`` spans end ``step_s`` apart from
    ``first_end`` on (the window opens on the first), with other spans
    between them."""
    ends = [first_end]
    for s in step_s:
        ends.append(ends[-1] + s)
    spans = []
    for t1 in ends:
        spans.append(("dispatch", t1 - 0.004, t1 - 0.003))
        spans.append(("observe_loss", t1 - 0.002, t1))
    return {"loop": {"window": window, "steps": len(step_s)},
            "spans": Spans(spans)}


@pytest.fixture
def recorded(monkeypatch):
    """Sets what ``tracing.collector_pauses()`` returns."""
    from tpu_syncbn.obs import tracing

    def record(pauses):
        monkeypatch.setattr(
            tracing, "collector_pauses",
            lambda since=0.0: [p for p in pauses if p[0] >= since])

    return record


@pytest.fixture
def pauses():
    from chipbench.readers import pauses

    return pauses


# -- the collector's time in the window ---------------------------------------


def test_pauses_are_summed_over_the_steps_and_clipped_at_the_edges(
        pauses, recorded):
    run = hand_made([0.2] * 100)  # 100 steps, the window's 20 s
    recorded([
        (90.0, 90.2, 2, 7),       # set-up's: before the window
        (99.9, 100.1, 2, 0),      # across the open edge: 0.1 s inside
        (105.0, 105.002, 0, 3),   # every generation counts
        (110.0, 110.13, 2, 40),
        (119.95, 120.05, 1, 0),   # across the close edge: 0.05 s inside
        (121.0, 121.5, 2, 0),     # the traced slice's: after the window
    ])
    assert pauses.ms_per_step(run) == pytest.approx(
        1e3 * (0.1 + 0.002 + 0.13 + 0.05) / 100)
    # the longest that BEGAN inside, at its whole length; the one that
    # began before the window opened is set-up's
    assert pauses.max_ms(run) == pytest.approx(130.0)
    recorded([(99.9, 100.4, 2, 0), (119.95, 120.05, 1, 0)])
    assert pauses.max_ms(run) == pytest.approx(100.0)


def test_an_empty_record_reads_zero_not_nothing(pauses, recorded):
    run = hand_made([0.2] * 100)
    recorded([])
    assert pauses.ms_per_step(run) == 0
    assert pauses.max_ms(run) == 0
    assert pauses.late_steps_outside(run) == 0
    recorded([(90.0, 90.2, 2, 7), (121.0, 121.5, 2, 0)])  # none touches it
    assert pauses.ms_per_step(run) == 0 and pauses.max_ms(run) == 0


# -- the late steps -----------------------------------------------------------


@pytest.mark.parametrize("median_ms, not_late_ms, late_ms", [
    # 2% of 58 ms is 1.16 ms: the 2 ms decide
    (58.0, 59.9, 60.1),
    # 2% of 774 ms is 15.48 ms: the share decides (Ouro's were 28 on 774)
    (774.0, 789.0, 790.0),
])
def test_a_late_step_is_over_the_median_by_2ms_and_by_2_percent(
        pauses, recorded, median_ms, not_late_ms, late_ms):
    steps = [median_ms / 1e3] * 20
    steps[5] = not_late_ms / 1e3
    steps[11] = late_ms / 1e3
    steps[17] = 3 * median_ms / 1e3
    steps[3] = 0.5 * median_ms / 1e3  # a short step is not a late one
    run = hand_made(steps, window=(LO, LO + sum(steps)))
    recorded([])
    assert pauses.late_steps(run) == 2
    starts = [round(a - LO, 6) for a, _ in pauses.late_intervals(run)]
    assert starts == [round(sum(steps[:11]), 6), round(sum(steps[:17]), 6)]


def test_only_the_steps_that_end_inside_the_window_are_read(pauses, recorded):
    # warm-up's late step before the window and the traced slice's after
    # it (the profiler's start stalls the host) are not the window's
    steps = [0.058] * 3 + [0.3] + [0.058] * 20 + [0.9, 0.058, 0.058]
    lo = LO - (3 * 0.058 + 0.3)
    run = hand_made(steps, first_end=lo,
                    window=(LO + 1e-5, LO + 20 * 0.058 + 1e-5))
    recorded([])
    assert pauses.late_steps(run) == 0
    # the edges are completion times, read just after the span's end:
    # the steps between the window's first and last ends are all there
    ends = [t1 for n, _, t1 in run["spans"].spans if n == "observe_loss"]
    assert len([e for e in ends if LO - 1e-3 <= e <= run["loop"]["window"][1]
                + 1e-3]) == 21


def test_a_late_step_over_a_pause_is_the_collectors_and_one_beside_is_not(
        pauses, recorded):
    steps = [0.058] * 30
    steps[4] = 0.190   # a full collection inside it
    steps[12] = 0.0605  # late, and no collection near
    steps[20] = 0.0615  # late, over a collection of generation 0: too short
    steps[26] = 0.0700  # late, the collection is in the step BEFORE it
    run = hand_made(steps, window=(LO, LO + sum(steps)))
    at = [LO + sum(steps[:i]) for i in range(len(steps))]
    recorded([
        (at[4] + 0.010, at[4] + 0.140, 2, 11),
        (at[20] + 0.010, at[20] + 0.0105, 0, 0),
        (at[25] + 0.010, at[25] + 0.012, 1, 0),
    ])
    assert pauses.late_steps(run) == 4
    assert pauses.late_steps_outside(run) == 3
    # a pause that only reaches into the late step explains it too
    recorded([(at[4] + 0.010, at[4] + 0.140, 2, 11),
              (at[25] + 0.050, at[26] + 0.010, 2, 0)])
    assert pauses.late_steps_outside(run) == 2


def test_a_window_of_one_step_has_no_late_step(pauses, recorded):
    recorded([])
    assert pauses.late_steps(hand_made([])) == 0
    assert pauses.late_steps(hand_made([0.5])) == 0


# -- a program without the record ---------------------------------------------


def test_a_program_without_the_record_reads_as_nothing(pauses, monkeypatch):
    """The parent commit, on which the driver runs these files too."""
    from tpu_syncbn.obs import tracing

    monkeypatch.delattr(tracing, "collector_pauses")
    steps = [0.058] * 20
    steps[7] = 0.2
    run = hand_made(steps, window=(LO, LO + sum(steps)))
    assert pauses.ms_per_step(run) is None
    assert pauses.max_ms(run) is None
    assert pauses.late_steps_outside(run) is None
    assert pauses.late_steps(run) == 1  # the benchmark's spans alone


def test_the_line_of_such_a_program_leaves_the_three_out(monkeypatch):
    from chipbench import run as bench
    from tpu_syncbn.obs import tracing

    monkeypatch.delattr(tracing, "collector_pauses")
    run = hand_made([0.058] * 20, window=(LO, LO + 20 * 0.058))
    run["wl"] = {"loop": "train"}
    only = {"gc_pause_max_ms.json", "gc_pause_ms_per_step.json",
            "late_steps.json", "late_steps_outside_gc.json"}
    real_glob = bench.glob.glob
    monkeypatch.setattr(
        bench.glob, "glob",
        lambda pattern, **k: [p for p in real_glob(pattern, **k)
                              if os.path.basename(p) in only])
    assert bench.per_layer_metrics(run) == {
        "late_steps": {"value": 0, "unit": "count"}}


# -- the files ----------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_metric_files_say_what_the_listed_entries_say(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed, = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    with open(os.path.join(ROOT, "chipbench", "metrics", name + ".json")) as f:
        m = json.load(f)
    # owed by every cell of the loop: no list, no ``when``
    assert "workloads" not in listed and "when" not in m
    assert m["loops"] == ["train"] and m["reader"].startswith("pauses.")
    assert {k: m[k] for k in listed} == listed
    assert listed["layer"] == "runtime" and listed["better"] == "lower"
    assert listed["moves"] == "img_s_chip"
    assert listed["source"] == ("host_clock" if name == "late_steps"
                                else "program_counter")
    assert "step_ms_p95" in m["description"]


# -- one cell, rehearsed on the CPU -------------------------------------------


def run_cell(capsys, workload: str, trace: int, seed: int = 2147483693):
    from chipbench import run

    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "1", "--trace", str(trace)])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), [json.loads(x) for x in out[:-1]]


def test_traced_run_prints_the_four_and_the_untraced_line_none(capsys):
    import gc

    from tpu_syncbn.obs import tracing

    rc, line, earlier = run_cell(capsys, "retinanet-train-b2", trace=1)
    assert rc == 0 and line["correct"] is True
    # the program's normal path switched the record on, nothing else did
    assert gc.callbacks.count(tracing._on_collection) == 1
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        units = {m["name"]: m["unit"] for m in json.load(f)["per_layer"]}
    got = {k: line["metrics"][k] for k in METRICS}
    assert {k: v["unit"] for k, v in got.items()} == {k: units[k]
                                                      for k in METRICS}
    window_ms = 1e3 * earlier[-1]["window_s"]
    steps = earlier[-1]["observations"]["steps_in_window"]
    # the rehearsal's window holds some fifty steps' worth of young
    # collections: the record is not empty
    assert 0 < got["gc_pause_max_ms"]["value"] <= window_ms
    assert 0 < got["gc_pause_ms_per_step"]["value"] <= window_ms / steps
    assert 0 <= got["late_steps_outside_gc"]["value"] \
        <= got["late_steps"]["value"] < steps
    assert all(isinstance(got[k]["value"], int)
               for k in ("late_steps", "late_steps_outside_gc"))

    rc, line, _ = run_cell(capsys, "retinanet-train-b2", trace=0)
    assert rc == 0 and not METRICS & set(line["metrics"])
