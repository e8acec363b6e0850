"""The chip benchmark, rehearsed on the CPU.

Everything here runs on the virtual CPU devices ``tests/conftest.py``
sets up; no TPU library is loaded and nothing is decided while this
module is imported. The command runs in-process through
``run.main([...])`` at the ``rehearsal`` sizes of the workload and
configuration files (float32 compute, a cut-down backbone), so the
comparison with the plain reference is tight here and the bf16
tolerance is the chip's business.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "chipbench")
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "v5e-r50-b64-look.json.gz")
WORKLOADS = ["r50-train-b128", "retinanet-train-b2",
             "r50-train-b128x4-resident"]
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
TRACE_METRICS = {"device_step_ms", "device_idle_share"}


def benchmark_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell(capsys, workload: str, trace: int, seed: int = 2147483659):
    from chipbench import run

    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "1", "--trace", str(trace)])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), [json.loads(x) for x in out[:-1]]


def expected_per_layer(workload: str) -> set[str]:
    return {m["name"] for m in benchmark_json()["per_layer"]
            if workload in m.get("workloads", [workload])}


# -- the command, end to end ------------------------------------------------


@pytest.mark.parametrize("workload", WORKLOADS)
def test_untraced_run_prints_the_end_to_end_metrics(capsys, workload):
    rc, line, earlier = run_cell(capsys, workload, trace=0)
    assert rc == 0
    assert set(line) == LINE_KEYS
    assert set(line["device"]) == DEVICE_KEYS
    assert line["device"]["platform"] == "cpu"  # never passed off as a chip
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 10
    with open(os.path.join(BENCH, "workloads", workload + ".json")) as f:
        listed = set(json.load(f)["end_to_end"]) | {"setup_s"}
    assert set(line["metrics"]) == listed
    for m in benchmark_json()["end_to_end"]:
        if m["name"] in listed:
            got = line["metrics"][m["name"]]
            assert got["unit"] == m["unit"] and got["value"] > 0
    errors = earlier[-1]["check"]["errors"]
    # float32 compute in the rehearsal: the reference agrees closely
    assert errors and all(v < 1e-3 for v in errors.values()), errors
    assert {"stem_running_mean", "stem_running_var"} <= set(errors)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_traced_run_prints_the_per_layer_metrics(capsys, workload):
    rc, line, earlier = run_cell(capsys, workload, trace=1)
    assert rc == 0 and earlier[-1]["traced_steps"] > 4
    assert set(line) == LINE_KEYS | {"breakdown"}
    assert set(line["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    # mfu needs a peak and the CPU has none: left out here. The CPU's
    # trace names its all-reduces only in some captures.
    want = expected_per_layer(workload) - {"mfu", "allreduce_ms"}
    assert set(line["metrics"]) - {"allreduce_ms"} == want
    units = {m["name"]: m["unit"] for m in benchmark_json()["per_layer"]}
    for name, got in line["metrics"].items():
        assert got["unit"] == units[name]
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert 0 <= line["metrics"]["device_idle_share"]["value"] < 100
    assert 1 <= len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 10
    labels = {g[0] for g in line["breakdown"]["idle_gaps"]}
    assert labels <= {"input_wait", "dispatch", "observe_loss", "other"}


@pytest.mark.parametrize("broken", ["start_trace", "stop_trace", "load"])
def test_a_failing_profiler_costs_only_the_trace_metrics(
        capsys, monkeypatch, broken):
    import jax

    from chipbench import trace_reduce

    def boom(*a, **k):
        raise RuntimeError(f"{broken} made to fail")

    if broken == "load":
        monkeypatch.setattr(trace_reduce, "load", boom)
    else:
        monkeypatch.setattr(jax.profiler, broken, boom)
    try:
        rc, line, _ = run_cell(capsys, "r50-train-b128", trace=1)
    finally:
        monkeypatch.undo()
        if broken == "stop_trace":
            jax.profiler.stop_trace()  # the real one: end the capture
    assert rc == 0
    assert set(line) == LINE_KEYS and line["correct"] is True
    assert set(line["device"]) == DEVICE_KEYS
    assert not TRACE_METRICS & set(line["metrics"])
    assert {"dispatch_ms", "input_wait_ms", "cache_misses",
            "compiles_in_window"} <= set(line["metrics"])


def test_the_seed_is_an_argument_of_the_programs_not_a_constant(capsys):
    """Another seed makes other weights and pixels with the same
    programs: no new entry in the compile cache."""
    _, a, ea = run_cell(capsys, "r50-train-b128", trace=0, seed=11)
    _, b, eb = run_cell(capsys, "r50-train-b128", trace=0, seed=2**31 + 12)
    assert ea[-1]["first_loss"] != eb[-1]["first_loss"]
    assert eb[-1]["cache"]["misses"] == 0


# -- the reduction from trace to numbers --------------------------------------


def fixture_trace() -> dict:
    """The recorded trace with the run's host-clock spans laid onto it."""
    from chipbench import trace_reduce

    with gzip.open(FIXTURE, "rt") as f:
        fx = json.load(f)
    trace = {"devices": {int(k): v for k, v in fx["devices"].items()}}
    trace["host_spans"] = trace_reduce.align(trace, fx["host_spans"],
                                             fx["completions"])
    return trace


def test_trace_reduce_on_the_recorded_v5e_trace():
    """Four executions of the ResNet-50 b64 step recorded on the chip
    (PR 24's look run): the slice from the second execution's start to
    the fourth's holds two whole steps."""
    from chipbench import trace_reduce

    trace = fixture_trace()
    # the fixture's host clock read 1000 s when the profile started, and
    # that run's quickest fetch returned 2.25 ms after its step ended
    first = trace["host_spans"][0]
    assert first[0] == "dispatch"
    assert first[1] == pytest.approx(133520807.0 - 2249204.0, abs=1.0)
    r = trace_reduce.reduce(trace, skip_steps=1)
    assert r["steps"] == 2 and r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.430590465, rel=1e-9)
    # operations on the core do not overlap, so the union is their sum
    assert r["busy_s_device0"] == pytest.approx(0.051015508, rel=1e-9)
    assert r["busy_s"] == r["busy_s_device0"]
    assert r["allreduce_s_device0"] == 0.0  # one chip: no collective
    assert r["device_ops"][0][0] == "select_and_scatter.9"
    assert r["device_ops"][0][1] == pytest.approx(0.001761888, rel=1e-9)
    assert len(r["device_ops"]) == 10
    # that run fetched every loss before the next dispatch: the device
    # waited for the host inside observe_loss
    assert r["idle_gaps"][0][0] == "observe_loss"
    assert r["idle_gaps"][0][1] == pytest.approx(0.368278457, rel=1e-9)
    # too few executions to skip four: the whole capture is used
    assert trace_reduce.reduce(fixture_trace(), skip_steps=4)["steps"] == 3


def test_trace_reduce_on_a_hand_made_trace():
    from chipbench import trace_reduce

    mod = "jit_step(1)"
    ops0 = [["%fusion.1 = f32[8]{0} fusion(...)", 100.0, 40.0],
            ["all-reduce.3", 120.0, 50.0],      # overlaps fusion.1
            ["all-reduce-start.4", 200.0, 10.0],
            ["copy.2", 260.0, 20.0],
            ["fusion.1", 310.0, 30.0]]
    ops0 = [[trace_reduce.short_name(n), s, d] for n, s, d in ops0]
    trace = {
        "devices": {
            0: {"ops": ops0,
                "modules": [[mod, 100.0, 90.0], [mod, 200.0, 90.0],
                            [mod, 300.0, 90.0]]},
            1: {"ops": [["fusion.1", 100.0, 100.0], ["fusion.1", 250.0, 25.0]],
                "modules": [[mod, 100.0, 90.0], [mod, 300.0, 90.0]]},
        },
    }
    # a host clock 5 s ahead of the trace's; the second fetch returned
    # 2 ns after its step ended at 290, the others later
    ahead = 5.0
    completions = [ahead + 200e-9, ahead + 292e-9, ahead + 400e-9]
    spans = [("dispatch", ahead + 167e-9, ahead + 207e-9),
             ("input_wait", ahead + 207e-9, ahead + 267e-9),
             ("before_the_trace", ahead - 1.0, ahead - 0.9)]
    trace["host_spans"] = trace_reduce.align(trace, spans, completions)
    assert [s[0] for s in trace["host_spans"]] == ["dispatch", "input_wait"]
    assert trace["host_spans"][0][1:] == [pytest.approx(165.0, abs=0.01),
                                          pytest.approx(40.0, abs=0.01)]
    assert trace_reduce.align(trace, spans, completions[:2]) == []
    r = trace_reduce.reduce(trace, skip_steps=0)
    assert r["steps"] == 2 and r["devices"] == 2
    assert r["window_s"] == pytest.approx(200e-9)
    # device 0: [100,170] + [200,210] + [260,280] = 100 ns of 200
    assert r["busy_s_device0"] == pytest.approx(100e-9)
    # device 1: [100,200] + [250,275] = 125 ns; the mean over chips
    assert r["busy_s"] == pytest.approx(112.5e-9)
    assert r["allreduce_s_device0"] == pytest.approx(60e-9)
    assert r["device_ops"][0] == ["all-reduce.3", pytest.approx(50e-9)]
    assert ["fusion.1", pytest.approx(40e-9)] in r["device_ops"]
    assert r["idle_gaps"][:2] == [["input_wait", pytest.approx(50e-9)],
                                  ["dispatch", pytest.approx(30e-9)]]
    assert trace_reduce.reduce({"devices": {}}) is None


def test_percentile_is_numpys():
    import numpy as np

    from chipbench.loops import train

    values = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.0]
    for q in (50, 90, 95, 100):
        assert train.percentile(values, q) == pytest.approx(
            float(np.percentile(values, q)))


# -- the closed-form operation counts ---------------------------------------


def test_closed_form_flops_against_hand_worked_values():
    from chipbench import flops

    with open(os.path.join(BENCH, "configs", "resnet50-syncbn.json")) as f:
        r50 = json.load(f)
    # torchvision's ResNet-50 at 224x224: 4.09 G multiply-adds forward
    assert flops.classifier_forward_macs(r50) == 4_089_184_256
    # stem by hand: 112*112 outputs x 7*7*3 x 64
    assert flops.conv_macs(224, 224, 7, 3, 64, 2) == (118_013_952, 112, 112)
    assert flops.train_flops(4_089_184_256) == 24_535_105_536

    with open(os.path.join(BENCH, "configs",
                           "retinanet-r50-fpn-syncbn.json")) as f:
        det = json.load(f)
    parts = flops.detector_forward_macs(det)
    locations = 100 * 168 + 50 * 84 + 25 * 42 + 13 * 21 + 7 * 11
    assert locations == 22_400 and locations * det["num_anchors"] == 201_600
    # head by hand: per location, 8 tower convolutions of 9*256*256 and
    # the two output convolutions of 9*256*(9*80) and 9*256*(9*4)
    assert parts["head"] == locations * (8 * 589_824 + 1_658_880 + 82_944)
    laterals = 16_800 * 512 * 256 + 4_200 * 1024 * 256 + 1_050 * 2048 * 256
    outputs = (16_800 + 4_200 + 1_050) * 589_824
    p6_p7 = 273 * 9 * 2048 * 256 + 77 * 589_824
    assert parts["fpn"] == laterals + outputs + p6_p7
    # the backbone without its classifier, at 800x1344 = 21.43 x 224x224
    assert parts["backbone"] == 87_581_491_200
    assert round(sum(parts.values()) / 1e9, 1) == 250.5


# -- BENCHMARK.json and the files it names ------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


def test_benchmark_json_meets_the_contract_and_names_files_that_exist():
    b = benchmark_json()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "chipbench/run.py"]
    assert b["paths"] == ["chipbench", "tests/chipbench"]
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    metrics = b["end_to_end"] + b["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    ends = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in ends and ends["setup_s"]["bound"] <= 0.1
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert c["file"].startswith("chipbench/") and PATH.match(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            on_disk = json.load(f)
        assert on_disk["name"] == c["name"]
        assert on_disk["source"] == c["source"]
        assert on_disk["reduced"] == c["reduced"]
    cells = [w["name"] for w in b["workloads"]]
    assert len(cells) == len(set(cells))
    assert len({(w["config"], w["traffic"]) for w in b["workloads"]}) == len(cells)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(1, len(cells) // 4)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        with open(os.path.join(BENCH, "workloads", w["name"] + ".json")) as f:
            on_disk = json.load(f)
        assert on_disk["config"] == w["config"]
        assert on_disk["chips"] == w["chips"] and on_disk["why"] == w["why"]
        assert set(on_disk["end_to_end"]) | {"setup_s"} == {
            m["name"] for m in b["end_to_end"]
            if w["name"] in m.get("workloads", [w["name"]])}
        for part in ("families", "inputs", "loops"):
            assert os.path.isdir(os.path.join(BENCH, part))
        with open(os.path.join(ROOT, configs[w["config"]]["file"])) as f:
            family = json.load(f)["family"]
        assert os.path.exists(os.path.join(BENCH, "families", family + ".py"))
        assert os.path.exists(os.path.join(
            BENCH, "inputs", on_disk["input"]["mode"] + ".py"))
        assert os.path.exists(os.path.join(
            BENCH, "loops", on_disk["loop"] + ".py"))
    assert {w["config"] for w in b["workloads"]} == set(configs)
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert len(m["layer"]) <= 200
        where = m.get("workloads", cells)
        assert where and set(where) <= set(cells)
        # the metric it moves is reported wherever it is
        assert set(where) <= set(ends[m["moves"]].get("workloads", cells))


def test_every_per_layer_metric_is_one_file_that_agrees_with_benchmark_json():
    import importlib

    b = benchmark_json()
    listed = {m["name"]: m for m in b["per_layer"]}
    files = {}
    for path in glob.glob(os.path.join(BENCH, "metrics", "*.json")):
        with open(path) as f:
            m = json.load(f)
        assert os.path.basename(path) == m["name"] + ".json"
        files[m["name"]] = m
        module, fn = m["reader"].rsplit(".", 1)
        assert callable(getattr(
            importlib.import_module("chipbench.readers." + module), fn))
    # a metric file that BENCHMARK.json does not list waits for a cell:
    # it has a condition that no listed cell meets
    assert set(listed) <= set(files)
    assert all("when" in files[name] for name in set(files) - set(listed))
    for name, m in listed.items():
        for key in ("unit", "better", "source", "layer", "moves"):
            assert files[name][key] == m[key], (name, key)


def test_an_unknown_device_has_no_peak():
    from chipbench.readers import derived

    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert all("source" in p for p in peaks.values())
    run = {"device": {"platform": "tpu", "kind": "TPU v9 imaginary"},
           "peaks": peaks}
    with pytest.raises(KeyError):
        derived.mfu(run)


# -- the plain reference against the program ----------------------------------


def test_reference_loss_statistics_and_gradient_norm_match_the_program():
    """Small size, float32 compute, one device: the program's first
    step (loss, stem running statistics, global gradient norm from its
    in-step monitor) against the plain reference and ``jax.grad`` of the
    reference's loss."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import correct, reference
    from chipbench.families import classifier
    from tpu_syncbn import parallel, runtime

    with open(os.path.join(BENCH, "configs", "resnet50-syncbn.json")) as f:
        cfg = json.load(f)
    cfg = {**cfg, **cfg["rehearsal"]}
    model = classifier.build_model(cfg, jax.random.key(3))
    dp = parallel.DataParallel(
        model, classifier.optimizer(cfg, 8), classifier.loss_fn,
        mesh=runtime.data_parallel_mesh(1),
    )
    rng = np.random.default_rng(3)
    pool = classifier.make_pool(cfg, 8, rng)
    batch = jax.device_put(classifier.transform(cfg)(pool), dp.batch_sharding)
    params = correct.pure(dp.params)

    def ref_loss(p):
        feats, _ = reference.resnet_features(p, batch[0], jnp.float32)
        logp = jax.nn.log_softmax(
            reference.classifier_head(p, feats[-1], jnp.float32))
        return -jnp.take_along_axis(logp, batch[1][:, None], axis=1).mean()

    want_loss, grads = jax.jit(jax.value_and_grad(ref_loss))(params)
    want_norm = float(jnp.sqrt(sum(
        jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(grads))))
    got = correct.program_outputs(dp, classifier, batch)
    ref = jax.jit(reference.classifier)(params, batch, got)
    assert set(ref["errors"]) == {"c2", "c3", "c4", "c5", "logits"}
    assert all(float(e) < 1e-4 for e in ref["errors"].values())
    out = dp.train_step(batch)
    assert float(out.loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert float(out.monitors["grad_norm"]) == pytest.approx(want_norm,
                                                             rel=1e-3)
    stem = classifier.stem_running_stats(correct.pure(dp.rest))
    for name, want in reference.expected_running_stats(ref["stem"]).items():
        assert float(reference.rel_l2(stem[name], want)) < 1e-5


def test_verdict_fails_on_each_thing_it_guards():
    import numpy as np

    from chipbench import correct

    good = {"logits": 1e-2, "c5": 0.3, "loss": 1e-4, "stem_running_var": 1e-4}
    before, after = np.zeros(10), np.ones(10)
    assert correct.verdict(good, [1.0, 0.5], before, after)["correct"]
    assert not correct.verdict({**good, "logits": 0.06}, [1.0], before,
                               after)["correct"]  # an 8-bit format's error
    assert not correct.verdict({**good, "c5": 1.4}, [1.0], before,
                               after)["correct"]  # an unrelated map
    assert not correct.verdict({**good, "stem_running_var": 0.5}, [1.0],
                               before, after)["correct"]  # stats stayed local
    nan = correct.verdict(good, [1.0, float("nan")], before, after)
    assert not nan["correct"] and nan["failed"] == 1
    assert not correct.verdict(good, [1.0], before, before)["correct"]
