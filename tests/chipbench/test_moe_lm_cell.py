"""The mixture-of-experts family and its cell
(``joyai-l5-train-b1x8192``), rehearsed on the CPU at the rehearsal sizes
of the two files: the command end to end, traced and untraced; what makes
``correct`` false; the closed-form counts against counts by hand; the two
roofline shares and the load metric from hand-made runs; the controls;
the configuration against the published one.
"""

from __future__ import annotations

import json
import os
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "joyai-l5-train-b1x8192"
CONFIG = "joyai-llm-flash-l5"
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# read by scope path, which the CPU's trace does not carry; mfu needs a
# peak, which the CPU has not
CHIP_ONLY = {"forward_ms", "backward_ms", "mla_attention_ms", "moe_ms",
             "moe_route_ms", "moe_experts_ms", "mtp_ms",
             "mla_backward_scan_ms", "moe_lm_head_ms", "moe_lm_recompute_ms",
             "mla_kernel_roofline_pct", "moe_experts_roofline_pct", "mfu"}
ERRORS = {"layer1", "attention", "router", "loads", "pairs_not_computed",
          "moe", "expert_layer", "head", "cross_entropy", "z_main", "z_mtp",
          "logits_main", "logits_mtp", "main_loss", "mtp_loss", "loss"}
KERNEL = ("jit(step)/forward_backward/jvp()/while/body/closed_call/checkpoint/"
          "mla/attention/flash_fwd_q512_k512/pallas_call")
PRODUCTS = ("jit(step)/forward_backward/jvp()/while/body/closed_call/"
            "checkpoint/moe/while/body/moe_experts/ragged_dot")


@pytest.fixture(autouse=True)
def no_loads_kept_by_an_earlier_run():
    """The family keeps the loads of the last run it was asked about in
    its module (``LAST_LOADS``, ``LAST_RECENT_LOADS``): a test starts
    without them."""
    from chipbench.families import moe_lm

    moe_lm.LAST_LOADS[:] = moe_lm.LAST_RECENT_LOADS[:] = []
    yield
    moe_lm.LAST_LOADS[:] = moe_lm.LAST_RECENT_LOADS[:] = []


def run_cell(capsys, trace: int, seed: int = 2147483693):
    from chipbench import run

    rc = run.main(["--workload", CELL, "--seed", str(seed),
                   "--seconds", "1", "--trace", str(trace)])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), [json.loads(x) for x in out[:-1]]


def files():
    from chipbench import run

    return (run.load_json("configs", CONFIG + ".json"),
            run.load_json("workloads", CELL + ".json"))


def a_run(ops: list, steps: int, family=None) -> dict:
    from chipbench import run
    from chipbench.families import moe_lm

    cfg, wl = files()
    return {"trace": {"steps": steps, "ops": ops},
            "family": family or moe_lm, "cfg": cfg, "wl": wl,
            "device": {"kind": "TPU v5 lite"},
            "peaks": run.load_json("peaks.json")}


def test_untraced_run_is_correct_and_reports_its_end_to_end_metrics(capsys):
    from chipbench import correct
    from chipbench.families import moe_lm

    rc, line, earlier = run_cell(capsys, trace=0)
    assert rc == 0 and set(line) == LINE_KEYS
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 10
    assert set(line["metrics"]) == {"img_s_chip", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    check = earlier[-1]["check"]
    assert set(check["errors"]) == ERRORS
    # float32 in the rehearsal: the reference agrees closely, the chosen
    # sets and the loads exactly, and no held pair goes uncomputed
    assert all(v < 1e-5 for v in check["errors"].values()), check["errors"]
    assert check["errors"]["loads"] == 0.0
    assert check["errors"]["pairs_not_computed"] == 0.0
    assert check["tolerances"] == {
        **{k: moe_lm.TOLERANCES[k] for k in ERRORS - {"loss"}},
        "loss": correct.LOSS_TOL}
    # every parameter leaf and every expert layer's selection bias moved
    assert check["stats_moved_share"] == 1.0


def test_traced_run_prints_the_per_layer_metrics_the_cell_owes(capsys):
    rc, line, earlier = run_cell(capsys, trace=1)
    assert rc == 0 and earlier[-1]["traced_steps"] > 4
    assert set(line) == LINE_KEYS | {"breakdown"} and line["correct"] is True
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        owed = {m["name"] for m in json.load(f)["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert CHIP_ONLY <= owed
    assert set(line["metrics"]) == owed - CHIP_ONLY
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    # 16 experts, 3 a token, Zipf tokens: the worst layer's fullest
    # expert is over the mean and under all of it
    assert 1.0 < line["metrics"]["expert_load_max_over_mean"]["value"] < 16.0


@pytest.mark.parametrize("name", sorted(ERRORS - {"loss"}))
def test_an_error_over_the_familys_tolerance_makes_correct_false(name):
    from chipbench import correct
    from chipbench.families import moe_lm

    good = {"loss": 1e-5,
            **{k: 0.5 * v for k, v in moe_lm.TOLERANCES.items()}}
    before, after = np.zeros(17), np.ones(17)
    assert correct.verdict(good, [11.0, 10.9], before, after,
                           moe_lm)["correct"]
    over = 2 * moe_lm.TOLERANCES[name] or 1 / 4096  # one pair of a layer's
    bad = correct.verdict({**good, name: over}, [11.0], before, after, moe_lm)
    assert not bad["correct"] and bad["out_of_tolerance"] == [name]
    assert moe_lm.TOLERANCES[name] <= correct.tolerance(name)


def test_closed_form_flops_against_a_count_by_hand():
    from chipbench import flops_moe_lm, run
    from chipbench.families import moe_lm

    cfg = run.rehearsal(files()[0])
    # hidden 64, 4 heads of 16 + 8 (q, k) and 16 (v), ranks 48 and 32,
    # dense MLP 128, experts 32 wide, 4 of 16 held, 3 a token, vocabulary
    # 256, 1 dense + 2 expert layers + the prediction module, 32 tokens
    mla = 64 * 48 + 48 * 4 * 24 + 64 * (32 + 8) + 32 * 4 * 32 + 4 * 16 * 64
    assert flops_moe_lm.mla_matmul_macs(cfg) == mla == 18432
    assert flops_moe_lm.attention_macs(cfg) == 4 * (24 + 16) * 16
    moe = 64 * 16 + 3 * 64 * 32 + (3 * 4 / 16) * 3 * 64 * 32
    assert flops_moe_lm.moe_macs(cfg) == moe == 11776
    per_token = ((mla + 2560 + 3 * 64 * 128) + 3 * (mla + 2560 + 11776)
                 + 2 * 64 * 64 + 2 * 64 * 256)
    assert flops_moe_lm.forward_macs_per_token(cfg) == per_token
    assert moe_lm.train_flops_per_image(cfg) == 3 * 2 * 32 * per_token
    # the published widths at 5 layers and 8,192 tokens: ISSUE 34's count
    full = files()[0]
    assert flops_moe_lm.mla_matmul_macs(full) == 26_345_472
    assert flops_moe_lm.attention_macs(full) == 41_943_040
    assert flops_moe_lm.moe_macs(full) == 524_288 + 4_718_592 + 2_359_296
    assert flops_moe_lm.forward_macs_per_token(full) == 566_362_112
    assert moe_lm.train_flops_per_image(full) == 27_837_830_529_024


def test_the_kernels_roofline_share_counts_its_calls_in_the_trace():
    """Two steps of six layer applications: twelve forward calls and
    twelve under ``jax.checkpoint``, 0.2 s in all, and a neighbour's
    reduction that carries the kernel's path, 0.004 s and no call. One
    call: 32 heads x 8192 x 8193 / 2 scores x 320 multiply-adds = 687
    GFLOP, 3.49 ms at 197 TFLOP/s; the 337 MB it moves take 0.41 ms: the
    operations bound it."""
    from chipbench import flops_moe_lm, run
    from chipbench.families import moe_lm
    from chipbench.readers import kernel_calls

    cfg, wl = files()
    flops, nbytes = flops_moe_lm.flash_forward_counts(cfg, 1)
    assert flops == 2 * 32 * (8192 * 8193 // 2) * (192 + 128)
    assert nbytes == 32 * 8192 * ((2 * 192 + 2 * 128) * 2 + 4)
    assert moe_lm.attention_kernel_call_counts(cfg, wl) == (flops, nbytes)
    remat = KERNEL.replace("jvp()", "transpose(jvp())").replace(
        "checkpoint/", "checkpoint/rematted_computation/")
    ops = [["flash_fwd_q512_k512.3", KERNEL, 0.11, 12],
           ["flash_fwd_q512_k512.4", remat, 0.09, 12],
           ["reduce.11", KERNEL, 0.004, 12],
           ["fusion.3", KERNEL.replace("flash_fwd_q512_k512/pallas_call",
                                       "dot_general"), 0.5, 12],
           ["while.1", None, 1.0, 2]]
    args = run.load_json("metrics", "mla_kernel_roofline_pct.json")["args"]
    got = kernel_calls.share_by_counted_calls(a_run(ops, 2), **args)
    assert got == pytest.approx(100 * 24 * (flops / 197e12) / 0.204)
    assert 40 < got < 42
    # a step that saved the kernel's output makes half the calls in half
    # the time: the share stays the kernel's own
    saved = kernel_calls.share_by_counted_calls(
        a_run([ops[0], ["reduce.11", KERNEL, 0.002, 12]], 2), **args)
    assert saved == pytest.approx(100 * 12 * (flops / 197e12) / 0.112)
    # nothing to read: XLA's attention, no trace, a device without a peak
    assert kernel_calls.share_by_counted_calls(a_run(ops[3:], 2), **args) is None
    assert kernel_calls.share_by_counted_calls(
        {**a_run(ops, 2), "trace": None}, **args) is None
    assert kernel_calls.share_by_counted_calls(
        {**a_run(ops, 2), "device": {"kind": "cpu"}}, **args) is None
    # a program without the span (the parent): a family without the
    # function reads as nothing and does not raise
    assert kernel_calls.share_by_counted_calls(
        a_run(ops, 2, family=types.SimpleNamespace()), **args) is None


def recent_loads(held_pairs: list) -> list:
    """What ``moving_state`` keeps of a run whose last steps put
    ``held_pairs[step][layer]`` pairs on held expert 3 of each of five
    expert layers (four of the stack, the prediction module's) and the
    rest on expert 200, which is not held: the sixteen steps' loads a
    block, oldest first, the steps not given empty."""
    recent = np.zeros((5, 16, 256))
    for step, layers in enumerate(held_pairs, start=16 - len(held_pairs)):
        recent[:, step, 3] = layers
        recent[:, step, 200] = 65536 - np.asarray(layers)
    return [recent[:4], recent[4:]]


def test_the_grouped_products_roofline_share_from_a_hand_made_trace(
        monkeypatch):
    """Two whole steps, 0.04 s under ``moe_experts``, in each of which
    every layer's held expert 3 got 4,096 pairs. A layer: 3 matrices x 3
    passes x 4096 x 2 x 2048 x 768 = 116 GFLOP, 0.59 ms; its bytes: ONE
    expert's 4.7M weights three times and 9 x 4096 rows of 2048 + 768,
    in bf16, 236 MB, 0.29 ms: the operations bound it. Five such layers a
    step."""
    from chipbench import flops_moe_lm, run
    from chipbench.families import moe_lm
    from chipbench.readers import named_ops, scope

    cfg, wl = files()
    flops, nbytes = flops_moe_lm.grouped_product_counts(cfg, [4096], [1])
    assert flops == 9 * 4096 * 2 * 2048 * 768
    assert nbytes == 2 * (3 * 3 * 2048 * 768 + 9 * 4096 * (2048 + 768))
    # the step after the slice (the run's last) is not of it
    monkeypatch.setattr(moe_lm, "LAST_RECENT_LOADS", recent_loads(
        [[9000] * 5, [4096] * 5, [4096] * 5, [123] * 5]))
    assert moe_lm.grouped_product_counts(cfg, wl, 2) == [
        (5 * flops, 5 * nbytes)] * 2
    # the compiler's own kernels carry their name as their only path
    ops = [["ragged-dot-none.12", "ragged-dot-none", 0.03, 30],
           ["fusion.9", PRODUCTS.replace("ragged_dot", "mul"), 0.01, 10],
           ["fusion.2", PRODUCTS.replace("moe_experts", "moe_route"), 0.2, 10]]
    args = run.load_json("metrics", "moe_experts_roofline_pct.json")["args"]
    got = named_ops.kernel_share(a_run(ops, 2), **args)
    assert got == pytest.approx(100 * 2 * 5 * (flops / 197e12) / 0.04)
    assert 14.5 < got < 15 and flops / 197e12 > nbytes / 819e9
    # the time metrics count the kernels too: by name, the rest by path
    ms = {name: named_ops.ms_per_step(a_run(ops, 2), **run.load_json(
        "metrics", name + ".json")["args"])
        for name in ("moe_ms", "moe_experts_ms")}
    assert ms == pytest.approx({"moe_ms": 120.0, "moe_experts_ms": 20.0})
    route = run.load_json("metrics", "moe_route_ms.json")["args"]
    assert scope.ms_per_step(a_run(ops, 2), **route) == pytest.approx(100.0)
    # nothing to read: a program without the scope or the kernels (the
    # parent), no trace
    assert named_ops.ms_per_step(a_run(
        [["fusion.1", "jit(step)/forward_backward/jvp()/mlp/dot", 1.0, 1]],
        1), contains=["moe"], names=["ragged-dot"]) is None
    assert named_ops.kernel_share({**a_run(ops, 2), "trace": None},
                                  **args) is None


def test_the_grouped_products_are_counted_at_the_pairs_of_the_traced_steps(
        monkeypatch):
    """The numerator and the denominator are of the same steps. A run
    whose steps differ: in the three whole steps of the slice the held
    expert of every layer got 8,000, 0 and 16,000 pairs, in the run's
    last step (which the slice leaves out) 60,000, in the steps before
    the slice 5. Each step is counted at its own pairs and at the ONE
    expert's weights that were read, a starved step at nothing; a run
    that kept no loads, or fewer steps' than the slice holds, reads as
    nothing, never as the expectation."""
    from chipbench import flops_moe_lm, run
    from chipbench.families import moe_lm
    from chipbench.readers import named_ops

    cfg, wl = files()
    ops = [["ragged-dot-none.12", "ragged-dot-none", 0.06, 45]]
    args = run.load_json("metrics", "moe_experts_roofline_pct.json")["args"]
    assert moe_lm.grouped_product_counts(cfg, wl, 3) is None
    assert named_ops.kernel_share(a_run(ops, 3), **args) is None
    monkeypatch.setattr(moe_lm, "LAST_RECENT_LOADS", recent_loads(
        [[5] * 5] * 4 + [[8000] * 5, [0] * 5, [16000] * 5, [60000] * 5]))
    by_step = moe_lm.grouped_product_counts(cfg, wl, 3)
    assert by_step == [
        flops_moe_lm.grouped_product_counts(cfg, [n] * 5, [used] * 5)
        for n, used in ((8000, 1), (0, 0), (16000, 1))]
    assert by_step[1] == (0, 0)
    assert by_step[2][0] == 5 * 9 * 16000 * 2 * 2048 * 768
    got = named_ops.kernel_share(a_run(ops, 3), **args)
    assert got == pytest.approx(
        100 * (by_step[0][0] + by_step[2][0]) / 197e12 / 0.06)
    # the mean of all the run's steps over the slice's time, which is
    # what the metric was before, would have read otherwise
    assert 28 < got < 29
    # sixteen steps are kept: a slice of sixteen would need seventeen
    assert moe_lm.grouped_product_counts(cfg, wl, 15) is not None
    assert moe_lm.grouped_product_counts(cfg, wl, 16) is None


@pytest.mark.parametrize("metric,planted", [
    ("mla_kernel_roofline_pct", "operations"),
    ("mla_kernel_roofline_pct", "bytes"),
    ("moe_experts_roofline_pct", "operations"),
    ("moe_experts_roofline_pct", "bytes"),
    ("moe_experts_roofline_pct", "pairs")])
def test_a_count_that_is_too_high_reads_over_100_percent(metric, planted,
                                                         monkeypatch):
    """Nothing in either reader holds a share under 100%: the same trace
    with one of the family's counts ten (the kernel's bytes a hundred)
    times too high reads well over it, which is what the driver refuses."""
    import importlib

    from chipbench import run
    from chipbench.families import moe_lm

    m = run.load_json("metrics", metric + ".json")
    module, fn = m["reader"].rsplit(".", 1)
    reader = getattr(importlib.import_module("chipbench.readers." + module), fn)
    name = m["args"]["counts"]
    ops = [["flash_fwd_q512_k512.3", KERNEL, 0.05, 6],
           ["ragged-dot-none.12", "ragged-dot-none", 0.02, 15]]
    monkeypatch.setattr(moe_lm, "LAST_RECENT_LOADS",
                        recent_loads([[8000] * 5] * 2))
    steps = (1,) if metric == "moe_experts_roofline_pct" else ()
    sound = getattr(moe_lm, name)(*files(), *steps)
    factor = 100 if planted == "bytes" else 10
    assert 20 < reader(a_run(ops, 1), **m["args"]) < 100
    if planted == "pairs":  # the program's counter reads ten times too many
        monkeypatch.setattr(moe_lm, "LAST_RECENT_LOADS",
                            recent_loads([[80000] * 5] * 2))
        family = moe_lm
    else:
        at = {"operations": 0, "bytes": 1}[planted]
        scale = lambda c: tuple(factor * v if i == at else v
                                for i, v in enumerate(c))
        high = [scale(c) for c in sound] if steps else scale(sound)
        family = types.SimpleNamespace(**{name: lambda *a: high})
    assert reader(a_run(ops, 1, family=family), **m["args"]) > 105


def rehearsal_trainer(seed: int = 5):
    import jax

    from chipbench import run
    from chipbench.families import moe_lm
    from tpu_syncbn import parallel, runtime

    cfg = run.rehearsal(files()[0])
    dp = parallel.DataParallel(
        moe_lm.build_model(cfg, jax.random.key(seed)), moe_lm.optimizer(cfg, 2),
        moe_lm.loss_fn, mesh=runtime.data_parallel_mesh(1))
    pool = moe_lm.make_pool(cfg, 2, np.random.default_rng(seed))
    return cfg, dp, jax.device_put(moe_lm.transform(cfg)(pool),
                                   dp.batch_sharding)


def test_correct_opens_the_expert_layer_that_holds_most_pairs():
    """Of the stack's expert layers the comparison opens the one whose
    held experts were chosen most often, on the program's own input of
    it, and the reference reads that layer's parameters: with the
    selection bias of layer 0 set against its held experts it is layer
    1, and every error is still small."""
    import jax
    from flax import nnx

    from chipbench import correct
    from chipbench.families import moe_lm

    cfg, dp, batch = rehearsal_trainer()
    held = slice(cfg["first_expert"],
                 cfg["first_expert"] + cfg["n_routed_experts"])

    def compare(bias):
        rest = correct.pure(dp.rest)
        rest["sparse"]["bias"] = bias
        nnx.replace_by_pure_dict(dp.rest, rest)
        got = correct.program_outputs(dp, moe_lm, batch)
        ref = jax.jit(moe_lm.reference_fn(cfg))(correct.pure(dp.params),
                                                batch, got)
        return got, {k: float(v) for k, v in ref["errors"].items()}

    bias = np.zeros((2, cfg["router_experts"]), np.float32)
    bias[0, held] = -10.0
    got, errors = compare(bias)
    assert int(got["opened"][0]) == 1
    assert float(np.sum(got["load"][0][held])) > 0
    assert max(errors.values()) < 1e-5, errors
    bias[0, held], bias[1, held] = 0.0, -10.0
    got, errors = compare(bias)
    assert int(got["opened"][0]) == 0 and max(errors.values()) < 1e-5


def test_a_stack_whose_held_experts_get_no_pair_is_still_compared():
    """Every expert layer's selection bias set against the experts held:
    no pair arrives, the share of held pairs not computed is 0 of one
    pair (not 0 / 0), and the run is ``correct``."""
    import jax
    from flax import nnx

    from chipbench import correct
    from chipbench.families import moe_lm

    cfg, dp, batch = rehearsal_trainer()
    held = slice(cfg["first_expert"],
                 cfg["first_expert"] + cfg["n_routed_experts"])
    rest = correct.pure(dp.rest)
    for block in ("sparse", "mtp_block"):
        rest[block]["bias"] = rest[block]["bias"].at[:, held].set(-10.0)
    nnx.replace_by_pure_dict(dp.rest, rest)
    got = correct.program_outputs(dp, moe_lm, batch)
    assert float(np.sum(got["load"][0][held])) == 0.0
    ref = jax.jit(moe_lm.reference_fn(cfg))(correct.pure(dp.params), batch, got)
    errors = {k: float(v) for k, v in ref["errors"].items()}
    assert errors["pairs_not_computed"] == 0.0
    assert all(np.isfinite(v) and v < 1e-5 for v in errors.values()), errors
    assert correct.verdict(errors, [5.0], np.zeros(3), np.ones(3),
                           moe_lm)["correct"]


def test_the_backward_scan_the_head_and_the_recomputation_by_their_paths():
    """A hand-made slice of one step: the forward kernel call, its
    recomputed call and the layout change beside it, the backward scan's
    products, the head forward, recomputed and backward, a recomputed
    matrix of the latent attention. ``mla_attention_ms`` holds the
    core's all; the backward scan's metric only what is neither a kernel
    call nor run again."""
    from chipbench import run
    from chipbench.readers import scope

    back = KERNEL.replace("jvp()", "transpose(jvp())")
    remat = back.replace("checkpoint/", "checkpoint/rematted_computation/")
    scan = back.replace("flash_fwd_q512_k512/pallas_call",
                        "while/body/dot_general")
    head = "jit(step)/forward_backward/jvp(lm_head)/dot_general"
    ops = [["flash_fwd_q512_k512.3", KERNEL, 0.036, 6],
           ["flash_fwd_q512_k512.4", remat, 0.038, 6],
           ["copy.5", remat.replace("flash_fwd_q512_k512/pallas_call",
                                    "transpose"), 0.006, 6],
           ["fusion.70", scan, 0.400, 96],
           ["fusion.71", scan.replace("dot_general", "exp"), 0.038, 96],
           ["fusion.80", head, 0.008, 2],
           ["fusion.81", "jit(step)/forward_backward/transpose(jvp())/"
            "checkpoint/rematted_computation/lm_head/dot_general", 0.0078, 2],
           ["fusion.82", head.replace("jvp(lm_head)",
                                      "transpose(jvp(lm_head))"), 0.0134, 2],
           ["fusion.90", remat.replace(
               "attention/flash_fwd_q512_k512/pallas_call", "dot_general"),
            0.0277, 6]]

    def read(name):
        return scope.ms_per_step(a_run(ops, 1), **run.load_json(
            "metrics", name + ".json")["args"])

    assert read("mla_attention_ms") == pytest.approx(518.0)
    assert read("mla_backward_scan_ms") == pytest.approx(438.0)
    assert read("moe_lm_head_ms") == pytest.approx(29.2)
    assert read("moe_lm_recompute_ms") == pytest.approx(
        38.0 + 6.0 + 7.8 + 27.7)
    # a program without the scopes (the parent): nothing, and no error
    other = [["fusion.1", "jit(step)/forward_backward/jvp()/mlp/dot", 1.0, 1]]
    for name in ("mla_backward_scan_ms", "moe_lm_head_ms",
                 "moe_lm_recompute_ms"):
        m = run.load_json("metrics", name + ".json")
        assert scope.ms_per_step(a_run(other, 1), **m["args"]) is None
        assert m["when"] == {"config": [CONFIG]}


def test_the_load_metric_reads_what_the_family_kept():
    from chipbench.readers import moe

    even = np.full((4, 256), 96.0)
    uneven = even.copy()
    uneven[2, 7] = 960.0
    family = types.SimpleNamespace(LAST_LOADS=[uneven, even[:1]])
    worst = 960.0 / uneven[2].mean()
    assert moe.load_max_over_mean({"family": family}) == pytest.approx(worst)
    assert moe.load_max_over_mean(
        {"family": types.SimpleNamespace(LAST_LOADS=[even])}) == 1.0
    # a family that keeps none (every other one), or was never asked
    assert moe.load_max_over_mean({"family": types.SimpleNamespace()}) is None
    assert moe.load_max_over_mean(
        {"family": types.SimpleNamespace(LAST_LOADS=[])}) is None


def test_the_token_pool_is_zipf_over_the_slice_and_holds_two_more_tokens():
    from chipbench import run
    from chipbench.families import moe_lm

    cfg = {**files()[0], "seq_len": 512}
    tokens, nxt, after = moe_lm.make_pool(cfg, 8, np.random.default_rng(5))
    assert tokens.shape == nxt.shape == after.shape == (8, 512)
    assert np.array_equal(tokens[:, 1:], nxt[:, :-1])
    assert np.array_equal(nxt[:, 1:], after[:, :-1])
    assert tokens.min() >= 0 and after.max() < cfg["vocab_size"] == 16160
    # exponent 1 over 16,160 ids: id 0 is a tenth of the tokens, and the
    # first ten ids are a little over a quarter
    share = np.mean(tokens == 0)
    assert 0.07 < share < 0.13 and 0.22 < np.mean(tokens < 10) < 0.36
    again = moe_lm.make_pool(cfg, 8, np.random.default_rng(5))
    assert np.array_equal(again[0], tokens)
    assert run.rehearsal(files()[1])["per_chip_batch"] == 2


def test_the_lower_precision_controls_through_the_committed_table(capsys):
    """``chipbench/controls_moe_lm.py`` at the rehearsal sizes (float32
    compute): the program against the reference as it is is ``correct``;
    against the reference with 8-bit products, with the router in
    bfloat16 (told by ``router``, ``loads`` and ``moe`` on the program's
    own router input) and with the loss in bfloat16 (``cross_entropy``)
    it is not, each through ``correct.verdict`` and the committed table.
    The attention's scores and softmax in bfloat16 move ``attention``,
    the number that no layer has amplified, from 0 to 2e-3, over its
    limit of 1.2e-3 (which lies between the chip's 5.5e-4 as configured
    and its 2.8e-3 under this control, PERF.md section 6), and nothing
    else over its own: every control fails, so ``ok``."""
    from chipbench import controls_moe_lm
    from chipbench.families import moe_lm

    rc = controls_moe_lm.main(["--workload", CELL, "--seed", "2147483693"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    variants = result["variants"]
    assert set(variants) == {"as_configured", *controls_moe_lm.CONTROLS}
    assert set(controls_moe_lm.CONTROLS) == {
        "fp8_products", "bf16_softmax", "bf16_router", "bf16_loss"}
    assert result["tolerances"]["router"] == moe_lm.TOLERANCES["router"]
    assert variants["as_configured"]["correct"] is True
    assert max(variants["as_configured"]["errors"].values()) < 1e-5
    for name in ("fp8_products", "bf16_router", "bf16_loss"):
        assert variants[name]["correct"] is False, name
    assert {"attention", "head", "layer1", "moe", "z_main", "logits_mtp"} <= \
        set(variants["fp8_products"]["out_of_tolerance"])
    # the router is float32 on both sides under the 8-bit products
    assert variants["fp8_products"]["errors"]["router"] < 1e-5
    assert {"router", "loads", "moe"} <= set(
        variants["bf16_router"]["out_of_tolerance"])
    assert variants["bf16_router"]["errors"]["attention"] < 1e-5
    assert "cross_entropy" in variants["bf16_loss"]["out_of_tolerance"]
    softmax = variants["bf16_softmax"]
    assert softmax["out_of_tolerance"] == ["attention"]
    assert 1.2e-3 < softmax["errors"]["attention"] < 3e-3
    assert softmax["errors"]["head"] == 0.0
    assert softmax["errors"]["router"] < 1e-5
    assert result["ok"] is True and rc == 0


def test_the_configuration_keeps_every_published_number_but_three():
    """The catalog's ``config`` of JoyAI-LLM-Flash, key by key: the
    depth, the experts held and the vocabulary are cut and ``reduced``
    says so; no width is."""
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280,
    }
    cfg = files()[0]
    differs = [k for k in cfg["reduced"] if cfg[k] != published[k]]
    assert sorted(k for k, v in published.items() if cfg[k] != v) == \
        sorted(differs)
    assert cfg["reduced"] == differs == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cfg["published"] == {k: published[k] for k in cfg["reduced"]}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 16, 16160)
    # the floors of a model_config cut: a dense layer and four that
    # follow it, eight experts or more, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= published["vocab_size"]
    # the router keeps its published width and its experts a token
    assert cfg["router_experts"] == published["n_routed_experts"]
    assert cfg["first_expert"] + cfg["n_routed_experts"] <= 256
    assert cfg["deployment"] and set(cfg["assumed"]) >= {
        "bias_update_gamma", "aux_loss", "mtp_loss_weight",
        "mtp_input_order", "init_std", "optimizer", "precision", "seq_len",
        "epoch_images", "data", "attn_impl"}
    # what is set here and not published is listed as assumed
    assert (cfg["bias_update_gamma"], cfg["mtp_loss_weight"],
            cfg["token_zipf_exponent"]) == (0.001, 0.3, 1.0)


def test_the_family_refuses_what_the_model_does_not_do():
    from chipbench.families import moe_lm

    cfg = files()[0]
    assert moe_lm.model_kwargs(cfg)["n_experts"] == 256
    assert moe_lm.model_kwargs(cfg)["experts_held"] == 16
    for key, value in (("n_group", 8), ("scoring_func", "softmax"),
                       ("rope_scaling", {"type": "yarn"}),
                       ("num_key_value_heads", 8),
                       ("num_nextn_predict_layers", 2)):
        with pytest.raises(ValueError):
            moe_lm.model_kwargs({**cfg, key: value})


def test_lowered_train_step_carries_the_scope_names():
    """The scopes the per-layer metrics read are in the program the
    trainer compiles, forward, recomputed and backward, the mixture's
    three inside ``moe`` and the core inside ``mla``; the prediction
    module's layer carries them inside ``mtp``."""
    import jax

    from chipbench import run
    from chipbench.families import moe_lm
    from tpu_syncbn import parallel, runtime

    cfg = run.rehearsal(files()[0])
    dp = parallel.DataParallel(
        moe_lm.build_model(cfg, jax.random.key(5)), moe_lm.optimizer(cfg, 2),
        moe_lm.loss_fn, mesh=runtime.data_parallel_mesh(1))
    pool = moe_lm.make_pool(cfg, 2, np.random.default_rng(5))
    batch = jax.device_put(moe_lm.transform(cfg)(pool), dp.batch_sharding)
    text = dp.lowered_train_step(batch).as_text(debug_info=True)
    # (the lowered text names a scan's body apart from the scan, and a
    # transformation wraps the outermost scope: jvp(mtp))
    for scope in ("forward_backward/", "mla/attention", "/mlp",
                  "moe/moe_route", "moe/moe_shared", "moe/while",
                  "moe_experts/ragged_dot", "jvp(mtp)/while/body",
                  "transpose(jvp(mtp))", "jvp(lm_head)",
                  "rematted_computation/lm_head"):
        assert scope in text, scope
    # every file of a by-scope metric names scopes that are there
    # (the rehearsal's attention is XLA's: no pallas_call on its path)
    for name in ("mla_attention_ms", "moe_ms", "moe_route_ms",
                 "moe_experts_ms", "mtp_ms", "mla_backward_scan_ms",
                 "moe_lm_head_ms", "moe_lm_recompute_ms"):
        m = run.load_json("metrics", name + ".json")
        assert m["reader"] in ("scope.ms_per_step", "named_ops.ms_per_step")
        assert "path of ONE of its instructions" in m["description"]
        for part in m["args"]["contains"] + m["args"].get("excludes", []):
            assert (part + "/" in text or part + ")" in text
                    or part.endswith("(") and part in text
                    or part == "pallas_call"), (name, part)
