"""The looped language-model family and its cell
(``ouro-l8-train-b2x2048``), rehearsed on the CPU at the rehearsal sizes
of the two files: the command end to end, traced and untraced; what makes
``correct`` false; the closed-form operation count against a count by
hand.
"""

from __future__ import annotations

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "ouro-l8-train-b2x2048"
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# read by scope path, which the CPU's trace does not carry; mfu needs a
# peak, which the CPU has not
CHIP_ONLY = {"forward_ms", "backward_ms", "loop_stack_ms", "attention_ms",
             "lm_head_ms", "recompute_ms", "attention_roofline_pct", "mfu"}


def run_cell(capsys, trace: int, seed: int = 2147483693):
    from chipbench import run

    rc = run.main(["--workload", CELL, "--seed", str(seed),
                   "--seconds", "1", "--trace", str(trace)])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), [json.loads(x) for x in out[:-1]]


def test_untraced_run_is_correct_and_reports_its_end_to_end_metrics(capsys):
    from chipbench.families import looped_lm

    rc, line, earlier = run_cell(capsys, trace=0)
    assert rc == 0 and set(line) == LINE_KEYS
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 10
    # no step_ms_p95: one set of six on the chip spread by 0.9% (PERF.md
    # section 6, PR 30); the observations line still carries it
    assert set(line["metrics"]) == {"img_s_chip", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert earlier[-1]["observations"]["step_ms_p95_single"] > 0
    check = earlier[-1]["check"]
    names = {"layer1", "attention", "head", "cross_entropy", "exit_p",
             "loss"} | {
        f"{n}_{t}" for n in ("z", "logits", "pass_loss") for t in (1, 2, 3, 4)}
    assert set(check["errors"]) == names
    # float32 in the rehearsal: the reference agrees closely
    assert all(v < 1e-5 for v in check["errors"].values()), check["errors"]
    # every limit is the family's own, but the first step's loss, which
    # cannot tell a precision and keeps the accepted cells' limit
    from chipbench import correct

    assert check["tolerances"] == {
        **{k: looped_lm.TOLERANCES[k] for k in names - {"loss"}},
        "loss": correct.LOSS_TOL}
    assert check["stats_moved_share"] == 1.0


def test_traced_run_prints_the_per_layer_metrics_the_cell_owes(capsys):
    rc, line, earlier = run_cell(capsys, trace=1)
    assert rc == 0 and earlier[-1]["traced_steps"] > 4
    assert set(line) == LINE_KEYS | {"breakdown"} and line["correct"] is True
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        owed = {m["name"] for m in json.load(f)["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert CHIP_ONLY & owed >= {"loop_stack_ms", "attention_ms", "lm_head_ms",
                                "recompute_ms", "attention_roofline_pct"}
    assert set(line["metrics"]) == owed - CHIP_ONLY
    assert line["metrics"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("name", ["layer1", "attention", "head",
                                  "cross_entropy", "z_4", "logits_1",
                                  "exit_p", "pass_loss_2"])
def test_an_error_over_the_familys_tolerance_makes_correct_false(name):
    import numpy as np

    from chipbench import correct
    from chipbench.families import looped_lm

    good = {"loss": 1e-5,
            **{k: 0.5 * v for k, v in looped_lm.TOLERANCES.items()}}
    before, after = np.zeros(17), np.ones(17)
    assert correct.verdict(good, [11.0, 10.9], before, after,
                           looped_lm)["correct"]
    bad = correct.verdict({**good, name: 2 * looped_lm.TOLERANCES[name]},
                          [11.0], before, after, looped_lm)
    assert not bad["correct"] and bad["out_of_tolerance"] == [name]
    # tighter than correct.py's table wherever that one would be looked up
    assert looped_lm.TOLERANCES[name] <= correct.tolerance(name)


def test_closed_form_flops_against_a_count_by_hand():
    from chipbench import flops_lm, run
    from chipbench.families import looped_lm

    cfg = run.rehearsal(run.load_json("configs", "ouro-2.6b-l8.json"))
    # hidden 64, 4 heads of 16, MLP 128, vocabulary 256, 2 layers, 4
    # passes, 32 tokens. A layer's matrices: 4 x 64 x 64 + 3 x 64 x 128 =
    # 40,960 multiply-adds a token; its causal attention: scores and
    # probabilities x values, 2 x 64 x 32 for the square, half of it;
    # the head 64 x 256 a pass.
    assert flops_lm.layer_matmul_macs(cfg) == 40960
    assert flops_lm.attention_macs(cfg) == 2048
    per_token = 4 * 2 * (40960 + 2048) + 4 * 64 * 256
    assert flops_lm.forward_macs_per_token(cfg) == per_token == 409600
    assert looped_lm.train_flops_per_image(cfg) == 3 * 2 * 32 * per_token
    # the published widths at 8 layers and 2,048 tokens: ISSUE 30's count
    full = run.load_json("configs", "ouro-2.6b-l8.json")
    assert flops_lm.layer_matmul_macs(full) == 51_380_224
    assert 2 * flops_lm.forward_macs_per_token(full) == (
        32 * (2 * 51_380_224 + 2 * 2048 * 2048) + 4 * 2 * 100_663_296)
    assert looped_lm.train_flops_per_image(full) == 26_800_595_927_040


def test_the_kernels_roofline_share_from_a_hand_made_trace():
    """Two steps. Under ``attention/pallas_call``: the forward call and
    its recomputation, 0.16 s in all, and a neighbour's reduction that
    carries the kernel's path, 0.0008 s; one operation under another
    path. The share is the least time of the 2 x 64 calls that the
    closed form counts over 0.1608 s, whatever the trace says of
    executions. 36.5 GFLOP a call at 197 TFLOP/s is 0.185 ms, the 67 MB
    it moves at 819 GB/s 0.082 ms, so operations bound it."""
    from chipbench import flops_lm, run
    from chipbench.families import looped_lm
    from chipbench.readers import roofline

    cfg = run.load_json("configs", "ouro-2.6b-l8.json")
    wl = run.load_json("workloads", CELL + ".json")
    flops, nbytes = flops_lm.flash_forward_counts(cfg, 2)
    # 16 x 17 / 2 = 136 tile pairs, two products of 128 x 128 x 128 each
    assert flops == 2 * 16 * 136 * 2 * 2 * 128 ** 3
    assert nbytes == 2 * 16 * 2048 * (4 * 128 * 2 + 4)
    # 8 layers x 4 passes, forward and recomputed
    assert flops_lm.flash_forward_calls_per_step(cfg) == 64
    assert looped_lm.attention_kernel_counts(cfg, wl) == (flops, nbytes, 64)
    kernel = "jit(step)/forward_backward/jvp()/loop_stack/attention/pallas_call"
    trace = {"steps": 2, "ops": [
        ["attention.1", kernel, 0.09, 64],
        ["attention.2", kernel.replace("jvp()", "transpose(jvp())/checkpoint/"
                                       "rematted_computation"), 0.07, 64],
        ["reduce.11", kernel, 0.0008, 64],
        ["fusion.3", "jit(step)/forward_backward/jvp()/loop_stack/attention/"
                     "dot_general", 0.5, 64],
        ["while.1", None, 1.0, 2]]}
    a_run = {"trace": trace, "family": looped_lm, "cfg": cfg, "wl": wl,
             "device": {"kind": "TPU v5 lite"},
             "peaks": run.load_json("peaks.json")}
    args = run.load_json("metrics", "attention_roofline_pct.json")["args"]
    got = roofline.kernel_share(a_run, **args)
    assert got == pytest.approx(100 * 2 * 64 * (flops / 197e12) / 0.1608)
    assert 14 < got < 15
    # nothing to read: XLA's attention, no trace, a device without a peak
    no_kernel = {**a_run, "trace": {"steps": 2, "ops": trace["ops"][3:]}}
    assert roofline.kernel_share(no_kernel, **args) is None
    assert roofline.kernel_share({**a_run, "trace": None}, **args) is None
    assert roofline.kernel_share({**a_run, "device": {"kind": "cpu"}},
                                 **args) is None


@pytest.mark.parametrize("planted", ["operations", "bytes", "calls"])
def test_a_count_that_is_too_high_reads_over_100_percent(planted):
    """Nothing in the reader holds the share under 100%: the same trace
    with one of the family's three counts ten (the bytes thirty) times
    too high reads well over it, which is what the driver refuses."""
    import types

    from chipbench import run
    from chipbench.families import looped_lm
    from chipbench.readers import roofline

    cfg = run.load_json("configs", "ouro-2.6b-l8.json")
    wl = run.load_json("workloads", CELL + ".json")
    flops, nbytes, calls = looped_lm.attention_kernel_counts(cfg, wl)
    counts = {"operations": (10 * flops, nbytes, calls),
              "bytes": (flops, 30 * nbytes, calls),
              "calls": (flops, nbytes, 10 * calls)}[planted]
    family = types.SimpleNamespace(attention_kernel_counts=lambda c, w: counts)
    kernel = "jit(step)/forward_backward/jvp()/loop_stack/attention/pallas_call"
    a_run = {"trace": {"steps": 1, "ops": [["attention.1", kernel, 0.1, 64]]},
             "family": family, "cfg": cfg, "wl": wl,
             "device": {"kind": "TPU v5 lite"},
             "peaks": run.load_json("peaks.json")}
    args = run.load_json("metrics", "attention_roofline_pct.json")["args"]
    sound = roofline.kernel_share({**a_run, "family": looped_lm}, **args)
    assert sound == pytest.approx(100 * 64 * (flops / 197e12) / 0.1)
    assert roofline.kernel_share(a_run, **args) > 105 > sound


def test_the_lower_precision_controls_through_the_committed_table(capsys):
    """``chipbench/controls_lm.py`` at the rehearsal sizes (float32
    compute): the program against the reference as it is is ``correct``;
    against the reference with 8-bit products and with the loss in
    bfloat16 (told by ``cross_entropy``, position by position on the
    program's own z) it is not, each through ``correct.verdict`` and the
    committed table. The attention's scores and softmax in bfloat16 move
    ``attention``, the number that no layer has amplified, from 0 to
    2e-3 and nothing else over its limit: the limit (3.3e-3) lies
    between what the chip reads at the timed sizes in bfloat16 as
    configured (2.6e-3) and under this control (PERF.md section 6), so
    here the control still passes and ``ok`` is the chip's to say."""
    from chipbench import controls_lm
    from chipbench.families import looped_lm

    rc = controls_lm.main(["--workload", CELL, "--seed", "2147483693"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    variants = result["variants"]
    assert set(variants) == {"as_configured", *controls_lm.CONTROLS}
    assert result["tolerances"]["attention"] == looped_lm.TOLERANCES["attention"]
    assert variants["as_configured"]["correct"] is True
    assert max(variants["as_configured"]["errors"].values()) < 1e-5
    for name in ("fp8_products", "bf16_loss"):
        assert variants[name]["correct"] is False, name
    assert {"attention", "head", "layer1", "z_1", "logits_4"} <= set(
        variants["fp8_products"]["out_of_tolerance"])
    failed = set(variants["bf16_loss"]["out_of_tolerance"])
    assert "cross_entropy" in failed and all(
        n.startswith("pass_loss_") for n in failed - {"cross_entropy"})
    softmax = variants["bf16_softmax"]["errors"]
    assert 1e-3 < softmax["attention"] < looped_lm.TOLERANCES["attention"]
    assert softmax["head"] == 0.0
    assert variants["bf16_softmax"]["correct"] is True
    assert result["ok"] is False and rc == 1
    # the readings that are no limits: in float32 XLA's attention, the
    # kernel and the kernel's backward agree with the reference
    readings = result["readings"]
    assert readings["xla_attention"] < 1e-5
    assert readings["flash_float32"] < 1e-5
    assert max(readings["flash_backward"].values()) < 1e-5


def test_the_configuration_keeps_every_published_number():
    """The catalog's ``config`` of Ouro-2.6B, key by key; the depth alone
    is cut and ``reduced`` says so."""
    from chipbench import run

    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_hidden_layers": 48,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4,
        "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152, "layer_types": ["full_attention"] * 48,
    }
    cfg = run.load_json("configs", "ouro-2.6b-l8.json")
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 8
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["deployment"] and set(cfg["assumed"]) >= {
        "norm_placement", "gate_input", "exit_distribution", "loss",
        "biases", "init_std", "optimizer", "seq_len", "epoch_images", "data"}
