"""The block-diffusion family and its cell (``sdar-l6-train-b1x4096``),
rehearsed on the CPU at the rehearsal sizes of the two files: the command
end to end, traced and untraced; what makes ``correct`` false; the
closed-form counts against counts by hand; the roofline shares from
hand-made runs; the controls; the configuration against the published
one.
"""

from __future__ import annotations

import json
import os
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "sdar-l6-train-b1x4096"
CONFIG = "sdar-30b-a3b-l6"
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# read by scope path, which the CPU's trace does not carry; mfu needs a
# peak, which the CPU has not
CHIP_ONLY = {"forward_ms", "backward_ms", "bd_attention_ms", "bd_moe_ms",
             "bd_moe_route_ms", "bd_moe_experts_ms", "bd_lm_head_ms",
             "bd_recompute_ms", "bd_attention_roofline_pct",
             "bd_moe_experts_roofline_pct", "mfu"}
ERRORS = {"layer1", "attention", "router", "loads", "pairs_not_computed",
          "moe", "head", "cross_entropy", "z", "logits", "diffusion_loss",
          "aux", "loss"}
CORE = ("jit(step)/forward_backward/jvp()/while/body/closed_call/checkpoint/"
        "gqa/attention/")
FWD = CORE + "flash_fwd_q512_k512/pallas_call"
PRODUCTS = ("jit(step)/forward_backward/jvp()/while/body/closed_call/"
            "checkpoint/moe/while/body/moe_experts/ragged_dot")


@pytest.fixture(autouse=True)
def no_loads_kept_by_an_earlier_run():
    from chipbench.families import block_diffusion_lm as family

    family.LAST_LOADS[:] = family.LAST_RECENT_LOADS[:] = []
    yield
    family.LAST_LOADS[:] = family.LAST_RECENT_LOADS[:] = []


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
    from chipbench.families import block_diffusion_lm

    cfg, wl = files()
    return {"trace": {"steps": steps, "ops": ops},
            "family": family or block_diffusion_lm, "cfg": cfg, "wl": wl,
            "device": {"kind": "TPU v5 lite"},
            "peaks": run.load_json("peaks.json")}


def test_untraced_run_is_correct_and_reports_its_end_to_end_metrics(capsys):
    from chipbench import correct
    from chipbench.families import block_diffusion_lm as family

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
        **{k: family.TOLERANCES[k] for k in ERRORS - {"loss"}},
        "loss": correct.LOSS_TOL}
    assert check["stats_moved_share"] == 1.0  # every parameter leaf moved


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
    # the noising runs on the loader's threads, inside loader.build
    assert 0 < line["metrics"]["bd_noise_ms"]["value"] \
        < line["metrics"]["loader_build_ms"]["value"]
    # 16 experts, 3 a token, Zipf tokens and a mask token: the worst
    # layer's fullest expert is over the mean and under all of it
    assert 1.0 < line["metrics"]["bd_expert_load_max_over_mean"]["value"] < 16


@pytest.mark.parametrize("name", sorted(ERRORS - {"loss"}))
def test_an_error_over_the_familys_tolerance_makes_correct_false(name):
    from chipbench import correct
    from chipbench.families import block_diffusion_lm as family

    good = {"loss": 1e-5,
            **{k: 0.5 * v for k, v in family.TOLERANCES.items()}}
    before, after = np.zeros(15), np.ones(15)
    assert correct.verdict(good, [11.0, 10.9], before, after,
                           family)["correct"]
    over = 2 * family.TOLERANCES[name] or 1 / 8192  # one pair of a layer's
    bad = correct.verdict({**good, name: over}, [11.0], before, after, family)
    assert not bad["correct"] and bad["out_of_tolerance"] == [name]
    assert family.TOLERANCES[name] <= correct.tolerance(name)


def test_closed_form_flops_against_a_count_by_hand():
    from chipbench import flops_block_diffusion_lm as flops
    from chipbench import run
    from chipbench.families import block_diffusion_lm as family

    cfg = run.rehearsal(files()[0])
    # hidden 64, 4 q heads over 2 k/v heads of 16, experts 32 wide, 4 of
    # 16 held, 3 a token, vocabulary 256, 3 layers, 32 tokens in blocks
    # of 4: 64 positions a sequence
    assert flops.gqa_matmul_macs(cfg) == 64 * (64 + 32 + 32 + 64) == 12288
    assert flops.live_scores(cfg) == 32 * 32 + 4 * 32 == 1152
    assert flops.attention_macs_per_sequence(cfg) == 4 * 1152 * 2 * 16
    assert flops.moe_macs(cfg) == 64 * 16 + (3 * 4 / 16) * 3 * 64 * 32 == 5632
    forward = 3 * (64 * (12288 + 5632) + 147456) + 32 * 64 * 256
    assert flops.forward_macs_per_sequence(cfg) == forward
    assert family.train_flops_per_image(cfg) == 3 * 2 * forward
    # the published widths at 6 layers and 4,096 tokens: ISSUE 36's count
    full = files()[0]
    assert flops.gqa_matmul_macs(full) == 18_874_368
    assert flops.live_scores(full) == 16_793_600
    assert flops.attention_macs_per_sequence(full) == 137_573_171_200
    assert flops.moe_macs(full) == 262_144 + 4_718_592
    layer = 8192 * (18_874_368 + 4_980_736) + 137_573_171_200
    assert flops.forward_macs_per_sequence(full) == (
        6 * layer + 4096 * 2048 * 18_992)
    assert 12.9e12 < family.train_flops_per_image(full) < 13.0e12


def test_the_attention_kernels_counts_against_a_count_by_hand():
    """One sequence: 32 heads x 16,793,600 live scores; the forward 2 x
    128 multiply-adds a score, dK/dV 4 x 128, dQ 3 x 128. 8,192 rows: q,
    the output, dO or dq 32 x 128 wide, k, v, dk or dv 4 x 128, in bf16;
    the log-sum-exp and delta a row and q head in float32."""
    from chipbench import flops_block_diffusion_lm as flops
    from chipbench.families import block_diffusion_lm as family

    cfg, wl = files()
    counts = family.attention_kernel_family_counts(cfg, wl)
    assert counts == flops.attention_kernel_counts(cfg, 1)
    scores = 32 * 16_793_600
    wide, narrow, stat = 8192 * 4096 * 2, 8192 * 512 * 2, 8192 * 32 * 4
    assert counts == {
        "flash_fwd": (scores * 2 * 128 * 2, 2 * wide + 2 * narrow + stat),
        "flash_bwd_dkv": (scores * 4 * 128 * 2,
                          2 * wide + 4 * narrow + 2 * stat),
        "flash_bwd_dq": (scores * 3 * 128 * 2,
                         3 * wide + 2 * narrow + 2 * stat)}
    # every one bound by its operations: 1.40, 2.79 and 2.09 ms a call
    for flop, nbytes in counts.values():
        assert flop / 197e12 > 5 * nbytes / 819e9
    assert counts["flash_fwd"][0] / 197e12 == pytest.approx(1.396e-3, rel=1e-3)


def kernel_ops(fwd=0.036, dkv=0.030, dq=0.024, steps=1) -> list:
    """A slice of ``steps`` steps of six layers: the forward kernel's
    call and its recomputation, dK/dV and dQ, a layout change beside
    them that carries the core's path but no ``pallas_call``, and a
    neighbour's reduction that carries a kernel's whole path."""
    back = CORE.replace("jvp()", "transpose(jvp())")
    remat = back.replace("checkpoint/", "checkpoint/rematted_computation/")
    n = 6 * steps
    return [["flash_fwd_q512_k512.3", FWD, fwd * steps / 2, n],
            ["flash_fwd_q512_k512.4",
             remat + "flash_fwd_q512_k512/pallas_call", fwd * steps / 2, n],
            ["flash_bwd_dkv_q512_k512.5",
             back + "flash_bwd_dkv_q512_k512/pallas_call", dkv * steps, n],
            ["flash_bwd_dq_q512_k512.6",
             back + "flash_bwd_dq_q512_k512/pallas_call", dq * steps, n],
            ["copy.7", back + "transpose", 0.006 * steps, n],
            ["reduce.11", FWD, 0.002 * steps, n],
            ["fusion.3", CORE.replace("attention/", "dot_general"), 0.5, n],
            ["while.1", None, 1.0, steps]]


def test_the_kernel_familys_roofline_share_from_a_hand_made_trace():
    """One share over the three kernels: 12 forward calls (6 of them
    recomputed), 6 dK/dV and 6 dQ a step at their least times, over
    everything with gqa, attention and pallas_call in its path (the
    neighbour's reduction too, the layout change not)."""
    from chipbench import run
    from chipbench.families import block_diffusion_lm as family
    from chipbench.readers import kernel_family, scope

    cfg, wl = files()
    counts = family.attention_kernel_family_counts(cfg, wl)
    least = {k: v[0] / 197e12 for k, v in counts.items()}
    args = run.load_json("metrics", "bd_attention_roofline_pct.json")["args"]
    want = 100 * (12 * least["flash_fwd"] + 6 * least["flash_bwd_dkv"]
                  + 6 * least["flash_bwd_dq"]) / (0.036 + 0.030 + 0.024
                                                  + 0.002)
    got = kernel_family.share_by_counted_calls(a_run(kernel_ops(), 1), **args)
    assert got == pytest.approx(want) and 49 < got < 51
    # more steps of the same: the same share
    assert kernel_family.share_by_counted_calls(
        a_run(kernel_ops(steps=3), 3), **args) == pytest.approx(want)
    # a backward that is not these kernels (a scan, say) leaves the
    # forward's share of the forward's time, not a third of it
    forward_only = kernel_ops()[:2]
    assert kernel_family.share_by_counted_calls(
        a_run(forward_only, 1), **args) == pytest.approx(
            100 * 12 * least["flash_fwd"] / 0.036)
    # the time metric holds the layout change too
    ms = run.load_json("metrics", "bd_attention_ms.json")["args"]
    assert scope.ms_per_step(a_run(kernel_ops(), 1), **ms) == pytest.approx(
        36 + 30 + 24 + 6 + 2)
    # nothing to read: XLA's attention, no trace, a device without a
    # peak, a family without the function (the parent's program)
    none = kernel_family.share_by_counted_calls
    assert none(a_run(kernel_ops()[6:], 1), **args) is None
    assert none({**a_run(kernel_ops(), 1), "trace": None}, **args) is None
    assert none({**a_run(kernel_ops(), 1), "device": {"kind": "cpu"}},
                **args) is None
    assert none(a_run(kernel_ops(), 1, family=types.SimpleNamespace()),
                **args) is None


def recent_loads(held_pairs: list) -> list:
    """What ``moving_state`` keeps of a run whose last steps put
    ``held_pairs[step]`` pairs on held expert 3 of each of six layers and
    the rest on expert 100, which is not held: the sixteen steps' loads,
    oldest first, the steps not given empty."""
    recent = np.zeros((6, 16, 128))
    for step, pairs in enumerate(held_pairs, start=16 - len(held_pairs)):
        recent[:, step, 3] = pairs
        recent[:, step, 100] = 65536 - pairs
    return [recent]


def test_the_grouped_products_roofline_share_from_a_hand_made_trace(
        monkeypatch):
    """Two whole steps, 0.04 s under ``moe_experts``, in each of which
    every layer's held expert 3 got 8,192 pairs: a layer 3 matrices x 3
    passes x 8192 x 2 x 2048 x 768 = 232 GFLOP, 1.18 ms; six such layers
    a step."""
    from chipbench import flops_moe_lm, run
    from chipbench.families import block_diffusion_lm as family
    from chipbench.readers import named_ops, scope

    cfg, wl = files()
    flops, nbytes = flops_moe_lm.grouped_product_counts(cfg, [8192], [1])
    assert flops == 9 * 8192 * 2 * 2048 * 768
    # the step after the slice (the run's last) is not of it
    monkeypatch.setattr(family, "LAST_RECENT_LOADS",
                        recent_loads([9000, 8192, 8192, 123]))
    assert family.grouped_product_counts(cfg, wl, 2) == [
        (6 * flops, 6 * nbytes)] * 2
    ops = [["ragged-dot-none.12", "ragged-dot-none", 0.03, 36],
           ["fusion.9", PRODUCTS.replace("ragged_dot", "mul"), 0.01, 12],
           ["fusion.2", PRODUCTS.replace("moe_experts", "moe_route"), 0.2, 12]]
    args = run.load_json("metrics", "bd_moe_experts_roofline_pct.json")["args"]
    got = named_ops.kernel_share(a_run(ops, 2), **args)
    assert got == pytest.approx(100 * 2 * 6 * (flops / 197e12) / 0.04)
    assert 35 < got < 36
    ms = {name: named_ops.ms_per_step(a_run(ops, 2), **run.load_json(
        "metrics", name + ".json")["args"])
        for name in ("bd_moe_ms", "bd_moe_experts_ms")}
    assert ms == pytest.approx({"bd_moe_ms": 120.0, "bd_moe_experts_ms": 20.0})
    route = run.load_json("metrics", "bd_moe_route_ms.json")["args"]
    assert scope.ms_per_step(a_run(ops, 2), **route) == pytest.approx(100.0)
    # a run that kept no loads, or fewer steps' than the slice holds
    monkeypatch.setattr(family, "LAST_RECENT_LOADS", [])
    assert family.grouped_product_counts(cfg, wl, 2) is None
    assert named_ops.kernel_share(a_run(ops, 2), **args) is None
    monkeypatch.setattr(family, "LAST_RECENT_LOADS", recent_loads([5] * 16))
    assert family.grouped_product_counts(cfg, wl, 15) is not None
    assert family.grouped_product_counts(cfg, wl, 16) is None


@pytest.mark.parametrize("metric,planted", [
    ("bd_attention_roofline_pct", "operations"),
    ("bd_attention_roofline_pct", "bytes"),
    ("bd_moe_experts_roofline_pct", "operations"),
    ("bd_moe_experts_roofline_pct", "pairs")])
def test_a_count_that_is_too_high_reads_over_100_percent(metric, planted,
                                                         monkeypatch):
    """Nothing in either reader holds a share under 100%: the same trace
    with one of the family's counts ten (the kernels' bytes a hundred)
    times too high reads well over it, which is what the driver refuses."""
    import importlib

    from chipbench import run
    from chipbench.families import block_diffusion_lm as family

    m = run.load_json("metrics", metric + ".json")
    module, fn = m["reader"].rsplit(".", 1)
    reader = getattr(importlib.import_module("chipbench.readers." + module), fn)
    name = m["args"]["counts"]
    ops = kernel_ops() + [["ragged-dot-none.12", "ragged-dot-none", 0.03, 18]]
    monkeypatch.setattr(family, "LAST_RECENT_LOADS",
                        recent_loads([8192] * 2))
    assert 20 < reader(a_run(ops, 1), **m["args"]) < 100
    if planted == "pairs":  # the program's counter reads ten times too many
        monkeypatch.setattr(family, "LAST_RECENT_LOADS",
                            recent_loads([81920] * 2))
        high_family = family
    elif metric == "bd_attention_roofline_pct":
        at = {"operations": 0, "bytes": 1}[planted]
        factor = 100 if planted == "bytes" else 10
        high = {k: tuple(factor * x if i == at else x
                         for i, x in enumerate(v))
                for k, v in getattr(family, name)(*files()).items()}
        high_family = types.SimpleNamespace(**{name: lambda *a: high})
    else:
        high = [(10 * f, b) for f, b in getattr(family, name)(*files(), 1)]
        high_family = types.SimpleNamespace(**{name: lambda *a: high})
    assert reader(a_run(ops, 1, family=high_family), **m["args"]) > 105


def test_the_head_and_the_recomputation_by_their_paths():
    from chipbench import run
    from chipbench.readers import scope

    head = "jit(step)/forward_backward/jvp(lm_head)/dot_general"
    remat = ("jit(step)/forward_backward/transpose(jvp())/checkpoint/"
             "rematted_computation/")
    ops = kernel_ops() + [
        ["fusion.80", head, 0.004, 1],
        ["fusion.81", remat + "lm_head/dot_general", 0.004, 1],
        ["fusion.82", head.replace("jvp(lm_head)", "transpose(jvp(lm_head))"),
         0.007, 1],
        ["fusion.90", remat + "gqa/dot_general", 0.020, 6]]

    def read(name):
        return scope.ms_per_step(a_run(ops, 1), **run.load_json(
            "metrics", name + ".json")["args"])

    assert read("bd_lm_head_ms") == pytest.approx(15.0)
    assert read("bd_recompute_ms") == pytest.approx(18.0 + 4.0 + 20.0)
    other = [["fusion.1", "jit(step)/forward_backward/jvp()/mlp/dot", 1.0, 1]]
    for name in ("bd_attention_ms", "bd_lm_head_ms", "bd_recompute_ms",
                 "bd_moe_route_ms"):
        m = run.load_json("metrics", name + ".json")
        assert scope.ms_per_step(a_run(other, 1), **m["args"]) is None
        assert m["when"] == {"config": [CONFIG]}


def test_the_load_metric_reads_what_the_family_kept():
    from chipbench import run
    from chipbench.readers import moe

    even = np.full((6, 128), 512.0)
    uneven = even.copy()
    uneven[2, 7] = 5120.0
    m = run.load_json("metrics", "bd_expert_load_max_over_mean.json")
    assert m["reader"] == "moe.load_max_over_mean"
    assert moe.load_max_over_mean(
        {"family": types.SimpleNamespace(LAST_LOADS=[uneven])}
    ) == pytest.approx(5120.0 / uneven[2].mean())
    assert moe.load_max_over_mean(
        {"family": types.SimpleNamespace(LAST_LOADS=[])}) is None


def test_the_pool_is_zipf_below_the_mask_id_and_carries_a_noise_key():
    from chipbench import run
    from chipbench.families import block_diffusion_lm as family

    cfg = {**files()[0], "seq_len": 512}
    tokens, keys = family.make_pool(cfg, 8, np.random.default_rng(5))
    assert tokens.shape == (8, 512) and keys.shape == (8,)
    assert family.mask_id(cfg) == 18991 == cfg["vocab_size"] - 1
    assert tokens.min() >= 0 and tokens.max() < 18991
    # exponent 1 over 18,991 ids: id 0 is a tenth of the tokens
    assert 0.07 < np.mean(tokens == 0) < 0.13
    again = family.make_pool(cfg, 8, np.random.default_rng(5))
    assert np.array_equal(again[0], tokens) and np.array_equal(again[1], keys)
    # the program's own noising is the loader's transform: the noise too
    # comes from the seed, by way of the key
    noise = family.transform(cfg)
    x0, xt, w = noise((tokens[0], keys[0]))
    assert np.array_equal(x0, tokens[0]) and xt.shape == w.shape == (512,)
    assert np.array_equal(noise((tokens[0], keys[0]))[1], xt)
    assert set(np.unique(xt[xt != x0])) == {18991}
    assert np.array_equal(w > 0, xt == 18991) and w.max() <= 1000.0
    assert run.rehearsal(files()[1])["per_chip_batch"] == 2


def test_the_controls_through_the_committed_table(capsys):
    """``chipbench/controls_block_diffusion_lm.py`` at the rehearsal sizes
    (float32 compute): the program against the reference as it is is
    ``correct``; against the reference with 8-bit products, with the
    router or the loss in bfloat16, with a mask that leaks a noisy
    token's own clean block, with a plain causal mask and with q heads
    reading the wrong k/v head it is not, each through
    ``correct.verdict`` and the committed table. The three faults of the
    mask and the group are told by ``attention`` alone among the pieces:
    the reference's core on the program's own q, k and v. The kernels'
    backward (interpreted here) gives the gradients of the reference's
    attention in float32, and not those of a leaking mask or of a wrong
    group."""
    from chipbench import controls_block_diffusion_lm as controls
    from chipbench.families import block_diffusion_lm as family

    rc = controls.main(["--workload", CELL, "--seed", "2147483693"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    variants = result["variants"]
    assert set(variants) == {"as_configured", *controls.CONTROLS}
    assert set(controls.CONTROLS) == {
        "fp8_products", "bf16_softmax", "bf16_router", "bf16_loss",
        "leaking_mask", "causal_mask", "wrong_group"}
    assert result["tolerances"]["attention"] == family.TOLERANCES["attention"]
    assert variants["as_configured"]["correct"] is True
    assert max(variants["as_configured"]["errors"].values()) < 1e-5
    for name in controls.CONTROLS:
        assert variants[name]["correct"] is False, name
    # (the unit-scale embedding is most of the residual stream, so the
    # chain's layer1, z and logits hardly feel a product's precision:
    # the pieces do)
    assert {"attention", "head", "moe", "cross_entropy"} <= set(
        variants["fp8_products"]["out_of_tolerance"])
    # the router is float32 on both sides under the 8-bit products
    assert variants["fp8_products"]["errors"]["router"] < 1e-5
    assert {"router", "loads"} <= set(
        variants["bf16_router"]["out_of_tolerance"])
    assert variants["bf16_router"]["errors"]["attention"] < 1e-5
    assert "cross_entropy" in variants["bf16_loss"]["out_of_tolerance"]
    assert variants["bf16_softmax"]["out_of_tolerance"] == ["attention"]
    for name in ("leaking_mask", "causal_mask", "wrong_group"):
        assert "attention" in variants[name]["out_of_tolerance"], name
        assert variants[name]["errors"]["attention"] > 0.05, name
        # what no mask or group reaches stays put
        assert variants[name]["errors"]["router"] < 1e-5
        assert variants[name]["errors"]["head"] == 0.0
    backward = result["backward"]
    assert set(backward) == {"as_configured", *controls.BACKWARD_CONTROLS}
    assert all(set(r) == set("qkv") for r in backward.values())
    assert max(backward["as_configured"].values()) < 1e-5
    for name in controls.BACKWARD_CONTROLS:
        assert min(backward[name].values()) > 10 * result["backward_limit"]
    assert result["ok"] is True and rc == 0


def test_the_configuration_keeps_every_published_number_but_three():
    """The catalog's ``config`` of SDAR-30B-A3B-Chat, key by key: the
    depth, the experts held and the vocabulary are cut and ``reduced``
    says so; no width is."""
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936,
    }
    cfg = files()[0]
    differs = [k for k in cfg["reduced"] if cfg[k] != published[k]]
    assert sorted(k for k, v in published.items() if cfg[k] != v) == \
        sorted(differs)
    assert cfg["reduced"] == differs == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {k: published[k] for k in cfg["reduced"]}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (6, 16, 18992)
    # the floors of a model_config cut: a whole period (every layer is
    # alike) and at least four, eight experts or more, an eighth of the
    # vocabulary
    assert cfg["num_hidden_layers"] >= 4 and cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= published["vocab_size"]
    # the router keeps its published width and its experts a token
    assert cfg["router_experts"] == published["num_experts"]
    assert cfg["first_expert"] + cfg["num_experts"] <= 128
    assert cfg["deployment"] and set(cfg["assumed"]) >= {
        "block_length", "noise_schedule", "no_shift", "positions", "mask_id",
        "qk_norm", "router_aux_loss_coef", "init_std", "optimizer",
        "precision", "seq_len", "epoch_images", "data", "attn_impl"}
    # what is set here and not published is listed as assumed
    assert (cfg["block_length"], cfg["noise_t_min"], cfg["seq_len"],
            cfg["router_aux_loss_coef"], cfg["init_std"],
            cfg["embed_init_std"]) == (4, 0.001, 4096, 0.001, 0.02, 1.0)


def test_the_family_refuses_what_the_model_does_not_do():
    from chipbench.families import block_diffusion_lm as family

    cfg = files()[0]
    kwargs = family.model_kwargs(cfg)
    assert (kwargs["n_experts"], kwargs["experts_held"]) == (128, 16)
    assert (kwargs["num_heads"], kwargs["num_kv_heads"]) == (32, 4)
    for key, value in (("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
                       ("norm_topk_prob", False),
                       ("rope_scaling", {"type": "yarn"}),
                       ("use_sliding_window", True),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError):
            family.model_kwargs({**cfg, key: value})


def test_lowered_train_step_carries_the_scope_names():
    """The scopes the per-layer metrics read are in the program the
    trainer compiles, forward, recomputed and backward: the core inside
    ``gqa``, the mixture's two inside ``moe``, the head."""
    import jax

    from chipbench import run
    from chipbench.families import block_diffusion_lm as family
    from tpu_syncbn import parallel, runtime

    cfg = run.rehearsal(files()[0])
    dp = parallel.DataParallel(
        family.build_model(cfg, jax.random.key(5)), family.optimizer(cfg, 2),
        family.loss_fn, mesh=runtime.data_parallel_mesh(1))
    pool = family.make_pool(cfg, 2, np.random.default_rng(5))
    noise = family.transform(cfg)
    samples = [noise(tuple(a[i] for a in pool)) for i in range(2)]
    batch = jax.device_put(tuple(np.stack(a) for a in zip(*samples)),
                           dp.batch_sharding)
    text = dp.lowered_train_step(batch).as_text(debug_info=True)
    for scope in ("forward_backward/", "gqa/attention", "moe/moe_route",
                  "moe/while", "moe_experts/ragged_dot", "jvp(lm_head)",
                  "rematted_computation/lm_head",
                  "rematted_computation/gqa/attention"):
        assert scope in text, scope
    # every file of a by-scope metric names scopes that are there (the
    # rehearsal's attention is XLA's: no pallas_call on its path)
    for name in ("bd_attention_ms", "bd_moe_ms", "bd_moe_route_ms",
                 "bd_moe_experts_ms", "bd_lm_head_ms", "bd_recompute_ms",
                 "bd_attention_roofline_pct", "bd_moe_experts_roofline_pct"):
        m = run.load_json("metrics", name + ".json")
        assert "path of ONE of its instructions" in m["description"]
        for part in m["args"]["contains"] + m["args"].get("excludes", []):
            assert (part + "/" in text or part + ")" in text
                    or part == "pallas_call"), (name, part)
