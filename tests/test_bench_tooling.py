"""Pin bench.py's semantics and the benchmark tooling (sweep resume,
block schemas) on CPU.

Reference parity note: the torch recipe has no benchmark tooling (the
reference is a 104-line README); this guards OUR harness (bench.py,
benchmarks/pallas_block_sweep.py).
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(ROOT, "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestBenchSemantics:
    def test_vs_baseline_null_off_tpu(self):
        mod = _load_bench()
        # the TPU line defines the baseline; a fallback line must carry
        # null so it can never read as a hardware baseline ratio
        assert mod._vs_baseline("tpu") == 1.0
        assert mod._vs_baseline("cpu") is None
        assert mod._vs_baseline("METAL") is None

    def test_vs_baseline_reads_published_entry(self, tmp_path):
        """ISSUE 5 satellite: with a published baseline for the metric
        key in BASELINE.json, vs_baseline is the measured/published
        ratio — on any backend (a published number is a real anchor,
        unlike the TPU-defines-itself convention)."""
        mod = _load_bench()
        p = str(tmp_path / "BASELINE.json")
        with open(p, "w") as f:
            json.dump({"published": {
                "m_bare": 200.0,
                "m_dict": {"value": 50.0, "source": "paper table 3"},
            }}, f)
        assert mod._vs_baseline("tpu", "m_bare", 100.0,
                                baseline_path=p) == 0.5
        assert mod._vs_baseline("cpu", "m_dict", 100.0,
                                baseline_path=p) == 2.0
        # a measured 0.0 against a published anchor is a real ratio
        # (flags the regression) — not a fall-through to the historical
        # tpu-defines-itself convention
        assert mod._vs_baseline("tpu", "m_bare", 0.0,
                                baseline_path=p) == 0.0

    def test_vs_baseline_falls_back_without_matching_entry(self, tmp_path):
        mod = _load_bench()
        p = str(tmp_path / "BASELINE.json")
        with open(p, "w") as f:
            json.dump({"published": {"other_metric": 1.0}}, f)
        # no matching key / unusable values -> historical convention
        assert mod._vs_baseline("tpu", "m", 100.0, baseline_path=p) == 1.0
        assert mod._vs_baseline("cpu", "m", 100.0, baseline_path=p) is None
        with open(p, "w") as f:
            json.dump({"published": {"m": 0.0}}, f)  # degenerate baseline
        assert mod._vs_baseline("cpu", "m", 100.0, baseline_path=p) is None
        with open(p, "w") as f:
            f.write('{"trunc')  # corrupt file is loud-logged, never fatal
        assert mod._vs_baseline("tpu", "m", 100.0, baseline_path=p) == 1.0

    def test_repo_baseline_has_no_usable_entry_yet(self):
        """The in-repo BASELINE.json publishes no numbers (the reference
        publishes none) — the shipped line's ratio must keep the
        historical semantics until a published entry lands."""
        mod = _load_bench()
        assert mod._vs_baseline(
            "cpu", "resnet50_syncbn_dp_train_throughput", 123.0
        ) is None


class TestBenchProgramIsDeterministic:
    """A later bench run's first step is a compile-cache hit only if the
    program it builds is byte-identical to the one compiled before."""

    def test_two_constructions_lower_to_identical_hlo(self, monkeypatch):
        # Two independent constructions of the benchmark program must
        # lower to byte-identical HLO — that is what makes one process's
        # compile a persistent-cache hit for a later bench.py process:
        # same HLO + same jit options -> same cache key. Shrunken config
        # so the CPU mesh can trace it.
        monkeypatch.setenv("BENCH_PER_CHIP_BATCH", "1")
        monkeypatch.setenv("BENCH_IMAGE_SIDE", "32")
        bench = _load_bench()
        from tpu_syncbn import runtime

        runtime.initialize()
        cfg = bench.bench_config(True)  # the on-chip config
        texts = []
        for _ in range(2):
            dp, batch, flops = bench.build_program(
                cfg["per_chip_batch"], cfg["side"], with_flops=False
            )
            assert flops is None
            texts.append(dp.lowered_train_step(batch).as_text())
        assert texts[0] == texts[1]


SWEEP_CMD = [
    sys.executable, os.path.join(ROOT, "benchmarks", "pallas_block_sweep.py"),
    "--allow-cpu", "--simulate", "1", "--max-rows", "64", "--iters", "1",
    "--blocks", "128",
]


def _run_sweep(partial, extra=()):
    proc = subprocess.run(
        SWEEP_CMD + ["--partial-out", partial] + list(extra),
        cwd=os.path.join(ROOT, "benchmarks"),
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


@pytest.mark.slow
class TestSweepResume:
    def test_resume_skips_measured_shapes_and_matches(self, tmp_path):
        partial = str(tmp_path / "partial.json")
        first, err1 = _run_sweep(partial)
        assert "resuming" not in err1
        assert first["by_block"] and not first["budget_exhausted"]
        # file is marked complete and carries the config fingerprint
        saved = json.load(open(partial))
        assert saved["partial"] is False and "config" in saved

        second, err2 = _run_sweep(partial)
        assert "resuming" in err2
        assert "compiling" not in err2  # zero re-measurement
        assert second["by_block"] == first["by_block"]

    def test_config_change_invalidates_partial(self, tmp_path):
        partial = str(tmp_path / "partial.json")
        _run_sweep(partial)
        _, err = _run_sweep(partial, extra=["--iters", "2"])
        assert "ignoring" in err and "config changed" in err

    def test_corrupt_partial_is_loud_not_fatal(self, tmp_path):
        partial = str(tmp_path / "partial.json")
        with open(partial, "w") as f:
            f.write('{"trunc')
        out, err = _run_sweep(partial)
        assert "unreadable partial file" in err
        assert out["by_block"]  # sweep still completed from scratch


@pytest.mark.slow
def test_zigzag_flops_benchmark_contract():
    """The zigzag FLOP comparison must report a real reduction (>1) and
    carry the structural prediction beside the measurement."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "zigzag_flops.py"),
         "--simulate", "2", "--seq-per-device", "64"],
        cwd=os.path.join(ROOT, "benchmarks"),
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["reduction_x"] > 1.0
    assert out["predicted_x"] == round(4 * 2 / (2 * 1 + 3), 4)
    assert out["zigzag_flops"] < out["contiguous_flops"]


class TestTelemetryBlock:
    """bench's `telemetry` block and `--trace` output: the schema the
    perf trajectory is read through. Drift here must fail tier-1, not
    silently break later rounds' analysis (ISSUE 2 satellite)."""

    def _tiny_build(self):
        """Stand-in for bench.build_program with the same contract —
        the block's schema, not the ResNet-50 workload, is under test."""
        import jax
        import jax.numpy as jnp
        import optax
        from flax import nnx

        from tpu_syncbn import nn as tnn, parallel

        class Net(nnx.Module):
            def __init__(self, rngs):
                self.fc = nnx.Linear(8, 8, rngs=rngs)
                self.bn = tnn.BatchNorm1d(8)

            def __call__(self, x):
                return self.bn(self.fc(x))

        def build(per_chip_batch, side, *, with_flops=True):
            dp = parallel.DataParallel(
                tnn.convert_sync_batchnorm(Net(nnx.Rngs(0))),
                optax.sgd(0.1), lambda m, b: (m(b) ** 2).mean(),
            )
            batch = jax.device_put(
                jnp.ones((8, 8), jnp.float32), dp.batch_sharding
            )
            return dp, batch, None

        return build

    def test_bench_line_telemetry_and_trace_validate(
        self, tmp_path, monkeypatch, capsys
    ):
        from tpu_syncbn.obs import flightrec, telemetry, tracing

        bench = _load_bench()
        monkeypatch.setenv("BENCH_STEPS", "3")
        monkeypatch.setattr(bench, "build_program", self._tiny_build())
        telemetry.REGISTRY.reset()
        trace = str(tmp_path / "t.json")
        try:
            bench.main(trace_path=trace)
        finally:
            # main() force-enables telemetry, installs a tracer, and
            # arms a flight recorder; restore the suite's ambient state
            telemetry.set_enabled(None)
            telemetry.REGISTRY.reset()
            rec = flightrec.uninstall()
            if rec is not None:
                rec.close()
            tracing.uninstall()
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        # the block validates against the pinned schema...
        tel = telemetry.validate_snapshot(line["telemetry"])
        # ...with nonzero step-time histogram counts (the acceptance bar)
        assert tel["histograms"]["step.time_s"]["count"] == 3
        assert tel["histograms"]["step.data_wait_s"]["count"] == 3
        # checkpoint activity of the run is visible in the block
        assert tel["counters"]["checkpoint.saves"] >= 1
        # the async-writer activity of the recovery block rides the
        # same registry
        assert tel["counters"]["checkpoint.async_saves"] >= 1
        # the scan block is always present (k=1 default: the per-step
        # loop IS the measurement) with the pinned field set
        self._validate_scan_block(line["scan"], k=1)
        # the monitor block is always present (the live-monitoring
        # layer is measured on every run — ISSUE 8)
        self._validate_monitor_block(line["monitor"], steps=3)
        # the audit block is always present (the static-analysis layer
        # measured on the run's own program — ISSUE 10)
        self._validate_audit_block(line["audit"])
        # the memory + compile blocks are always present (the live
        # memory/compile plane measured on the run's own state, with
        # the reconciler fed the audit block's pinned peak — ISSUE 14)
        self._validate_memory_block(
            line["memory"],
            audited_peak=line["audit"]["sharding"]["peak_bytes_per_device"],
        )
        self._validate_compile_block(line["compile"])
        # the incident block is always present (the flight recorder is
        # armed on every run and a manual bundle is forced — ISSUE 11)
        self._validate_incident_block(line["incident"], steps=3)
        # the collectives block is always present (the compressed-
        # collective layer measured per wire mode — ISSUE 12)
        self._validate_collectives_block(line["collectives"])
        # the numerics block is always present (the drift/compression-
        # health monitors published through the timed loop — ISSUE 13)
        self._validate_numerics_block(line["numerics"], steps=3)
        # the autopilot block is always present (the closed-loop
        # controller A/B under an injected numerics fault — ISSUE 17)
        self._validate_autopilot_block(line["autopilot"])
        # the planner block is always present (the contract-driven
        # layout search ranked against reality — ISSUE 19)
        self._validate_planner_block(line["planner"])
        # the layout block is always present (the composed-layout
        # memory/wire claim from traced contracts — ISSUE 20)
        self._validate_layout_block(line["layout"])
        # the serve block is null unless --serve ran the sweep
        assert line["serve"] is None
        # the --trace file is valid Chrome trace JSON with the three
        # span families a step loop produces
        events = tracing.validate_trace(tracing.load_trace(trace))
        names = {e["name"] for e in events}
        assert {"data_wait", "step"} <= names
        assert any(n.startswith("checkpoint") for n in names)

    @staticmethod
    def _validate_scan_block(block, *, k):
        """The schema-pinned `scan` block (ISSUE 4 satellite; pipeline
        bubble fields ISSUE 15): drift here breaks the
        host-dispatch-gap and bubble-fraction trajectories across
        rounds."""
        assert set(block) == {
            "k", "chunks", "host_gap_frac", "host_gap_frac_scan1",
            "dispatch_frac", "dispatch_frac_scan1",
            "img_per_sec_per_chip",
            "pipeline", "bubble_frac_predicted", "bubble_frac_measured",
        }
        assert block["k"] == k
        assert isinstance(block["chunks"], int) and block["chunks"] >= 1
        for key in ("host_gap_frac", "host_gap_frac_scan1",
                    "dispatch_frac", "dispatch_frac_scan1"):
            assert block[key] is None or 0.0 <= block[key] <= 1.5, key
        assert block["img_per_sec_per_chip"] > 0
        # pipeline bubble accounting (measured on every line; the
        # 8-device test mesh always splits into a 2x4 data x pipe mesh)
        pipe = block["pipeline"]
        assert pipe is not None
        assert pipe["n_stages"] >= 2
        assert pipe["n_stages"] * pipe["data_world"] >= 2
        assert pipe["microbatches"] == 2 * pipe["n_stages"]
        assert pipe["dense_step_s"] > 0
        assert 0.0 < pipe["canonical_gpipe_bubble"] < 1.0
        assert set(pipe["schedules"]) == {"gpipe", "1f1b"}
        for name, s in pipe["schedules"].items():
            assert s["ticks"] > 0 and s["step_s"] > 0
            assert 0.0 <= s["bubble_frac_predicted"] < 1.0
            assert s["bubble_frac_measured"] is None \
                or 0.0 <= s["bubble_frac_measured"] <= 1.0, name
        # 1F1B's fused steady state needs strictly fewer ticks than
        # GPipe's flush at M = 2N (the predicted half of the acceptance
        # bound; the measured half is timing and gated generously by
        # BASELINE.json's scan.bubble_frac_measured anchor)
        g, f = pipe["schedules"]["gpipe"], pipe["schedules"]["1f1b"]
        assert f["ticks"] < g["ticks"]
        assert f["bubble_frac_predicted"] < g["bubble_frac_predicted"]
        # the fused K x M chunk ran as ONE compiled program
        assert pipe["fused"]["k"] >= 2
        assert pipe["fused"]["dispatches"] == 1
        assert pipe["fused"]["chunk_s"] > 0
        # the micro-bench's own traced collectives: the ppermute rings
        # live HERE, scoped to the pipeline programs (the incident
        # block's DP contract must not claim them)
        assert pipe["collective_calls"].get("ppermute", 0) >= 2
        # headline fields mirror the shipped default schedule (1f1b)
        assert block["bubble_frac_predicted"] == f["bubble_frac_predicted"]
        assert block["bubble_frac_measured"] == f["bubble_frac_measured"]

    @staticmethod
    def _validate_monitor_block(block, *, steps):
        """The schema-pinned `monitor` block (ISSUE 8): the live
        monitoring layer benchmarked on the run's own metrics —
        exposition fetch latency and windowed-vs-cumulative agreement
        are the acceptance quantities."""
        assert set(block) == {
            "port", "metrics_fetch_s", "exposition_bytes", "series",
            "healthz_ok", "readyz_ok", "windowed_steps",
            "cumulative_steps", "window_agreement",
            "steps_per_s_windowed", "step_p99_s_windowed",
            "slo_burn_rate", "slo_firing",
        }
        assert block["port"] > 0
        assert 0 < block["metrics_fetch_s"] < 30
        assert block["exposition_bytes"] > 0 and block["series"] >= 3
        assert block["healthz_ok"] is True
        assert block["readyz_ok"] is True
        # the delta layer saw exactly the timed loop's steps
        assert block["windowed_steps"] == steps
        assert block["cumulative_steps"] >= steps
        assert block["window_agreement"] is not None
        assert 0 < block["window_agreement"] <= 1.0
        assert block["steps_per_s_windowed"] > 0
        assert block["step_p99_s_windowed"] > 0
        # the liveness-grade SLO (p99 < 60s) holds on a healthy run
        assert block["slo_firing"] is False
        assert block["slo_burn_rate"] is not None

    @staticmethod
    def _validate_collectives_block(block):
        """The schema-pinned `collectives` block (ISSUE 12): per-mode
        traced bytes-on-wire + measured all-reduce time, and the
        golden-pinned compression ratios that BASELINE anchors gate."""
        assert set(block) == {
            "payload_mb_per_chip", "world", "modes", "golden_ratio",
            "measure_s",
        }
        assert block["world"] >= 1
        assert set(block["modes"]) == {
            "fp32", "bf16", "int8", "shuffle_sharded",
        }
        for mode, entry in block["modes"].items():
            assert set(entry) == {
                "wire_bytes", "ms", "gbytes_per_s", "compression_ratio",
            }, mode
            assert entry["ms"] >= 0
        fp32 = block["modes"]["fp32"]["wire_bytes"]
        assert fp32 > 0
        # the wire-dtype arithmetic is exact: bf16 halves, int8 is the
        # s8 payload plus the fp32 range-stat side channel
        assert block["modes"]["bf16"]["wire_bytes"] * 2 == fp32
        assert 3.5 <= block["modes"]["int8"]["compression_ratio"] <= 4.0
        # golden ratios mirror the pinned contracts (the acceptance
        # floors of the ISSUE 12 invariant)
        assert block["golden_ratio"]["bf16"] >= 2.0
        assert block["golden_ratio"]["int8"] >= 3.5

    @staticmethod
    def _validate_numerics_block(block, *, steps):
        """The schema-pinned `numerics` block (ISSUE 13): the drift/
        compression-health layer measured on the run's own monitors —
        the publish-cost bound is a BASELINE anchor (≤2% of step time)
        and the forced drift must yield exactly one valid
        numerics_drift bundle carrying the pre-trigger step ring."""
        assert set(block) == {
            "monitors", "samples", "published", "record_step_cost_s",
            "record_overhead_frac", "drift", "rules",
        }
        # the loop's monitors were published and the skew family landed
        assert block["published"] == steps
        assert block["samples"] >= steps
        mon = block["monitors"]
        assert {"bn_mean_skew", "bn_var_skew", "replica_grad_norm",
                "replica_grad_norm_disp"} <= set(mon)
        for key, value in mon.items():
            assert value is None or value == value, key  # no NaNs
        # the ≤2% steady-state publish-cost acceptance bound
        assert block["record_overhead_frac"] is not None
        assert 0 <= block["record_overhead_frac"] <= 0.02
        # forced drift: exactly ONE schema-valid numerics_drift bundle
        # with the pre-trigger monitor ring
        drift = block["drift"]
        assert drift is not None
        assert drift["bundles"] == 1
        assert drift["trigger"] == "numerics_drift"
        assert drift["valid"] is True
        assert drift["ring_steps"] == steps
        assert block["rules"] == [
            "numerics_residual", "numerics_skew", "numerics_clip",
        ]

    @staticmethod
    def _validate_autopilot_block(block):
        """The schema-pinned `autopilot` block (ISSUE 17): the
        injected-fault A/B — the controller must escalate off int8
        within one evaluation window (2 chunks at the injected 30s
        clock; escalate_within_chunks and advantage_ratio are BASELINE
        anchors), converge while the static arm degrades, and every
        actuation must dump a schema-valid autopilot bundle naming the
        triggering signal."""
        assert block is not None
        assert set(block) == {
            "steps", "fault_gain", "initial_mse", "static_final_mse",
            "autopilot_final_mse", "advantage_ratio",
            "escalate_within_chunks", "first_signal", "modes_visited",
            "final_mode", "actuations", "clamped", "suppressed",
            "bundles",
        }
        # the controller reacted within one evaluation window...
        assert block["escalate_within_chunks"] is not None
        assert 1 <= block["escalate_within_chunks"] <= 2
        assert block["first_signal"] == "numerics_clip"
        # ...escaped int8 (ladder order preserved)...
        assert block["modes_visited"][0] == "int8"
        assert block["final_mode"] in ("bf16", "none")
        assert block["actuations"] >= 1
        # ...and the A/B verdict holds: the controlled arm converges
        # below its start while the static int8 arm ends up clearly
        # worse (the injected fault quantizes its real gradients away)
        assert block["autopilot_final_mse"] < block["initial_mse"]
        assert block["advantage_ratio"] >= 2.0
        # every actuation dumped a schema-valid autopilot bundle
        # quoting the triggering signal
        bundles = block["bundles"]
        assert bundles is not None and bundles["valid"] is True
        assert bundles["count"] == block["actuations"]
        assert all(s == "numerics_clip" for s in bundles["signals"])

    @staticmethod
    def _validate_planner_block(block):
        """The schema-pinned `planner` block (ISSUE 19): the static
        cost model must rank {DP, DP+ZeRO, 1F1B pipeline} in the same
        order the host actually runs them (Kendall tau == 1.0 is the
        ordinal acceptance gate; measured/predicted ratios are
        recorded, never gated), and the planner-backed autopilot A/B
        must escalate off the violated plan with a schema-valid
        plan_change bundle."""
        assert block is not None
        assert set(block) == {
            "world", "batch", "rates", "plan_s", "cache",
            "candidates_feasible", "candidates", "predicted_order",
            "measured_order", "kendall_tau", "autopilot",
        }
        assert set(block["rates"]) == {
            "flop_rate", "wire_rate", "dispatch_s",
        }
        assert block["plan_s"] > 0
        # the restricted surface is exactly the three measured layouts
        assert block["candidates_feasible"] == 3
        assert set(block["candidates"]) == {
            "dp.fp32.k1", "zero.fp32.k1", "pipe.1f1b.n4.m8",
        }
        for name, cand in block["candidates"].items():
            assert set(cand) == {
                "predicted_step_s", "measured_step_s", "ratio",
            }, name
            assert cand["predicted_step_s"] > 0
            assert cand["measured_step_s"] > 0
            # ratio is recorded for cross-round trend reading, not
            # gated: the rates are host-calibrated, not host-exact
            assert cand["ratio"] > 0
        assert sorted(block["predicted_order"]) \
            == sorted(block["measured_order"]) \
            == sorted(block["candidates"])
        # recorded, not gated: the measured order is a timing of three
        # tiny programs on virtual CPU devices, which says nothing about
        # the chip (under jax 0.9 DP's one-all-reduce-per-leaf step runs
        # slower there than ZeRO's, and tau reads 1/3)
        assert -1.0 <= block["kendall_tau"] <= 1.0
        # the planner-backed A/B: top-2 planned layouts, the live
        # plan's measured step time violates its prediction, and the
        # controller escalates with the bundle proof
        ab = block["autopilot"]
        assert set(ab) == {
            "plans", "escalated", "frm", "to", "signal", "switches",
            "bundles",
        }
        assert ab["plans"] == block["predicted_order"][:2]
        assert ab["escalated"] is True
        assert (ab["frm"], ab["to"]) == tuple(ab["plans"])
        assert ab["signal"] == "plan_violation"
        assert ab["switches"] == [ab["to"]]
        assert ab["bundles"] is not None
        assert ab["bundles"]["valid"] is True
        assert ab["bundles"]["count"] == 1

    @staticmethod
    def _validate_layout_block(block):
        """The schema-pinned `layout` block (ISSUE 20): per-device peak
        and traced wire bytes for the same model+optimizer under DP,
        the composed DP×FSDP SpecLayout, and its int8 twin. The two
        ratios are the BASELINE --check-regression anchors; here the
        composition claims themselves are pinned deterministically."""
        assert block is not None
        assert set(block) == {
            "dp", "dp_fsdp", "dp_fsdp_int8", "fsdp_peak_ratio",
            "int8_wire_ratio", "layout_s",
        }
        for kind in ("dp", "dp_fsdp", "dp_fsdp_int8"):
            sub = block[kind]
            assert set(sub) == {
                "world", "peak_bytes_per_device", "wire_bytes_per_device",
            }, kind
            assert sub["world"] == 8
            assert sub["peak_bytes_per_device"] > 0
            assert sub["wire_bytes_per_device"] > 0
        # the memory claim: composed FSDP peak <= 0.6x plain DP (the
        # contract.fsdp_peak_memory invariant, live on the bench line)
        assert block["fsdp_peak_ratio"] <= 0.6
        # the wire claim: int8 keeps compressing on the layout-derived
        # reduce/scatter axes (>= 2x vs the fp32 composed twin)
        assert block["int8_wire_ratio"] >= 2.0
        assert block["layout_s"] > 0

    @staticmethod
    def _validate_incident_block(block, *, steps):
        """The schema-pinned `incident` block (ISSUE 11): the flight
        recorder's forced-trigger bundle — write latency and size are
        BASELINE anchors, the ring must cover the timed loop, the
        per-step recording cost must stay within the 2% steady-state
        bound, and the attribution shares must sum to ~1.0."""
        assert set(block) == {
            "dump_s", "bundle_bytes", "incident_id", "trigger",
            "ring_steps", "ring_seconds", "trace_events",
            "record_step_cost_s", "record_overhead_frac", "attribution",
        }
        assert 0 < block["dump_s"] < 30
        assert block["bundle_bytes"] > 1000
        assert block["trigger"] == "manual"
        assert block["incident_id"].endswith("-manual")
        # the ring held every step of the timed loop (pre-trigger data)
        assert block["ring_steps"] == steps
        assert block["ring_seconds"] >= 0
        assert block["trace_events"] > 0
        # the ≤2% steady-state recorder-overhead acceptance bound
        assert block["record_overhead_frac"] is not None
        assert 0 <= block["record_overhead_frac"] <= 0.02
        attr = block["attribution"]
        assert attr is not None
        assert attr["steps"] >= 1
        assert set(attr["shares"]) == {
            "data_wait", "host_dispatch", "compute", "collective",
        }
        # the attribution acceptance bound: shares sum to 1.0 ± 0.05
        assert abs(attr["share_sum"] - 1.0) <= 0.05
        # per-family collective counts ride the contract (ISSUE 15) —
        # and they are SCOPED to the headline DP program (tallies
        # snapshotted before the pipeline micro-bench traced its
        # ppermute rings; those live in scan.pipeline.collective_calls)
        counts = attr["collective_counts"]
        assert counts and counts.get("psum", 0) >= 1
        assert "ppermute" not in counts

    @staticmethod
    def _validate_memory_block(block, *, audited_peak):
        """The schema-pinned `memory` block (ISSUE 14): live watermarks
        reconciled against the sharding auditor's pinned per-device
        peak, sampler cost (memory.sample_cost_s is a BASELINE anchor),
        the planted mem_pressure drill (exactly one schema-valid bundle
        with pre-trigger watermark history), and a /profilez round
        trip."""
        assert set(block) == {
            "source", "bytes_in_use", "peak_bytes", "rss_bytes",
            "cache_bytes_live", "contract_bytes_per_device",
            "contract_source", "used_frac", "headroom_frac", "samples",
            "sample_cost_s", "sample_overhead_frac", "pressure",
            "profilez",
        }
        assert block["source"] in ("device", "host")
        assert block["bytes_in_use"] >= 0
        assert block["samples"] >= 3  # pre-loop, post-loop, reconcile
        assert 0 <= block["sample_cost_s"] < 1.0
        # the ≤2% steady-state bound is gated by the BASELINE anchor
        # (memory.sample_overhead_frac) on real runs; this tiny-model
        # run has ~ms steps, so a fixed ~100µs census reads inflated —
        # the schema test only pins sanity (fraction present, bounded)
        assert block["sample_overhead_frac"] is not None
        assert 0 <= block["sample_overhead_frac"] <= 0.5
        # the reconciler demonstrably used the audited peak
        assert block["contract_bytes_per_device"] == audited_peak
        assert block["contract_source"] == "sharding_audit"
        assert block["used_frac"] is not None
        assert block["headroom_frac"] is not None
        assert abs(block["used_frac"]
                   - block["bytes_in_use"] / audited_peak) < 1e-3
        assert abs(block["headroom_frac"]
                   - (1.0 - block["used_frac"])) < 1e-3
        # planted drill: exactly ONE schema-valid mem_pressure bundle
        # whose mem ring holds the pre-trigger watermark history
        drill = block["pressure"]
        assert drill is not None
        assert drill["bundles"] == 1
        assert drill["trigger"] == "mem_pressure"
        assert drill["ring_mem"] >= 3
        assert drill["valid"] is True
        # the /profilez round trip answered with a bounded capture
        prof = block["profilez"]
        assert prof is not None
        assert prof["status"] == 200
        assert prof["bytes"] > 0
        assert prof["roundtrip_s"] < 120

    @staticmethod
    def _validate_compile_block(block):
        """The schema-pinned `compile` block (ISSUE 14): compile-seam
        events/time for the run — warmup_s is a BASELINE anchor, the
        first-dispatch latch must have fired, storms read 0 on a
        healthy run."""
        assert set(block) == {
            "warmup_s", "events_total", "storms", "time_s_count",
            "time_s_sum", "families",
        }
        assert block["warmup_s"] > 0
        # the headline program's first dispatch is a compile event
        assert block["events_total"] >= 1
        assert block["families"].get("train", 0) >= 1
        assert block["time_s_count"] >= 1
        assert block["time_s_sum"] > 0
        assert block["storms"] == 0

    @staticmethod
    def _validate_audit_block(block):
        """The schema-pinned `audit` block (ISSUE 10): the static-
        analysis layer run against the bench's own train-step program.
        A healthy run lints clean and propagates with zero implicit
        reshards / zero over-threshold replication."""
        assert set(block) == {
            "files_linted", "lint_violations", "sharding", "audit_s",
        }
        assert block["files_linted"] >= 50
        assert block["lint_violations"] == 0
        assert block["audit_s"] > 0
        sh = block["sharding"]
        assert set(sh) == {
            "collectives_explained", "implicit_reshards",
            "replicated_intermediates", "max_replicated_mb",
            "peak_mb_per_device", "peak_bytes_per_device",
        }
        # the paper's program: at least the BN-stat/grad psums explained
        assert sh["collectives_explained"] >= 1
        assert sh["implicit_reshards"] == 0
        assert sh["replicated_intermediates"] == 0
        assert sh["peak_mb_per_device"] > 0
        # the exact-bytes twin the memory block reconciles against
        assert sh["peak_bytes_per_device"] > 0
        assert (round(sh["peak_bytes_per_device"] / 1e6, 3)
                == sh["peak_mb_per_device"])

    def test_scan_flag_emits_fused_block(self, tmp_path, monkeypatch, capsys):
        """--scan K: the fused K-step loop runs and the scan block
        carries both gap fractions (its own scan-1 baseline rides the
        same line, so the win is a tracked number)."""
        from tpu_syncbn.obs import flightrec, telemetry, tracing

        bench = _load_bench()
        monkeypatch.setenv("BENCH_STEPS", "4")
        monkeypatch.setattr(bench, "build_program", self._tiny_build())
        telemetry.REGISTRY.reset()
        try:
            bench.main(scan=2)
        finally:
            telemetry.set_enabled(None)
            telemetry.REGISTRY.reset()
            rec = flightrec.uninstall()
            if rec is not None:
                rec.close()
            tracing.uninstall()
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        self._validate_scan_block(line["scan"], k=2)
        assert line["scan"]["chunks"] == 2  # 4 steps / K=2
        # the fused dispatch histogram landed in the telemetry block
        tel = telemetry.validate_snapshot(line["telemetry"])
        assert tel["histograms"]["scan.chunk_dispatch_s"]["count"] == 2

    def test_xla_spew_filter_is_armed_before_jax(self):
        """ISSUE 4 satellite: the XLA C++ "host machine features ...
        SIGILL" advisory must be routed off the result stream so the
        JSON line is always the last stdout line. bench.py arms
        TF_CPP_MIN_LOG_LEVEL at import, before anything pulls in jax
        (TSL latches it at first log)."""
        import re

        with open(os.path.join(ROOT, "bench.py")) as f:
            src = f.read()
        setdefault = src.index('os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL"')
        log_stream = src.index(
            'os.environ.setdefault("TPU_SYNCBN_LOG_STREAM"')
        first_jax = re.search(r"^\s*(import jax|from jax)", src,
                              re.MULTILINE)
        first_local = src.index("from _common import")
        assert setdefault < first_local and log_stream < first_local
        assert first_jax is None or setdefault < first_jax.start()
        _load_bench()
        assert os.environ.get("TF_CPP_MIN_LOG_LEVEL") is not None

    def test_trace_flag_requires_path(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "bench.py"), "--trace"],
            capture_output=True, text=True, timeout=60,
        )
        assert proc.returncode != 0
        assert "--trace requires a path" in proc.stderr


@pytest.mark.serve
class TestServeBlock:
    """bench's `serve` block (ISSUE 5): the schema the serving
    trajectory is read through, plus a CPU smoke of the full
    `--serve` closed-loop sweep on a stand-in program."""

    _tiny_build = TestTelemetryBlock._tiny_build

    @staticmethod
    def _validate_serve_block(block):
        """The schema-pinned `serve` block: drift here breaks the
        throughput/latency trajectory across rounds."""
        assert set(block) == {
            "buckets", "max_batch", "max_wait_ms", "warm_compile_s",
            "levels", "clients", "requests", "rejected",
            "throughput_rps", "latency_p50_ms", "latency_p99_ms",
            "fill_ratio", "buckets_compiled", "drained", "open_loop",
            "publish", "tenancy",
        }
        assert isinstance(block["buckets"], list) and block["buckets"]
        assert all(isinstance(b, int) and b >= 1 for b in block["buckets"])
        assert isinstance(block["levels"], list) and len(block["levels"]) >= 2
        for lvl in block["levels"]:
            assert set(lvl) == {
                "clients", "requests", "throughput_rps",
                "latency_p50_ms", "latency_p99_ms", "fill_ratio",
            }
            assert lvl["requests"] >= 1
            assert lvl["throughput_rps"] > 0
            assert 0 < lvl["latency_p50_ms"] <= lvl["latency_p99_ms"]
        # acceptance bounds: nonzero throughput, p50/p99 samples,
        # saturating fill >= 0.9, bounded compiled-program count
        assert block["throughput_rps"] > 0
        assert block["latency_p50_ms"] > 0
        assert block["latency_p99_ms"] >= block["latency_p50_ms"]
        assert block["fill_ratio"] >= 0.9
        assert 1 <= block["buckets_compiled"] <= 4
        assert block["rejected"] >= 0
        assert block["drained"] is True
        # ISSUE 9: the open-loop overload section (null only if that
        # sub-measurement failed — which is itself a failure here)
        ol = block["open_loop"]
        assert ol is not None
        assert set(ol) == {
            "slo_ms", "deadline_ms", "levels", "offered_rps",
            "goodput_rps", "latency_p99_ms", "deadline_miss_rate",
            "shed_rate", "shed", "rejected", "p99_bounded",
            "sheds_rise", "degradation_graceful",
        }
        assert ol["slo_ms"] > 0
        assert isinstance(ol["levels"], list) and len(ol["levels"]) >= 2
        for lvl in ol["levels"]:
            assert set(lvl) == {
                "offered", "offered_rps", "duration_s", "answered",
                "goodput_rps", "latency_p50_ms", "latency_p99_ms",
                "deadline_miss_rate", "shed_rate", "reject_rate",
                "late", "shed", "rejected", "errored", "lost",
                "p99_bounded",
            }
            assert lvl["offered"] >= 1
            assert lvl["lost"] == 0  # every request resolved
        # offered load really swept past saturation...
        assert ol["levels"][-1]["offered_rps"] > \
            ol["levels"][0]["offered_rps"] * 2
        # ...and degradation was graceful: the client-visible p99 stays
        # within the pinned SLO at EVERY level while the overloaded
        # levels shed/reject instead of queueing without bound (the
        # ROADMAP item 4 acceptance regime)
        assert ol["p99_bounded"] is True
        assert ol["sheds_rise"] is True
        assert ol["degradation_graceful"] is True
        # zero-downtime publication drill (null only if that
        # sub-measurement failed — which is itself a failure here)
        pub = block["publish"]
        assert pub is not None
        assert set(pub) == {
            "swap_s", "commit_s", "swap_outcome",
            "requests_during_swap", "baseline_p99_ms",
            "p99_during_swap_ms", "p99_ratio",
            "double_buffer_peak_bytes", "memwatch_contract_bytes",
            "double_buffer_bounded", "rollback_s",
            "rollback_bit_identical",
        }
        assert pub["swap_outcome"] == "swapped"
        assert 0 < pub["commit_s"] <= pub["swap_s"]
        assert pub["requests_during_swap"] >= 1
        assert pub["baseline_p99_ms"] > 0
        assert pub["p99_during_swap_ms"] > 0
        assert pub["p99_ratio"] > 0
        assert pub["double_buffer_peak_bytes"] > 0
        assert pub["double_buffer_bounded"] is True
        # rollback restores the pre-swap version bit-identically,
        # faster than any rebuild could (retained buffers, no compile)
        assert pub["rollback_s"] > 0
        assert pub["rollback_bit_identical"] is True
        # ISSUE 18: the per-tenant SLO isolation drill on labeled
        # metrics (null only if that sub-measurement failed — which is
        # itself a failure here)
        ten = block["tenancy"]
        assert ten is not None
        assert set(ten) == {
            "deadline_ms", "miss_target", "burn_threshold", "tenants",
            "aggressive_burn", "steady_burn", "isolation_ok",
            "alert_bundle",
        }
        assert set(ten["tenants"]) == {"aggressive", "steady"}
        for t in ("aggressive", "steady"):
            assert set(ten["tenants"][t]) == {
                "requests", "deadline_misses", "miss_fraction",
                "latency_p50_ms", "latency_p99_ms", "burn_rate",
                "firing",
            }
            assert ten["tenants"][t]["requests"] >= 1
        # identical rules, asymmetric outcome — carried entirely by the
        # tenant label: aggressive fires past the threshold, steady's
        # twin rule stays quiet on the same evaluation pass
        assert ten["aggressive_burn"] > ten["burn_threshold"]
        assert ten["steady_burn"] is not None \
            and ten["steady_burn"] <= ten["burn_threshold"]
        assert ten["tenants"]["aggressive"]["firing"] is True
        assert ten["tenants"]["steady"]["firing"] is False
        assert ten["isolation_ok"] is True
        # the fired alert's incident bundle carries the labeled series
        assert ten["alert_bundle"] is not None
        assert ten["alert_bundle"]["trigger"] == "slo_alert"
        assert ten["alert_bundle"]["labeled_series"] >= 1

    def test_serve_flag_emits_block_and_line_stays_last(
        self, tmp_path, monkeypatch, capsys
    ):
        from tpu_syncbn.obs import flightrec, telemetry, tracing

        bench = _load_bench()
        monkeypatch.setenv("BENCH_STEPS", "3")
        monkeypatch.setattr(bench, "build_program", self._tiny_build())
        telemetry.REGISTRY.reset()
        try:
            bench.main(serve=True)
        finally:
            telemetry.set_enabled(None)
            telemetry.REGISTRY.reset()
            rec = flightrec.uninstall()
            if rec is not None:
                rec.close()
            tracing.uninstall()
        out_lines = capsys.readouterr().out.strip().splitlines()
        # the JSON result line remains the last stdout line (drivers
        # parse the tail); the sweep's own chatter goes to stderr
        line = json.loads(out_lines[-1])
        self._validate_serve_block(line["serve"])
        # serve activity rides the same telemetry block as everything
        tel = telemetry.validate_snapshot(line["telemetry"])
        assert tel["histograms"]["serve.latency_s"]["count"] >= 1
        assert tel["counters"]["serve.compiles"] >= 1

class TestCheckRegression:
    """bench's `--check-regression` CI gate (ISSUE 8 satellite): the
    emitted line vs BASELINE.json published anchors, with tolerance,
    exit non-zero on regression — vs_baseline stops being informational."""

    _tiny_build = TestTelemetryBlock._tiny_build

    LINE = {
        "metric": "resnet50_syncbn_dp_train_throughput",
        "value": 100.0,
        "serve": {"latency_p99_ms": 12.0},
        "monitor": {"metrics_fetch_s": 0.004},
    }

    def _baseline(self, tmp_path, published):
        p = str(tmp_path / "BASELINE.json")
        with open(p, "w") as f:
            json.dump({"published": published}, f)
        return p

    def _check(self, tmp_path, published, **kw):
        bench = _load_bench()
        return bench.check_regression(
            dict(self.LINE), baseline_path=self._baseline(tmp_path, published),
            **kw,
        )

    def test_within_tolerance_passes(self, tmp_path):
        assert self._check(tmp_path, {
            "resnet50_syncbn_dp_train_throughput": 105.0,  # -4.8% ok
        }, tolerance=0.1) == []

    def test_degraded_headline_metric_fails(self, tmp_path):
        fails = self._check(tmp_path, {
            "resnet50_syncbn_dp_train_throughput": 200.0,  # measured half
        }, tolerance=0.1)
        assert len(fails) == 1 and "below the published" in fails[0]

    def test_lower_is_better_direction(self, tmp_path):
        # latency anchors declare direction=lower: a RISE is a regression
        fails = self._check(tmp_path, {
            "serve.latency_p99_ms": {"value": 6.0, "direction": "lower"},
        })
        assert len(fails) == 1 and "above the published" in fails[0]
        assert self._check(tmp_path, {
            "serve.latency_p99_ms": {"value": 12.5, "direction": "lower"},
        }) == []

    def test_dotted_path_resolution_and_skip(self, tmp_path):
        # a key the line cannot resolve is skipped (e.g. serve metrics
        # on a run without --serve), never a false failure
        assert self._check(tmp_path, {
            "serve.nonexistent_field": 1.0,
            "monitor.metrics_fetch_s": {"value": 0.005,
                                        "direction": "lower"},
        }) == []

    def test_labeled_key_dotted_path_resolution(self, tmp_path):
        """ISSUE 18: a published key may point at a LABELED series in
        the telemetry block — the dots inside the ``{...}`` selector
        are part of the dict key, not path separators, and a component
        that is itself a dotted metric name resolves longest-first."""
        bench = _load_bench()
        line = dict(self.LINE)
        line["telemetry"] = {"counters": {
            'serve.requests{tenant="a"}': 50.0,
            "serve.requests": 80.0,
        }}
        key = 'telemetry.counters.serve.requests{tenant="a"}'
        assert bench._resolve_metric(line, key) == 50.0
        assert bench._resolve_metric(
            line, "telemetry.counters.serve.requests") == 80.0
        # an anchor over the labeled series gates like any other
        assert bench.check_regression(line, baseline_path=self._baseline(
            tmp_path, {key: 50.0})) == []
        fails = bench.check_regression(line, baseline_path=self._baseline(
            tmp_path, {key: 200.0}))
        assert len(fails) == 1 and "below the published" in fails[0]

    def test_per_entry_tolerance_overrides(self, tmp_path):
        published = {"resnet50_syncbn_dp_train_throughput": {
            "value": 104.0, "tolerance": 0.01,
        }}
        fails = self._check(tmp_path, published)  # -3.8% vs 1% tolerance
        assert len(fails) == 1

    def test_unusable_baseline_is_a_failure(self, tmp_path):
        """A CI gate that silently passes on a corrupt anchor file is
        worse than no gate — unusable baseline must exit non-zero."""
        bench = _load_bench()
        p = str(tmp_path / "BASELINE.json")
        with open(p, "w") as f:
            f.write('{"trunc')
        fails = bench.check_regression(dict(self.LINE), baseline_path=p)
        assert len(fails) == 1 and "unusable" in fails[0]
        assert self._check(tmp_path, {"m": 0.0}) \
            == ["m: unusable published value 0.0"]
        assert self._check(tmp_path, {
            "resnet50_syncbn_dp_train_throughput": {
                "value": 100.0, "direction": "sideways"},
        }) == ["resnet50_syncbn_dp_train_throughput: unknown direction "
               "'sideways'"]

    def test_empty_published_map_passes(self, tmp_path):
        # the shipped BASELINE.json publishes nothing yet: the gate is
        # vacuously green until an anchor lands (recorded trajectory
        # starts empty, ISSUE 8 motivation)
        assert self._check(tmp_path, {}) == []

    def test_cli_exit_codes(self, tmp_path, monkeypatch, capsys):
        """End to end through bench.main + the gate: a synthetically
        degraded anchor exits non-zero, a met anchor exits zero."""
        from tpu_syncbn.obs import telemetry, tracing

        bench = _load_bench()
        monkeypatch.setenv("BENCH_STEPS", "3")
        monkeypatch.setattr(bench, "build_program", self._tiny_build())
        telemetry.REGISTRY.reset()
        try:
            line = bench.main()
        finally:
            telemetry.set_enabled(None)
            telemetry.REGISTRY.reset()
            tracing.uninstall()
        capsys.readouterr()
        assert isinstance(line, dict) and line["value"] > 0
        good = str(tmp_path / "good.json")
        with open(good, "w") as f:
            json.dump({"published": {line["metric"]: line["value"]}}, f)
        assert bench.check_regression(line, baseline_path=good) == []
        bad = str(tmp_path / "bad.json")
        with open(bad, "w") as f:
            json.dump({"published": {line["metric"]: line["value"] * 10}}, f)
        assert bench.check_regression(line, baseline_path=bad) != []


class TestRecoveryBlock:
    """bench's `recovery` block: the robustness-cost measurement that
    rides the BENCH_*.json line (manifest overhead + time-to-resume
    after an injected mid-write kill)."""

    def test_schema_and_fallback_resume(self):
        import jax.numpy as jnp
        import optax
        from flax import nnx

        from tpu_syncbn import nn as tnn, parallel

        bench = _load_bench()

        class Net(nnx.Module):
            def __init__(self, rngs):
                self.fc = nnx.Linear(8, 8, rngs=rngs)
                self.bn = tnn.BatchNorm1d(8)

            def __call__(self, x):
                return self.bn(self.fc(x))

        dp = parallel.DataParallel(
            tnn.convert_sync_batchnorm(Net(nnx.Rngs(0))),
            optax.sgd(0.1), lambda m, b: (m(b) ** 2).mean(),
        )
        dp.train_step(jnp.ones((8, 8), jnp.float32))
        rec = bench.measure_recovery(dp, repeats=1)
        assert set(rec) == {
            "ckpt_roundtrip_s", "ckpt_roundtrip_seed_s",
            "manifest_overhead_s", "manifest_overhead_frac",
            "ckpt_async_enqueue_s", "ckpt_async_flush_s",
            "async_manifest_verified",
            "resume_after_kill_s", "resumed_step_after_kill", "ckpt_bytes",
        }
        assert rec["manifest_overhead_s"] >= 0
        # async checkpointing: the loop-visible enqueue cost exists, and
        # the background write still produced a certified manifest
        assert rec["ckpt_async_enqueue_s"] >= 0
        assert rec["async_manifest_verified"] is True
        # the injected kill truncated step 2: resume must land on the
        # older verified step, and quickly
        assert rec["resumed_step_after_kill"] == 1
        assert rec["ckpt_bytes"] > 0
        assert rec["ckpt_roundtrip_s"] > 0
        assert rec["resume_after_kill_s"] < 10


def _load_flash_sweep():
    bench_dir = os.path.join(ROOT, "benchmarks")
    sys.path.insert(0, bench_dir)  # its ``from _common import ...``
    try:
        spec = importlib.util.spec_from_file_location(
            "flash_tile_sweep_under_test",
            os.path.join(bench_dir, "flash_tile_sweep.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(bench_dir)
    return mod


class TestFlashTileSweep:
    """benchmarks/flash_tile_sweep.py's pure parts: the counts beside
    each time, the fit, and which operations of a capture belong to
    which program."""

    def test_walk_counts_the_cells_call(self):
        from tpu_syncbn.ops import pallas_attention as pa

        mod = _load_flash_sweep()
        assert mod.walk(pa, 2048, 128, 128) == 136  # x 32 = 4,352 steps
        assert mod.walk(pa, 2048, 512, 512) == 10   # x 32 = 320

    def test_fit_recovers_step_and_score_costs(self):
        mod = _load_flash_sweep()
        rows = [{"steps": s, "scores": n,
                 "kernel_ms": 4e-4 * s + 3e-9 * n}
                for s, n in [(4352, 71e6), (320, 84e6), (96, 100e6),
                             (1152, 75e6)]]
        got = mod.fit(rows + [{"steps": 1, "scores": 1, "error": "x"}])
        assert got["points"] == 4
        assert got["a_us_per_step"] == pytest.approx(0.4, rel=1e-6)
        assert got["b_ps_per_score"] == pytest.approx(3.0, rel=1e-6)
        assert mod.fit(rows[:2]) == {}

    def test_operations_go_to_the_execution_that_holds_them(self):
        mod = _load_flash_sweep()
        plane = {
            "metadata": {1: ("jit_flash_q128_k128(123)", None),
                         2: ("jit_other(9)", None),
                         3: ("%k = custom-call(...)", "a/pallas_call"),
                         4: ("%copy.1 = copy(...)", None)},
            "lines": [
                {"name": "XLA Modules",
                 "events": [(1, 0.0, 100.0), (2, 100.0, 50.0),
                            (1, 200.0, 100.0)]},
                {"name": "XLA Ops",
                 "events": [(3, 10.0, 60.0), (4, 70.0, 20.0),
                            (4, 110.0, 30.0), (3, 210.0, 80.0)]},
            ],
        }
        runs = mod.by_program([plane])
        assert [len(r) for r in runs["jit_flash_q128_k128"]] == [2, 1]
        kernel, whole = mod.kernel_ms(runs["jit_flash_q128_k128"])
        assert kernel == pytest.approx(70e-6)  # median of 60 and 80 ns
        assert whole == pytest.approx(80e-6)
        assert mod.kernel_ms(runs["jit_other"]) == (0.0, pytest.approx(30e-6))
