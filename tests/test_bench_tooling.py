"""Pin the kernel probes under ``benchmarks/`` on CPU: the block sweep's
resume file (``pallas_block_sweep.py``), the zigzag FLOP comparison's
report (``zigzag_flops.py``) and the pure parts of the flash-attention
tile sweep (``flash_tile_sweep.py``).

The benchmark itself is ``chipbench/``; its CPU rehearsal is
``tests/chipbench/``.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

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

    def test_backward_fit_is_one_fit_a_kernel(self):
        mod = _load_flash_sweep()
        rows = [{"dkv_steps": s, "dq_steps": s, "dkv_scores": n,
                 "dq_scores": n, "dkv_ms": 4e-4 * s + 5e-9 * n,
                 "dq_ms": 4e-4 * s + 4e-9 * n}
                for s, n in [(4352, 71e6), (320, 84e6), (96, 100e6),
                             (1152, 75e6)]] + [{"scan_block": 128}]
        got = mod.fit_backward(rows)
        assert got["dkv"]["b_ps_per_score"] == pytest.approx(5.0, rel=1e-6)
        assert got["dq"]["b_ps_per_score"] == pytest.approx(4.0, rel=1e-6)
        assert got["dq"]["a_us_per_step"] == pytest.approx(0.4, rel=1e-6)

    def test_a_kernels_time_is_read_by_its_name(self):
        mod = _load_flash_sweep()
        executions = [
            [("%flash_bwd_dkv_q512_k512 = custom-call()", "a", 2e6),
             ("%flash_bwd_dq_q512_k512 = custom-call()", "a", 1e6),
             ("%fusion.1 = fusion()", None, 5e5)],
            [("%flash_bwd_dkv_q512_k512 = custom-call()", "a", 4e6),
             ("%flash_bwd_dq_q512_k512 = custom-call()", "a", 3e6)],
        ]
        assert mod.named_ms(executions, "flash_bwd_dkv") == 3.0
        assert mod.named_ms(executions, "flash_bwd_dq") == 2.0

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
