"""Backend selection and compile-cache placement: one in-process rule, no
fallback that hides the device (``tpu_syncbn.runtime.probe``), and a
cache that can be placed from outside
(``runtime.distributed.enable_persistent_compilation_cache``)."""

import os
import subprocess
import sys

import jax
import pytest

from tpu_syncbn.runtime import distributed, probe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_device_count_flag_merge():
    out = probe._merge_device_count_flag(
        "--foo --xla_force_host_platform_device_count=2", 8
    )
    assert "--xla_force_host_platform_device_count=8" in out
    assert "--foo" in out
    # keeps a larger existing value
    out = probe._merge_device_count_flag(
        "--xla_force_host_platform_device_count=16", 8
    )
    assert "--xla_force_host_platform_device_count=16" in out


def test_force_cpu_after_backend_init():
    # with the cpu backend live (8 devices): a satisfiable request is a
    # no-op, an unsatisfiable one must raise loudly — XLA_FLAGS edits can
    # no longer take effect
    jax.device_count()  # ensure backend initialization
    assert probe._backend_initialized()
    probe.force_cpu(8)  # satisfied: no-op
    with pytest.raises(RuntimeError, match="already initialized"):
        probe.force_cpu(10_000)


class TestBackendRequirement:
    def test_explicit_cpu_choice_is_honoured(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # what conftest chose
        info = probe.ensure_backend(4)
        assert info == ("cpu", jax.device_count())
        assert info.device_count >= 4

    def test_too_few_devices_raises(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        with pytest.raises(RuntimeError, match="already initialized"):
            probe.ensure_backend(10_000)

    @pytest.mark.parametrize("platforms", [None, "", "tpu,cpu"])
    def test_asking_for_a_chip_on_a_cpu_backend_raises(
        self, monkeypatch, platforms
    ):
        # the live backend is the CPU (conftest), but the environment no
        # longer says the CPU was CHOSEN — e.g. JAX fell back to it on a
        # machine with no chip. That is an error, never a quiet CPU run.
        if platforms is None:
            monkeypatch.delenv("JAX_PLATFORMS")
        else:
            monkeypatch.setenv("JAX_PLATFORMS", platforms)
        with pytest.raises(RuntimeError, match="no CPU fallback"):
            probe.ensure_backend(1)

    def test_nothing_spawns_a_process(self, monkeypatch):
        def boom(*a, **k):
            raise AssertionError("backend selection must stay in-process")

        monkeypatch.setattr(subprocess, "run", boom)
        monkeypatch.setattr(subprocess, "Popen", boom)
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert probe.ensure_backend(1).platform == "cpu"


class TestCompileCachePlacement:
    """What the code sets is asserted on ``jax.config.update`` calls, not
    on the live config: the suite's own cache (conftest) stays put."""

    @pytest.fixture
    def updates(self, monkeypatch):
        calls = []
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: calls.append((k, v))
        )
        return calls

    def test_env_set_means_no_directory_set_in_code(
        self, monkeypatch, updates
    ):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert distributed.enable_persistent_compilation_cache() == "/some/dir"
        assert "jax_compilation_cache_dir" not in dict(updates)

    def test_env_unset_means_one_fixed_path_in_the_checkout(
        self, monkeypatch, updates
    ):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = distributed.enable_persistent_compilation_cache()
        assert dict(updates)["jax_compilation_cache_dir"] == path
        assert path == os.path.join(ROOT, ".jax_cache")
        # two calls, same path: the directory is part of the cache key
        assert distributed.enable_persistent_compilation_cache() == path

    def test_path_has_no_pid_home_temp_or_time_component(self):
        # the checkout itself may live anywhere (a temp directory, a
        # numbered one); what the program adds to it is one fixed name
        import tpu_syncbn

        checkout = os.path.dirname(os.path.dirname(tpu_syncbn.__file__))
        assert distributed._COMPILE_CACHE_DIR == os.path.join(
            checkout, ".jax_cache")
        # …and git would not commit it
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()

    def test_min_compile_time_env_wins(self, monkeypatch, updates):
        monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "5")
        distributed.enable_persistent_compilation_cache()
        assert "jax_persistent_cache_min_compile_time_secs" \
            not in dict(updates)

    def test_initialize_turns_it_on_for_every_entry_point(self, monkeypatch):
        seen = []
        monkeypatch.setattr(
            distributed, "enable_persistent_compilation_cache",
            lambda: seen.append(True),
        )
        monkeypatch.setattr(distributed, "_initialized", False)
        distributed.initialize()
        assert seen == [True]


class TestChipSmokeWithoutAChip:
    """The contract's CPU side: with no accelerator the script exits
    non-zero and prints no result — under an explicit CPU choice and
    under JAX's own silent fallback alike."""

    @pytest.mark.parametrize("env", [
        {"JAX_PLATFORMS": "cpu"},
        {},  # unset: jax itself falls back to the CPU when no chip answers
    ], ids=["cpu-chosen", "platform-unset"])
    def test_fails_and_prints_no_result(self, env):
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
            capture_output=True, text=True, timeout=120, cwd=ROOT,
            env={**{k: v for k, v in os.environ.items()
                    if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}, **env},
        )
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""

    def test_alone_in_a_directory_it_fails_too(self, tmp_path):
        import shutil

        shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
        proc = subprocess.run(
            [sys.executable, "chip_smoke.py"], cwd=tmp_path,
            capture_output=True, text=True, timeout=120,
            env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        )
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""


#: chip_smoke's phases at a size the CPU finishes in seconds: the depth
#: and width of the model cut, everything else the same code. Both modes
#: in one process, so jax is imported and the model's layers are
#: compiled once.
_REHEARSAL = """
import json
import chip_smoke
sz = chip_smoke.Sizes(
    model="resnet18", width=8, num_classes=10, batch=4, side=32, steps=2,
    buckets=(2, 4), requests=5, bn_shape=(2, 8, 8, 128),
    flash_shape=(1, 128, 2, 64), four_chip_batch=2,
)
for chips in (1, 4):
    print(json.dumps(chip_smoke.run(sz, chips=chips)))
"""


@pytest.fixture(scope="module")
def rehearsal():
    """Every phase of chip_smoke.py, through the same code, at tiny size
    on four virtual CPU devices (on-chip-measurement guide §2.1-2.2).
    ``main`` is what refuses to run without a chip; ``run`` is driven
    directly, and what it prints names the CPU, so it can never read as
    a chip run. One subprocess, under its own 120 s limit."""
    import json

    proc = subprocess.run(
        [sys.executable, "-c", _REHEARSAL],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    ok = {"ok": True,
          "device": {"platform": "cpu", "kind": "cpu", "count": 4}}
    split = lines.index(ok)
    assert lines[-1] == ok
    return lines[:split], lines[split + 1:-1]


def test_chip_smoke_rehearsal_one_chip(rehearsal):
    start, train, serve, kernels = rehearsal[0]
    assert [l["phase"] for l in rehearsal[0]] == [
        "start", "train", "serve", "kernels"]
    assert start["chips_used"] == 1
    assert train["compile"]["first_dispatch_cache"] == "hit"
    assert len(train["losses"]) == 5
    assert serve["requests_answered"] == 5
    assert kernels["interpret"] is True  # the CPU, and it says so


def test_chip_smoke_rehearsal_four_chips(rehearsal):
    # with the option: that path and what it is compared with, only
    assert [l["phase"] for l in rehearsal[1]] == [
        "start", "four_chip", "four_chip.done"]
    four = rehearsal[1][1]
    assert four["control_ratio"]["bn_stem"] >= four["control_ratio_required"]
    assert four["arms"]["four_chip_syncbn"]["all_reduces"] \
        > four["arms"]["four_chip_per_replica_bn"]["all_reduces"]
