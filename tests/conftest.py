"""Test configuration: run every test against 8 virtual CPU devices.

This is the TPU-native analogue of testing torch SyncBN on the ``gloo``
CPU backend (the reference stack's CPU path at
``[torch] nn/modules/_functions.py:64-86`` exists for exactly this):
``--xla_force_host_platform_device_count=8`` gives JAX eight host "devices"
in one process, so every collective (psum/pmean/all_gather over the mesh)
executes for real under pytest without TPU hardware.

Must run before jax is imported anywhere: jax reads ``JAX_PLATFORMS``
when it is imported and ``XLA_FLAGS`` when the backend starts. No pytest
plugin installed here imports jax ahead of this file (checked under jax
0.9.0 with ``"jax" in sys.modules`` at this point), so the environment
alone selects the CPU and nothing is mirrored into ``jax.config``.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # the suite never runs on a chip
# Persist every compile, not only those over the library's 0.25 s floor:
# the suite recompiles the same small programs in file after file and in
# every subprocess it starts, and the limit it runs under is tight
# (costs nothing cold; a warm run is about a fifth faster for it).
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)

# Persistent compilation cache: the suite's wall-clock is dominated by
# XLA compiles of the same sharded programs every run; cache keys are
# HLO+options+backend hashes, so reuse is correctness-safe. Tests that
# never call runtime.initialize() get it from here.
from tpu_syncbn.runtime.distributed import (  # noqa: E402
    enable_persistent_compilation_cache,
)

enable_persistent_compilation_cache()


def pytest_report_header(config):
    return f"jax devices: {jax.device_count()} ({jax.default_backend()})"
