"""Backend selection: one in-process rule and no fallback.

The platform is what ``JAX_PLATFORMS`` names, or JAX's default when it
is unset; this program never switches it. An entry point that needs the
chip (``chipbench/run.py``, ``__graft_entry__``, ``chip_smoke.py``) calls
:func:`ensure_backend`, which raises when the backend is not a TPU with
at least the devices asked for — a run that was meant for the chip must
not quietly become a CPU run and still print a number.

The CPU is an explicit choice: ``JAX_PLATFORMS=cpu`` (what the tests and
``--simulate-chips`` set). Under it :func:`ensure_backend` arranges as
many virtual host devices as were asked for, so sharded programs still
run with real collectives.
"""

from __future__ import annotations

import os
from typing import NamedTuple


class BackendInfo(NamedTuple):
    platform: str
    device_count: int


def _backend_initialized() -> bool:
    """Has THIS process already initialized a jax backend? (After that,
    platform/XLA_FLAGS changes silently do nothing — fail loudly instead.)"""
    from jax._src import xla_bridge

    return bool(xla_bridge.backends_are_initialized())


def _cpu_chosen() -> bool:
    """Did the environment choose the CPU platform explicitly?"""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def force_cpu(min_devices: int = 1) -> None:
    """Select the CPU platform *before* the in-process backend initializes,
    with at least ``min_devices`` virtual host devices.

    ``XLA_FLAGS`` is only read at first backend initialization, so this
    must run before any ``jax.devices()`` call in this process. If the
    backend is already live and does not satisfy the request, this
    raises instead of returning with a choice that never took effect.
    """
    import jax

    if _backend_initialized():
        if jax.default_backend() != "cpu" or len(jax.devices()) < min_devices:
            raise RuntimeError(
                "cannot select the CPU: this process already initialized "
                f"the '{jax.default_backend()}' backend with "
                f"{len(jax.devices())} device(s) (< {min_devices} requested "
                "or wrong platform). Call ensure_backend(min_devices) "
                "before any jax computation in the process."
            )
        return
    os.environ["JAX_PLATFORMS"] = "cpu"
    if min_devices > 1:
        os.environ["XLA_FLAGS"] = _merge_device_count_flag(
            os.environ.get("XLA_FLAGS", ""), min_devices
        )
    # jax read JAX_PLATFORMS when it was imported, which may be before
    # the line above
    jax.config.update("jax_platforms", "cpu")


def _merge_device_count_flag(flags: str, min_devices: int) -> str:
    """Ensure ``--xla_force_host_platform_device_count`` is present with at
    least ``min_devices`` (keeping a larger existing value)."""
    token = "--xla_force_host_platform_device_count="
    parts = [p for p in flags.split() if not p.startswith(token)]
    existing = next(
        (int(p[len(token):]) for p in flags.split() if p.startswith(token)),
        0,
    )
    parts.append(token + str(max(existing, min_devices)))
    return " ".join(parts)


def ensure_backend(min_devices: int = 1) -> BackendInfo:
    """The backend this process runs on, or an error: a TPU with at least
    ``min_devices`` chips — or, only when ``JAX_PLATFORMS=cpu`` chose it,
    the CPU with at least that many (virtual) host devices. Call before
    the first jax computation in the process.
    """
    import jax

    if _cpu_chosen():
        force_cpu(min_devices)
        return BackendInfo("cpu", len(jax.devices()))
    devices = jax.devices()
    info = BackendInfo(devices[0].platform, len(devices))
    if info.platform != "tpu" or info.device_count < min_devices:
        raise RuntimeError(
            f"need a TPU backend with >= {min_devices} device(s), found "
            f"'{info.platform}' with {info.device_count} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). There "
            "is no CPU fallback: set JAX_PLATFORMS=cpu to choose the CPU."
        )
    return info
