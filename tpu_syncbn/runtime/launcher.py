"""Single-program launcher — replaces ``python -m torch.distributed.launch
--nproc_per_node=N distributed_train.py`` (reference ``README.md:94-103``).

The reference launcher spawns one OS process per GPU and wires the env
contract (``MASTER_ADDR/PORT``, ``RANK``, ``WORLD_SIZE``, ``LOCAL_RANK``;
``[torch] distributed/run.py:211-232``). On TPU there is nothing to spawn:
one Python process per *host* drives all local chips, and chip-level
parallelism is the mesh. So the launcher's job shrinks to:

* initialize the distributed runtime (slice metadata / explicit flags);
* optionally simulate an N-chip mesh on CPU
  (``--simulate-chips``, via ``--xla_force_host_platform_device_count``)
  so the same script runs anywhere — the TPU analogue of debugging the
  recipe on the gloo backend;
* run the user's training script with ``__name__ == "__main__"`` intact.

Usage::

    python -m tpu_syncbn.launch [--simulate-chips 8] \
        [--coordinator host:port --num-processes H --process-id I] \
        your_train.py -- --your-script-args

No ``--local_rank`` is injected (reference step 1, ``README.md:11-19``):
scripts read identity from ``tpu_syncbn.runtime.process_index()``.
"""

from __future__ import annotations

import argparse
import os
import runpy
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m tpu_syncbn.launch",
        description="TPU-native launcher (replaces torch.distributed.launch)",
    )
    p.add_argument(
        "--simulate-chips",
        type=int,
        default=None,
        metavar="N",
        help="simulate an N-chip mesh on CPU host devices (testing without "
        "TPU hardware; sets --xla_force_host_platform_device_count)",
    )
    p.add_argument(
        "--nproc-per-node",
        type=int,
        default=None,
        metavar="N",
        help="compatibility alias for torch.distributed.launch's flag "
        "(reference README.md:96): there are no per-chip processes on TPU, "
        "so this asserts N == local chip count on hardware, or behaves "
        "like --simulate-chips N on CPU",
    )
    p.add_argument(
        "--coordinator",
        default=None,
        metavar="HOST:PORT",
        help="multi-host coordinator address (MASTER_ADDR:MASTER_PORT "
        "analogue; on a Cloud TPU slice leave unset — autodetected)",
    )
    p.add_argument("--num-processes", type=int, default=None,
                   help="number of host processes (WORLD_SIZE analogue)")
    p.add_argument("--process-id", type=int, default=None,
                   help="this host's index (RANK analogue)")
    p.add_argument("script", help="training script to run")
    p.add_argument("script_args", nargs=argparse.REMAINDER,
                   help="arguments passed through to the script")
    return p


def main(argv: list[str] | None = None) -> None:
    args = build_parser().parse_args(argv)

    if args.nproc_per_node is not None and args.simulate_chips is None:
        # Probing the backend here would initialize it before the simulate
        # flags can take effect, so: CPU-only environments (no accelerator
        # platform requested) treat the flag as --simulate-chips; otherwise
        # the count is validated after runtime.initialize() below.
        # Only an EXPLICIT cpu request maps to simulation; unset means
        # autodetect (likely real TPU) and falls through to the
        # post-initialize chip-count validation.
        platforms = os.environ.get("JAX_PLATFORMS", "")
        if platforms.split(",")[0] == "cpu":
            args.simulate_chips = args.nproc_per_node

    if args.simulate_chips is not None:
        if args.simulate_chips < 1:
            raise SystemExit("--simulate-chips must be >= 1")
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.simulate_chips}"
        ).strip()
        os.environ["JAX_PLATFORMS"] = "cpu"
        # jax reads JAX_PLATFORMS when it is imported, and `python -m
        # tpu_syncbn.launch` imports the package (and jax) before main()
        # runs: the env alone is too late, so mirror it into the config
        if "jax" in sys.modules:
            import jax

            jax.config.update("jax_platforms", "cpu")

    # export the env contract for DistributedConfig.from_env()
    if args.coordinator is not None:
        os.environ["TPU_SYNCBN_COORDINATOR"] = args.coordinator
    if args.num_processes is not None:
        os.environ["TPU_SYNCBN_NUM_PROCESSES"] = str(args.num_processes)
    if args.process_id is not None:
        os.environ["TPU_SYNCBN_PROCESS_ID"] = str(args.process_id)

    from tpu_syncbn import runtime

    runtime.initialize()

    if args.nproc_per_node is not None and args.simulate_chips is None:
        import jax

        if jax.local_device_count() != args.nproc_per_node:
            raise SystemExit(
                f"--nproc-per-node={args.nproc_per_node} but this host has "
                f"{jax.local_device_count()} chips; on TPU the mesh spans "
                "all local chips automatically — drop the flag or match it"
            )

    script_args = args.script_args
    if script_args and script_args[0] == "--":
        script_args = script_args[1:]
    sys.argv = [args.script] + script_args
    runpy.run_path(args.script, run_name="__main__")


if __name__ == "__main__":
    main()
