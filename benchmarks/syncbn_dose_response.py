"""Dose-response: SyncBN-vs-per-replica divergence as per-chip batch shrinks.

The reference's claim is not just "per-device BN hurts" but that it
hurts *at small per-device batches* (``README.md:3``). This sweep runs
the classification convergence A/B (``syncbn_convergence_ab.py``) at
several doses and reports the per-replica arm's absolute trajectory
damage (loss-curve MAE) alongside the divergence ratio, as one JSON
line. Two modes isolate different variables:

* ``--mode per_chip`` (default): fixed replica count (8), per-chip batch
  swept over ``--batches``. NOTE each dose has its OWN oracle (the
  single-device arm trains at global batch = replicas x b, which varies
  with the dose), so each point records its ``global_batch`` and the
  oracle's final loss; compare ratios across points, and absolute MAEs
  only with that caveat in mind.
* ``--mode const_global``: fixed global batch (``--global-batch``),
  replica count swept over ``--replicas`` => per-chip batch G/R. Every
  dose shares ONE oracle configuration (1 device, batch G, same seed and
  data order — the oracle curve is identical across doses, which the
  driver verifies on the full unrounded per-step curve and treats as
  fatal if violated), so the per-replica damage column varies ONLY the
  per-device-statistics mechanism the reference names — not the global
  batch.

Points are written to ``--out`` incrementally: a mid-sweep failure keeps
every completed dose.

Each dose is a child process started with ``--simulate R``, so this is a
CPU-only tool; the parent never imports jax (a parent that touched JAX
would hold the chip, and a child that needed it would fail or hang).

    python benchmarks/syncbn_dose_response.py --batches 1 2 4 8
    python benchmarks/syncbn_dose_response.py --mode const_global \
        --global-batch 16 --replicas 2 4 8
"""

import argparse
import atexit
import json
import os
import signal
import subprocess
import sys

from _common import log

HERE = os.path.dirname(os.path.abspath(__file__))


def _rm_quiet(path):
    try:
        os.remove(path)
    except OSError:
        pass


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--mode", choices=["per_chip", "const_global"],
                   default="per_chip")
    p.add_argument("--simulate", type=int, default=8,
                   help="replica count (per_chip mode)")
    p.add_argument("--batches", type=int, nargs="+", default=[1, 2, 4, 8],
                   help="per-chip batches to sweep (per_chip mode)")
    p.add_argument("--global-batch", type=int, default=16,
                   help="fixed global batch (const_global mode)")
    p.add_argument("--replicas", type=int, nargs="+", default=[2, 4, 8],
                   help="replica counts to sweep (const_global mode)")
    p.add_argument("--steps", type=int, default=300)
    p.add_argument("--child-timeout-s", type=float, default=7200,
                   help="per-dose wall clock; the heaviest dose (largest "
                        "global batch) trains 3 arms x steps and has "
                        "blown a 3600s budget under CPU contention")
    p.add_argument("--out", default=None, help="also write the JSON here")
    return p.parse_args()


def _last_json_line(stdout: str):
    """First parseable JSON line scanning from the end — tolerates any
    trailing library chatter on stdout."""
    for line in reversed(stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except (json.JSONDecodeError, ValueError):
            continue
    raise RuntimeError("child produced no JSON line")


def main():
    args = parse_args()
    if args.mode == "per_chip":
        metric = "syncbn_dose_response_per_chip_batch"
        # (replicas, per_chip_batch) per dose
        doses = [(args.simulate, b) for b in args.batches]
    else:
        metric = "syncbn_dose_response_const_global_batch"
        for r in args.replicas:
            if args.global_batch % r:
                raise SystemExit(
                    f"--global-batch {args.global_batch} not divisible by "
                    f"replica count {r}"
                )
        doses = [(r, args.global_batch // r) for r in args.replicas]
    result = {
        "metric": metric,
        "steps": args.steps,
        "points": [],
        "failed": [],
    }
    if args.mode == "per_chip":
        result["replicas"] = args.simulate
    else:
        result["global_batch"] = args.global_batch

    def save():
        if args.out:
            tmp = args.out + ".tmp"
            with open(tmp, "w") as f:
                json.dump(result, f, indent=2)
            os.replace(tmp, args.out)

    oracle_curves = {}  # dose -> full per-step oracle loss curve
    # const_global: ONE oracle, trained by the first dose child and
    # loaded (not retrained) by the rest — on CPU, different --simulate
    # values compile different thread/device partitionings, so
    # independently-trained oracles drift by float noise that training
    # chaos amplifies (observed; the shared file removes the variable)
    oracle_path = os.path.join(HERE, f".dose_oracle_{os.getpid()}.json")
    # a graceful parent-level kill (^C, SIGTERM from a budget overrun)
    # must not leak temp files into the tree — neither the PID-named
    # oracle curve nor the in-flight per-dose curves file; SIGTERM is
    # routed through sys.exit so the atexit hook actually runs (atexit
    # never fires on a raw signal death, and nothing can cover SIGKILL)
    temp_paths = [oracle_path]
    atexit.register(lambda: [_rm_quiet(p) for p in temp_paths])
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    for (r, b) in doses:
        log(f"replicas {r}, per-chip batch {b}...")
        curves_path = os.path.join(HERE, f".dose_curves_{r}_{b}.json")
        temp_paths.append(curves_path)
        cmd = [sys.executable,
               os.path.join(HERE, "syncbn_convergence_ab.py"),
               "--simulate", str(r),
               "--per-chip-batch", str(b), "--steps", str(args.steps)]
        if args.mode == "const_global":
            # curves only exist to verify oracle identity — per_chip
            # mode has no such invariant and skips the plumbing
            cmd += ["--curves", curves_path, "--oracle-curve", oracle_path]
        try:
            try:
                proc = subprocess.run(
                    cmd,
                    cwd=HERE, capture_output=True, text=True,
                    timeout=args.child_timeout_s,
                )
                if proc.returncode != 0:
                    raise RuntimeError(
                        f"rc={proc.returncode}: {proc.stderr[-1000:]}"
                    )
                d = _last_json_line(proc.stdout)
            except (subprocess.TimeoutExpired, RuntimeError) as e:
                # completed doses are training hours — keep them
                log(f"  ({r}, {b}) FAILED: {e}")
                result["failed"].append({"replicas": r, "per_chip_batch": b})
                save()
                continue
            if args.mode == "const_global":
                # verification input only: an unreadable curves file
                # must not discard a successfully-parsed dose — the
                # identity check below accounts for the missing curve
                try:
                    with open(curves_path) as f:
                        oracle_curves[(r, b)] = json.load(f)["oracle"]
                except (OSError, KeyError, ValueError) as e:
                    log(f"  ({r}, {b}) oracle-curve readback failed: {e}")
        finally:
            try:
                os.remove(curves_path)
            except OSError:
                pass
        result["points"].append({
            "replicas": r,
            "per_chip_batch": b,
            "global_batch": r * b,  # = this dose's oracle batch
            "oracle_final_loss": d["final_loss"]["oracle"],
            "syncbn_loss_mae": d["syncbn_loss_mae"],
            "perreplica_loss_mae": d["perreplica_loss_mae"],
            "divergence_ratio": d["divergence_ratio"],
        })
        save()
        log(f"  perreplica MAE {d['perreplica_loss_mae']}, "
            f"ratio {d['divergence_ratio']}")
    _rm_quiet(oracle_path)
    if args.mode == "const_global" and len(result["points"]) > 1:
        # every dose must have scored against the SAME oracle curve
        # (trained once, shared via --oracle-curve) — verified on the
        # FULL unrounded per-step curve. Fatal on drift AND on
        # unverifiability: an artifact whose documented isolation
        # invariant was never checked must not look like a verified one
        curves = list(oracle_curves.values())
        verified = (
            len(oracle_curves) == len(result["points"])
            and all(c == curves[0] for c in curves[1:])
        )
        result["oracle_shared"] = verified
        if not verified:
            log("ERROR: oracle identity across doses not verified "
                f"(curves readable for {len(oracle_curves)}/"
                f"{len(result['points'])} doses, "
                f"identical={bool(curves) and all(c == curves[0] for c in curves[1:])})")
        save()
    print(json.dumps(result))
    if result["failed"] or result.get("oracle_shared") is False:
        sys.exit(1)


if __name__ == "__main__":
    main()
