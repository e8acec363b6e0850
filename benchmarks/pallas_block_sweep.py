"""Tune the Pallas BN kernels' row-block size on real hardware.

``_BLOCK_M`` (rows per grid step) was chosen analytically in round 1 and
has never been validated on a chip. This sweep times the three kernels
(stats, normalize, backward-reduce) and the full fused_batch_norm
fwd+bwd at ResNet-50-representative (M, C) shapes across candidate block
sizes, and prints a JSON recommendation. Run ON TPU (on CPU it measures
interpret-mode overhead, which is meaningless — the script refuses
unless --allow-cpu).

    python benchmarks/pallas_block_sweep.py [--blocks 128 256 512 1024]
"""

import argparse
import json
import os
import sys
import time

from _common import fetch_sync, log, setup


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--blocks", type=int, nargs="+",
                   default=[128, 256, 512, 1024])
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--allow-cpu", action="store_true")
    p.add_argument("--max-rows", type=int, default=None,
                   help="clip each shape's M (CPU smoke runs: interpret "
                        "mode at full R50 sizes is impractical)")
    p.add_argument("--simulate", type=int, default=None)
    p.add_argument("--budget-s", type=float, default=None,
                   help="wall-clock budget: stop starting new blocks once "
                        "exceeded and report whatever finished (a "
                        "killed sweep reports nothing)")
    p.add_argument("--partial-out", default=None,
                   help="write the running result JSON here after every "
                        "shape so a timeout still leaves evidence; if the "
                        "file already exists its timings seed a resume")
    return p.parse_args()


# (M, C) pairs a ResNet-50 step actually runs BN over (per-chip batch 64,
# 224px): M = N*H*W per stage, C per stage
R50_SHAPES = [
    (64 * 56 * 56, 64),
    (64 * 56 * 56, 256),
    (64 * 28 * 28, 512),
    (64 * 14 * 14, 1024),
    (64 * 7 * 7, 2048),
]


def main():
    args = parse_args()
    setup(args.simulate)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_syncbn.ops import pallas_bn

    if jax.default_backend() != "tpu" and not args.allow_cpu:
        print(json.dumps({
            "metric": "pallas_block_sweep",
            "skipped": "requires a TPU backend (interpret-mode timings "
                       "are meaningless); pass --allow-cpu to force",
            "backend": jax.default_backend(),
        }))
        sys.exit(0)

    shapes = R50_SHAPES
    if args.max_rows:
        shapes = [(min(m, args.max_rows), c) for m, c in shapes]

    default_block = pallas_bn._BLOCK_M
    # baseline first: under a wall-clock budget the blocks measured last
    # are the first casualties, and a sweep without the default measured
    # cannot report speedup_vs_default
    blocks = [default_block] + [b for b in args.blocks if b != default_block]

    rng = np.random.RandomState(0)
    results: dict[int, float] = {}
    failures: dict[str, str] = {}
    # per-shape timings, keyed "block:MxC" — this is the resume unit: a
    # budget-killed run leaves them in --partial-out, and the next run
    # (chip time is budgeted) skips every shape already measured.
    # The config fingerprint (incl. a hash of the kernel source) keeps a
    # stale file from silently replacing fresh measurements; recorded
    # failures are NOT resumed — a machine lost mid-compile looks the
    # same as a real VMEM overflow, and only a retry can tell them apart.
    import hashlib

    kernel_sha = hashlib.sha256(
        open(pallas_bn.__file__, "rb").read()
    ).hexdigest()[:16]
    # backend is part of the fingerprint: interpret-mode CPU timings must
    # never seed a TPU sweep (or vice versa)
    config = {"iters": args.iters, "max_rows": args.max_rows,
              "kernel_sha": kernel_sha, "backend": jax.default_backend()}
    shape_ms: dict[str, float] = {}
    if args.partial_out and os.path.exists(args.partial_out):
        try:
            with open(args.partial_out) as f:
                prev = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            # a hard kill mid-write used to be able to truncate the file;
            # writes are atomic now, but stay loud rather than silent
            log(f"[sweep] unreadable partial file {args.partial_out} "
                f"({type(e).__name__}: {e}); starting fresh")
            prev = {}
        if prev.get("config") == config:
            shape_ms.update(prev.get("shape_ms", {}))
            if shape_ms:
                log(f"[sweep] resuming: {len(shape_ms)} shape timing(s) "
                    f"from {args.partial_out}")
        elif prev:
            log(f"[sweep] ignoring {args.partial_out}: config changed "
                f"({prev.get('config')} -> {config})")
    t_start = time.perf_counter()
    budget_exhausted = False

    def write_partial(done: bool = False):
        if args.partial_out:
            payload = {"by_block": {str(k): v for k, v in results.items()},
                       "shape_ms": shape_ms, "config": config,
                       "failures": failures, "partial": not done}
            tmp = args.partial_out + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, args.partial_out)  # survive a mid-write SIGKILL

    try:
        for block in blocks:
            if args.budget_s and time.perf_counter() - t_start > args.budget_s:
                budget_exhausted = True
                log(f"[sweep] budget {args.budget_s}s exhausted; stopping "
                    f"after {len(results)} block(s)")
                break
            pallas_bn._BLOCK_M = block
            jax.clear_caches()  # _BLOCK_M is baked into traced kernels
            total = 0.0
            ok = True
            for m, c in shapes:
                # The VMEM-aware clamp (pallas_bn._block_m) treats
                # _BLOCK_M as a MAX: where it clamps this shape below the
                # requested block, the kernel actually runs the clamped
                # size — key the timing by what RUNS, so no label ever
                # names a configuration that doesn't exist and the
                # clamped row is measured/reused exactly once.
                effective = pallas_bn._block_m(c, 4)
                if effective != block:
                    log(f"[sweep] block={block} shape=({m},{c}) clamps "
                        f"to {effective}")
                key = f"{effective}:{m}x{c}"
                if key in shape_ms:
                    total += shape_ms[key] / 1e3
                    continue
                # re-check inside the block: one block's five
                # compiles can overshoot the budget into the caller's
                # hard kill, which loses the final JSON entirely
                if (args.budget_s
                        and time.perf_counter() - t_start > args.budget_s):
                    budget_exhausted = True
                    log(f"[sweep] budget exhausted mid-block {block}; "
                        "its measured shapes are saved for resume")
                    ok = False
                    break
                log(f"[sweep] block={block} shape=({m},{c}) compiling...")
                x = jnp.asarray(rng.randn(m, c).astype(np.float32) * 0.5)
                w = jnp.ones((c,), jnp.float32)
                b = jnp.zeros((c,), jnp.float32)
                coeff = jnp.asarray(rng.randn(m, c).astype(np.float32))

                def loss(x):
                    y, _, _, _ = pallas_bn.fused_batch_norm(
                        x, w, b, 1e-5, None
                    )
                    return jnp.sum(y * coeff)

                g = jax.jit(jax.grad(loss))
                try:
                    fetch_sync(g(x))  # compile + warm (fetch: PJRT lies)
                except Exception as e:  # e.g. VMEM overflow at big blocks
                    failures[f"{block}@({m},{c})"] = (
                        f"{type(e).__name__}: {e}"[:200]
                    )
                    ok = False
                    break
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    out = g(x)
                # iters dispatches of the same args are independent;
                # fetching the last bounds the batch under FIFO execution
                fetch_sync(out)
                dt = (time.perf_counter() - t0) / args.iters
                log(f"[sweep] block={block} shape=({m},{c}) {dt*1e3:.3f} ms")
                shape_ms[key] = round(dt * 1e3, 4)
                write_partial()  # every shape is chip time worth keeping
                # accumulate the ROUNDED value so a resumed run rebuilds a
                # bit-identical by_block from the same shape_ms entries
                total += shape_ms[key] / 1e3
            if ok:
                results[block] = round(total * 1e3, 3)
            write_partial()
    finally:
        pallas_bn._BLOCK_M = default_block

    write_partial(done=not budget_exhausted)
    best = min(results, key=results.get) if results else None
    print(json.dumps({
        "metric": "pallas_block_sweep",
        "unit": "ms (sum of fused fwd+bwd over R50 BN shapes)",
        "backend": jax.default_backend(),
        "by_block": {str(k): v for k, v in results.items()},
        "failures": failures,
        "budget_exhausted": budget_exhausted,
        "blocks_requested": args.blocks,
        "blocks_planned": blocks,  # execution order: default first
        "best_block": best,
        "current_default": default_block,
        "speedup_vs_default": (
            round(results[default_block] / results[best], 3)
            if best is not None and default_block in results
            else None
        ),
    }))


if __name__ == "__main__":
    main()
