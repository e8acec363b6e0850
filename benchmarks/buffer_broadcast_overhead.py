"""Per-step buffer-broadcast overhead on a converted (SyncBN) model.

DDP broadcasts module buffers from rank 0 before every forward
(``forward_sync_buffers``, ``[torch] nn/parallel/distributed.py:793``).
With full-world SyncBN the running stats are already identical on every
replica, but XLA cannot fold a value-dependent all-reduce, so the
DDP-parity broadcast is a real per-step cost on hardware. This measures
it: compiled-step all-reduce counts and step time with
``broadcast_buffers=True`` (DDP parity) vs ``"auto"`` (skips the
broadcast for converted models — the framework default).

    python benchmarks/buffer_broadcast_overhead.py --simulate 8 [--r50]
"""

import argparse
import json
import re
import time

from _common import fetch_sync, setup


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--simulate", type=int, default=8)
    p.add_argument("--r50", action="store_true",
                   help="full ResNet-50 (use on TPU; default small net)")
    p.add_argument("--per-chip-batch", type=int, default=4)
    p.add_argument("--image-size", type=int, default=None)
    p.add_argument("--steps", type=int, default=20)
    return p.parse_args()


def main():
    args = parse_args()
    setup(args.simulate)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax import nnx

    from tpu_syncbn import models, nn, parallel, runtime

    runtime.initialize()
    n = runtime.global_device_count()
    side = args.image_size or (224 if args.r50 else 16)
    global_batch = args.per_chip_batch * n

    def build(mode):
        if args.r50:
            m = models.resnet50(num_classes=1000, dtype=jnp.bfloat16,
                                rngs=nnx.Rngs(0))
        else:
            m = models.resnet18(num_classes=10, small_input=True,
                                rngs=nnx.Rngs(0))
        m = nn.convert_sync_batchnorm(m)

        def loss_fn(mo, b):
            x, y = b
            logits = mo(x).astype(jnp.float32)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()

        return parallel.DataParallel(
            m, optax.sgd(0.1, momentum=0.9), loss_fn, broadcast_buffers=mode
        )

    batch = None
    results = {}
    for mode, key in ((True, "broadcast"), ("auto", "auto_skip")):
        dp = build(mode)
        if batch is None:
            x = jnp.zeros((global_batch, side, side, 3), jnp.float32)
            y = jnp.zeros((global_batch,), jnp.int32)
            batch = jax.device_put((x, y), dp.batch_sharding)
        hlo = dp.lowered_train_step(batch).compile().as_text()
        n_ar = len(re.findall(r" all-reduce(?:-start)?\(", hlo))
        for _ in range(3):
            out = dp.train_step(batch)
        fetch_sync(out.loss)  # warmup must be DONE before t0
        t0 = time.perf_counter()
        for _ in range(args.steps):
            out = dp.train_step(batch)
        fetch_sync(out.loss)  # see _common.fetch_sync
        dt = (time.perf_counter() - t0) / args.steps
        results[key] = {
            "all_reduces_per_step": n_ar,
            "step_ms": round(dt * 1e3, 2),
            "per_step_broadcast": dp._per_step_broadcast,
        }

    b, a = results["broadcast"], results["auto_skip"]
    print(json.dumps({
        "metric": "syncbn_buffer_broadcast_overhead",
        "backend": jax.default_backend(),
        "chips": n,
        "model": "resnet50" if args.r50 else "resnet18-small",
        **{f"{k}_{kk}": vv for k, v in results.items() for kk, vv in v.items()},
        "allreduces_saved": b["all_reduces_per_step"] - a["all_reduces_per_step"],
        "step_time_saved_pct": round(
            100 * (b["step_ms"] - a["step_ms"]) / max(b["step_ms"], 1e-9), 1
        ),
    }))


if __name__ == "__main__":
    main()
