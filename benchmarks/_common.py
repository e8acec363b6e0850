"""Shared benchmark bootstrap: --simulate N wiring (forced host devices
on the explicitly chosen CPU platform), the chip requirement otherwise,
and repo-root imports."""

import os
import sys
import time


def log(*a, ts: bool = False) -> None:
    """Stderr progress line (stdout is reserved for the final JSON);
    ``ts=True`` prefixes a timestamp for long-running watchers."""
    if ts:
        a = (time.strftime("[%H:%M:%S]"),) + a
    print(*a, file=sys.stderr, flush=True)


def running_stats_vector(state):
    """Concatenate every BN running-stat leaf (``running_mean`` /
    ``running_var``) of an nnx State into one flat numpy vector — the
    direct object SyncBN synchronizes, used by the convergence A/Bs as a
    trajectory-noise-robust measure of the statistics mechanism."""
    import jax
    import numpy as np

    leaves = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        if any("running" in str(k) for k in path):
            leaves.append(np.asarray(leaf).ravel())
    if not leaves:
        raise ValueError("state carries no running_* BN leaves")
    return np.concatenate(leaves)


def rel_rms(a, b) -> float:
    """Relative RMS distance ||a-b|| / ||b|| (b = the reference arm)."""
    import numpy as np

    return float(
        np.sqrt(np.mean((a - b) ** 2)) / (np.sqrt(np.mean(b**2)) + 1e-12)
    )


def ab_divergence_blocks(curves, oracle_stats, sync_stats, local_stats,
                         *, early_steps=50):
    """The two report blocks shared by every convergence A/B
    (gan/detection_convergence_ab): the pre-chaos early-window loss MAEs
    (trajectory chaos dominates whole-curve MAE past ~tens of steps) and
    the BN running-stats distance (the very quantity SyncBN synchronizes,
    immune to trajectory chaos).

    ``curves`` maps name -> (oracle, sync, local) per-step loss arrays;
    multi-curve entries (GAN's D and G) are summed into one MAE.
    """
    import numpy as np

    E = min(early_steps, *(len(o) for o, _, _ in curves.values()))
    sync_early = float(sum(
        np.abs(s[:E] - o[:E]).mean() for o, s, _ in curves.values()
    ))
    local_early = float(sum(
        np.abs(l[:E] - o[:E]).mean() for o, _, l in curves.values()
    ))
    stats_sync = rel_rms(sync_stats, oracle_stats)
    stats_local = rel_rms(local_stats, oracle_stats)
    return {
        "early_window": {
            "steps": E,
            "syncbn_loss_mae": round(sync_early, 6),
            "perreplica_loss_mae": round(local_early, 6),
            "divergence_ratio": round(local_early / max(sync_early, 1e-12), 2),
        },
        "running_stats_rel_rms_vs_oracle": {
            "syncbn": round(stats_sync, 6),
            "perreplica": round(stats_local, 6),
            "ratio": round(stats_local / max(stats_sync, 1e-12), 2),
        },
    }


def setup(simulate: int | None, *, needs_backend: bool = True) -> None:
    """``--simulate N`` chooses the CPU with N virtual devices; without
    it the benchmark needs the chip and raises when there is none (no
    fallback — ``JAX_PLATFORMS=cpu`` is the explicit CPU choice).
    Benchmarks that never touch a jax backend pass
    ``needs_backend=False``."""
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    from tpu_syncbn.runtime import distributed, probe

    if simulate:
        probe.force_cpu(simulate)
    elif needs_backend:
        probe.ensure_backend(1)
    else:
        return
    distributed.enable_persistent_compilation_cache()


def fetch_sync(out) -> float:
    """Timing barrier for on-chip measurements: FETCH a value instead of
    calling ``block_until_ready``.

    A PJRT backend was once caught reporting buffer readiness before
    execution completed (2026-07-31, over an access path that is gone:
    the per-step "blocked" arm timed FASTER than the chained arm). A
    device-to-host copy cannot complete before
    the value exists, so fetching one scalar of the last output is a
    barrier that holds whatever the runtime does. ``chip_smoke.py``
    times its steps under both barriers on the machine there is. For
    chained computations (donated train-step state, fori_loop carries)
    the fetched leaf transitively forces the whole chain; for a loop of
    independent dispatches it bounds the batch under the TPU runtime's
    FIFO single-stream execution.

    Accepts any array / StepOutput / pytree; fetches the first leaf's
    first element and returns it as a float (f32-cast so bf16 leaves
    fetch cleanly).
    """
    import jax
    import jax.numpy as jnp

    leaf = jax.tree_util.tree_leaves(out)[0]
    return float(jnp.ravel(leaf)[0].astype(jnp.float32))
