"""The comparison that decides ``correct``.

Before the window, outside the timing, on the first batch:

(a) what the program computes in training mode through the trainer's own
    mesh (the family's ``outputs``) against the plain reference
    (``reference.py``, at the configuration's stated precision), in
    pieces: the backbone's maps C2..C5 from the pixels, and what follows
    the backbone (pooling and classifier; FPN and head) from the
    program's own maps; and the loss of the first ``train_step`` against
    the reference's loss where it has one;
(b) the stem BN's running mean and variance after that step against the
    reference's statistics of the **global** batch. Across chips this is
    the check that the statistics crossed them: per-replica BN is
    hundreds of percent off here (PERF.md, PR 21).

After the window: every loss finite, and the BN running statistics
finite and moved.

Tolerances are relative L2 errors, |a-b| / |b|, each with what was
measured on the chip against it (PERF.md, PR 24):

* ``OUTPUT_TOL`` 5e-2, for C2 (measured 0.007-0.009) and for the pieces
  after the backbone (logits 0.002; class logits and box deltas 0.005 to
  0.007). The
  reference rounds where the configuration says the program does, so
  what is left is the order of float32 accumulation, an occasional last
  bit of a bf16 activation and, in FPN and head, the TPU's default
  (bf16-pass) float32 convolution against HIGHEST. An 8-bit float
  format (2**-4 an element), a dropped branch or a wrong stride fail it.
* ``DEEP_TOL`` 0.7, for C3..C5: a coarse check for a wrong or missing
  layer (an unrelated map is 1.4 off). At random initialization the BN
  layers amplify a last-bit difference in an early activation from map
  to map: C2 0.007-0.009, C3 0.03, C4 0.13-0.15, C5 0.30-0.36 in both
  configurations over six seeds, so nothing tighter holds there.
* ``LOSS_TOL`` 1e-2: at initialization the loss is close to
  log(classes) whatever the logits, so it is a weak check, kept tight
  (measured 1e-4 to 4e-4).
* ``STEM_TOL`` 1e-3: one convolution of the raw pixels, its statistics
  taken in float32 over at least 1e5 elements a channel (measured 4e-8
  to 2e-7). Statistics that stayed on one chip are of order 1 off.
"""

from __future__ import annotations

import math

import numpy as np

OUTPUT_TOL = 5e-2
DEEP_TOL = 0.7
LOSS_TOL = 1e-2
STEM_TOL = 1e-3
MOVED_SHARE = 0.9
DEEP_MAPS = ("c3", "c4", "c5")


def pure(state) -> dict:
    """An nnx State as nested dicts of arrays, keyed as the program
    names its parameters."""
    from flax import nnx

    return nnx.to_pure_dict(state)


def running_stats(rest) -> np.ndarray:
    """Every BN running mean and variance of the trainer, as one host
    vector."""
    import jax

    leaves = [
        leaf for path, leaf in jax.tree_util.tree_flatten_with_path(rest)[0]
        if "running_" in jax.tree_util.keystr(path)
    ]
    return np.concatenate([np.asarray(x, np.float64).ravel()
                           for x in jax.device_get(leaves)])


def program_outputs(dp, family, batch) -> dict:
    """The family's outputs from the program's model in training mode,
    each replica on its shard of the batch inside the trainer's mesh, so
    that SyncBN's collectives run as they do in the step."""
    import jax
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn import compat

    def forward(params, rest, shard):
        # copy=True: BN's running-statistics update happens on
        # variables of this trace, as in the trainer's own step
        model = compat.nnx_merge(dp.graphdef, params, rest, copy=True)
        model.train()
        return family.outputs(model, shard)

    return jax.jit(jax.shard_map(
        forward, mesh=dp.mesh,
        in_specs=(P(), P(), P(dp.axis_name)), out_specs=P(dp.axis_name),
        check_vma=False,
    ))(dp.params, dp.rest, batch)


def first_step(dp, family, cfg: dict, batch,
               mark=lambda name: None) -> tuple[dict, float]:
    """Runs the reference, the program's forward and the first
    ``train_step`` on ``batch``; returns the errors and that step's
    loss. The outputs are compared on the device, where they are: the
    backbone maps of 128 images are 385 MB. ``mark(name)`` is called at
    the end of each of the three."""
    import jax

    from chipbench import reference

    got = jax.block_until_ready(program_outputs(dp, family, batch))
    mark("program_forward")
    ref = jax.jit(family.reference_fn(cfg))(pure(dp.params), batch, got)
    errors = {name: float(e) for name, e in ref["errors"].items()}
    del got
    mark("reference")
    loss = float(dp.train_step(batch).loss)  # donates the state: last
    mark("first_step")
    if "loss" in ref:
        want = float(ref["loss"])
        errors["loss"] = abs(loss - want) / abs(want)
    stem = family.stem_running_stats(pure(dp.rest))
    for name, want in reference.expected_running_stats(ref["stem"]).items():
        errors["stem_" + name] = float(reference.rel_l2(stem[name], want))
    return errors, loss


def tolerance(name: str) -> float:
    if name == "loss":
        return LOSS_TOL
    if name.startswith("stem_"):
        return STEM_TOL
    return DEEP_TOL if name in DEEP_MAPS else OUTPUT_TOL


def verdict(errors: dict, losses: list[float], stats_before: np.ndarray,
            stats_after: np.ndarray) -> dict:
    failed = sum(1 for v in losses if not math.isfinite(v))
    moved = float(np.mean(stats_after != stats_before))
    bad = {k: v for k, v in errors.items()
           if not (math.isfinite(v) and v <= tolerance(k))}
    ok = (not bad and failed == 0 and moved >= MOVED_SHARE
          and bool(np.all(np.isfinite(stats_after))))
    return {"correct": ok, "failed": failed, "stats_moved_share": moved,
            "errors": errors, "out_of_tolerance": sorted(bad)}
