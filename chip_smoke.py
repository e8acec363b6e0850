"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one TPU chip: train, serve, kernels
    python chip_smoke.py --chips 4  # four chips: SyncBN across chips, only

One process, normal entry points only. With no option, on one chip:

* **train** — ``runtime.initialize()`` → ``nn.convert_sync_batchnorm(
  models.resnet50(num_classes=1000, dtype=bf16))`` → ``parallel.
  DataParallel(model, optax.sgd(0.1, momentum=0.9), loss_fn)`` fed by
  ``data.DataLoader`` + ``data.device_prefetch`` from a seeded synthetic
  dataset, per-chip batch 64 at 224². The
  step is compiled twice — ahead of time, then by the first dispatch —
  and the second must be a persistent-cache hit. Then 5 steps ended by
  ``block_until_ready`` and 5 ended by fetching a scalar. Every loss is
  finite and the BN running statistics have left their initial values.
* **serve** — ``serve.InferenceEngine.from_trainer(dp, buckets=(8, 32))``,
  ``engine.warm``, and a ``serve.DynamicBatcher`` that answers 16
  single-image requests; the logits equal a direct eval-mode forward of
  the synced model within bf16 tolerance.
* **kernels** — compiled on the chip, not interpreted:
  ``pallas_bn.fused_batch_norm`` forward + grad at 64×56²×256 bf16
  against the XLA path of ``ops/batch_norm.py``, and
  ``pallas_attention.flash_attention`` forward + grad (both ``backward=``
  arms) at S 2048, 16 heads, d 128, causal, against a plain ``jnp``
  softmax.

With ``--chips 4``, only the path that exists only across chips: one
SyncBN ``DataParallel`` step at per-chip batch 16 on a four-chip mesh,
against the same global batch of 64 on one chip of the same host (world
1: SyncBN is plain BN over the full batch) and, as the negative control,
against the same four-chip step without ``convert_sync_batchnorm``.

Every earlier line of standard output is one JSON object of
observations (they are not metrics). The last line is the contract's::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without a TPU the script exits non-zero and prints no result; a phase
that raises ends it non-zero with ``"ok": false``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
import traceback

# the package's loggers default to stdout; stdout is the result channel
os.environ.setdefault("TPU_SYNCBN_LOG_STREAM", "stderr")

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmarks"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a run is sized by. The defaults are the real sizes and the
    only ones ``main`` uses; the CPU rehearsal in tests/test_probe.py
    drives the same phases through :func:`run` with small ones."""

    model: str = "resnet50"
    width: int = 64
    num_classes: int = 1000
    batch: int = 64          # per chip
    side: int = 224
    steps: int = 5
    buckets: tuple = (8, 32)
    requests: int = 16
    bn_shape: tuple = (64, 56, 56, 256)
    flash_shape: tuple = (1, 2048, 16, 128)   # (B, S, heads, d)
    four_chip_batch: int = 16  # per chip


#: Relative L2 error (‖a−b‖/‖b‖) allowed between two bf16 computations
#: of the same mathematics that tile or batch differently.
BF16_TOL = 2e-2
#: The same for the global gradient norm after one step of the four-chip
#: comparison: the backward pass compounds bf16 rounding through every
#: layer, at batch 16 per chip against batch 64 on one.
GRAD_NORM_TOL = 5e-2
#: How much further from the one-chip oracle the per-replica-BN control
#: must be than the SyncBN arm, in the stem BN's running statistics,
#: before the statistics count as having crossed chips. The stem is the
#: layer that sees the raw pixels, whose statistics differ from replica
#: to replica by construction; once a BN layer has normalized each
#: replica's activations the deeper statistics hardly tell the arms
#: apart (on the chip: 1.7e7 at the stem, 10.04 over all layers
#: together), so the all-layer ratio is printed and not judged.
CONTROL_RATIO = 10.0


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def rel_err(a, b) -> float:
    """‖a−b‖/‖b‖ of two arrays of any float dtype, device or host."""
    import numpy as np
    from _common import rel_rms

    return rel_rms(np.asarray(a, np.float64), np.asarray(b, np.float64))


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


@contextlib.contextmanager
def cache_events():
    """Count persistent-compilation-cache hits and misses (an entry
    written counts as a miss) inside the block."""
    import jax

    seen = {"hits": 0, "misses": 0}

    def listener(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            seen["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            seen["misses"] += 1

    jax.monitoring.register_event_listener(listener)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_listener(listener)


def loss_fn(m, batch):
    import jax.numpy as jnp
    import optax

    x, y = batch
    logits = m(x).astype(jnp.float32)  # CE in f32
    return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()


def build_trainer(sz: Sizes, seed: int, mesh, *, sync: bool = True):
    import jax.numpy as jnp
    import optax
    from flax import nnx

    from tpu_syncbn import models, nn, parallel

    model = models.RESNETS[sz.model](
        num_classes=sz.num_classes, width=sz.width, dtype=jnp.bfloat16,
        rngs=nnx.Rngs(seed),
    )
    if sync:
        model = nn.convert_sync_batchnorm(model)
    return parallel.DataParallel(
        model, optax.sgd(0.1, momentum=0.9), loss_fn, mesh=mesh
    )


# ---------------------------------------------------------------------------
# one chip


def phase_train(sz: Sizes, seed: int):
    import jax
    import numpy as np
    from _common import fetch_sync, running_stats_vector

    from tpu_syncbn import data, runtime

    dp = build_trainer(sz, seed, runtime.data_parallel_mesh(1))
    n_steps = 1 + 2 * sz.steps  # compile step, blocked window, fetched window
    n = n_steps * sz.batch
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, sz.side, sz.side, 3), dtype=np.float32)
    y = rng.integers(0, sz.num_classes, n, dtype=np.int32)
    loader = data.DataLoader(
        data.ArrayDataset(x, y), sz.batch, num_workers=2, drop_last=True
    )
    batches = data.device_prefetch(iter(loader), sharding=dp.batch_sharding)
    stats0 = running_stats_vector(dp.rest)

    # the same program compiled twice in one run: ahead of time (what the
    # cache has never seen costs a real compile), then — with jax's
    # in-memory executables dropped, as in a fresh process — by the first
    # dispatch, which must find the first one's entry on disk
    first = next(batches)
    t0 = time.perf_counter()
    with cache_events() as aot:
        compiled = dp.lowered_train_step(first).compile()
    aot_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    del compiled
    jax.clear_caches()
    t0 = time.perf_counter()
    with cache_events() as jit:
        out = dp.train_step(first)
        jax.block_until_ready(out.loss)
    first_step_s = time.perf_counter() - t0
    if not (jit["hits"] >= 1 and jit["misses"] == 0):
        raise AssertionError(
            f"second compile of the step was not a cache hit: {jit}"
        )
    losses = [out.loss]

    t0 = time.perf_counter()
    for _ in range(sz.steps):
        out = dp.train_step(next(batches))
        losses.append(out.loss)
    jax.block_until_ready(out.loss)
    blocked_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(sz.steps):
        out = dp.train_step(next(batches))
        losses.append(out.loss)
    fetch_sync(out.loss)
    fetched_s = time.perf_counter() - t0

    losses = [float(l) for l in losses]
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    stats1 = running_stats_vector(dp.rest)
    moved = float(np.mean(stats1 != stats0))
    if not (np.all(np.isfinite(stats1)) and moved > 0.9):
        raise AssertionError(
            f"BN running stats did not move (moved fraction {moved})"
        )
    return dp, {
        "model": sz.model, "per_chip_batch": sz.batch, "side": sz.side,
        "steps": n_steps, "losses": [round(l, 4) for l in losses],
        "bn_stats_moved_frac": round(moved, 4),
        "compile": {
            "aot_s": round(aot_s, 2),
            "aot_cache": "hit" if aot["hits"] and not aot["misses"]
            else "miss",
            "first_dispatch_s": round(first_step_s, 2),
            "first_dispatch_cache": "hit",
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        },
        f"{sz.steps}_steps_wall_s": {
            "ended_by_block_until_ready": round(blocked_s, 4),
            "ended_by_scalar_fetch": round(fetched_s, 4),
        },
    }


def phase_serve(dp, sz: Sizes, seed: int) -> dict:
    import numpy as np
    from flax import nnx

    from tpu_syncbn import serve

    rng = np.random.default_rng(seed + 1)
    x = rng.standard_normal(
        (sz.requests, sz.side, sz.side, 3), dtype=np.float32
    )
    engine = serve.InferenceEngine.from_trainer(dp, buckets=sz.buckets)
    t0 = time.perf_counter()
    engine.warm(x[:1])
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    with serve.DynamicBatcher(engine, max_wait_ms=20.0) as batcher:
        futures = [batcher.submit(x[i:i + 1]) for i in range(sz.requests)]
        outs = [f.result(timeout=300) for f in futures]
    answer_s = time.perf_counter() - t0
    logits = np.concatenate([np.asarray(o, np.float32) for o in outs])
    if logits.shape != (sz.requests, sz.num_classes):
        raise AssertionError(f"logits shape {logits.shape}")
    if not np.all(np.isfinite(logits)):
        raise AssertionError("non-finite logits")

    # the reference: the synced model itself, eval mode, one direct call
    model = dp.sync_to_model()
    model.eval()
    ref = np.asarray(nnx.jit(lambda m, b: m(b))(model, x), np.float32)
    err = rel_err(logits, ref)
    if not err <= BF16_TOL:
        raise AssertionError(
            f"served logits differ from the direct forward: rel err {err}"
        )
    return {
        "requests_answered": len(outs), "buckets": list(engine.buckets),
        "programs_compiled": engine.stats()["programs_compiled"],
        "warm_s": round(warm_s, 2), "answer_wall_s": round(answer_s, 4),
        "logits_rel_err_vs_direct_forward": err, "tolerance": BF16_TOL,
    }


def phase_kernels(sz: Sizes, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from tpu_syncbn.ops import _pallas_common, batch_norm as xla_bn
    from tpu_syncbn.ops import pallas_attention, pallas_bn

    interpreted = _pallas_common.interpret()
    if jax.default_backend() == "tpu" and interpreted is not False:
        raise AssertionError("kernels would run interpreted on the chip")
    keys = jax.random.split(jax.random.key(seed + 2), 8)
    errs: dict[str, float] = {}

    def compare(name, got_tree, want_tree):
        for i, (g, w) in enumerate(zip(jax.tree_util.tree_leaves(got_tree),
                                       jax.tree_util.tree_leaves(want_tree))):
            errs[f"{name}[{i}]"] = rel_err(g, w)

    # -- fused BN forward + grad against the XLA path ----------------------
    c = sz.bn_shape[-1]
    x = (0.5 + 2.0 * jax.random.normal(keys[0], sz.bn_shape)).astype(
        jnp.bfloat16)
    w = jax.random.uniform(keys[1], (c,), jnp.float32, 0.5, 1.5)
    b = jax.random.normal(keys[2], (c,), jnp.float32)
    g = jax.random.normal(keys[3], sz.bn_shape, jnp.float32)

    # (the cotangent weights ride in as an argument: closed over, they
    # would be baked into the executable as a constant of their size)
    def pallas_loss(x, w, b, g):
        y, mean, var, _ = pallas_bn.fused_batch_norm(x, w, b, 1e-5, None)
        return (y.astype(jnp.float32) * g).sum(), (y, mean, var)

    def xla_loss(x, w, b, g):
        y, _ = xla_bn.batch_norm_train(x, None, None, None, w, b, eps=1e-5)
        mean, var, _ = xla_bn.sync_moments(x, axis_name=None)
        return (y.astype(jnp.float32) * g).sum(), (y, mean, var)

    vg = lambda f: jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                              has_aux=True))
    t0 = time.perf_counter()
    (_, p_fwd), p_grads = vg(pallas_loss)(x, w, b, g)
    with xla_bn.pallas_mode("off"):
        (_, x_fwd), x_grads = vg(xla_loss)(x, w, b, g)
    compare("bn.fwd(y,mean,var)", p_fwd, x_fwd)
    compare("bn.grad(x,w,b)", p_grads, x_grads)
    bn_s = time.perf_counter() - t0

    # -- flash attention forward + grad against a plain softmax -----------
    q, k, v = (jax.random.normal(kk, sz.flash_shape).astype(jnp.bfloat16)
               for kk in keys[4:7])
    go = jax.random.normal(keys[7], sz.flash_shape, jnp.float32)
    hi = jax.lax.Precision.HIGHEST

    def plain(q, k, v):
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi)
        s = s * q.shape[-1] ** -0.5
        causal = jnp.tril(jnp.ones(s.shape[-2:], bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=hi)

    def loss_of(attn):
        def loss(q, k, v, go):
            o = attn(q, k, v)
            return (o.astype(jnp.float32) * go).sum(), o
        return loss

    t0 = time.perf_counter()
    (_, ref_o), ref_grads = vg(loss_of(plain))(q, k, v, go)
    for backward in ("xla", "pallas"):
        flash = lambda q, k, v: pallas_attention.flash_attention(
            q, k, v, causal=True, backward=backward)
        (_, o), grads = vg(loss_of(flash))(q, k, v, go)
        compare(f"flash[{backward}].fwd", o, ref_o)
        compare(f"flash[{backward}].grad(q,k,v)", grads, ref_grads)
    flash_s = time.perf_counter() - t0

    bad = {n: e for n, e in errs.items() if not e <= BF16_TOL}
    if bad:
        raise AssertionError(f"kernel parity beyond {BF16_TOL}: {bad}")
    return {
        "interpret": interpreted, "bn_shape": list(sz.bn_shape),
        "flash_shape": list(sz.flash_shape), "tolerance": BF16_TOL,
        "rel_err": {n: float(f"{e:.3g}") for n, e in errs.items()},
        "bn_wall_s": round(bn_s, 2), "flash_wall_s": round(flash_s, 2),
    }


# ---------------------------------------------------------------------------
# four chips


def phase_four_chip(sz: Sizes, seed: int) -> dict:
    import jax
    import numpy as np

    from tpu_syncbn import runtime

    mesh4 = runtime.data_parallel_mesh(4)
    if len(set(mesh4.devices.flat)) != 4:
        raise AssertionError(f"mesh does not hold 4 distinct devices: {mesh4}")
    per_chip = sz.four_chip_batch
    n = 4 * per_chip
    # random pixels with a mean and a scale of each sample's own, so that
    # sixteen samples' statistics are visibly not the sixty-four's
    rng = np.random.default_rng(seed)
    shift = rng.standard_normal((n, 1, 1, 1)).astype(np.float32)
    scale = rng.uniform(0.5, 1.5, (n, 1, 1, 1)).astype(np.float32)
    x = shift + scale * rng.standard_normal(
        (n, sz.side, sz.side, 3), dtype=np.float32)
    y = rng.integers(0, sz.num_classes, n, dtype=np.int32)

    def bn_moved(rest):
        """{layer path: what one step added to that running stat}"""
        out = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(rest)[0]:
            name = jax.tree_util.keystr(path)
            if "running_mean" in name:
                out[name] = np.asarray(leaf, np.float64)
            elif "running_var" in name:
                out[name] = np.asarray(leaf, np.float64) - 1.0
        return out

    def arm(mesh, *, sync):
        dp = build_trainer(sz, seed, mesh, sync=sync)
        batch = jax.device_put((x, y), dp.batch_sharding)
        world = int(mesh.size)
        if len(batch[0].sharding.device_set) != world or any(
                s.data.shape[0] != n // world
                for s in batch[0].addressable_shards):
            raise AssertionError(
                f"batch not split {world} ways: {batch[0].sharding}")
        leaf = jax.tree_util.tree_leaves(dp.params)[0]
        if leaf.sharding.device_set != set(mesh.devices.flat):
            raise AssertionError(
                f"params not on every device of the mesh: {leaf.sharding}")
        t0 = time.perf_counter()
        text = dp.lowered_train_step(batch).compile().as_text()
        compile_s = time.perf_counter() - t0
        out = dp.train_step(batch)
        return {
            "loss": float(out.loss),
            "grad_norm": float(out.monitors["grad_norm"]),
            "bn": bn_moved(dp.rest),
            "all_reduces": text.count(" all-reduce("),
            "compile_s": round(compile_s, 2),
        }

    oracle = arm(runtime.data_parallel_mesh(1), sync=True)
    synced = arm(mesh4, sync=True)
    local = arm(mesh4, sync=False)
    if synced["all_reduces"] < 2:
        raise AssertionError(
            "no gradient and statistics all-reduces in the four-chip step")

    def errors(a):
        per_layer = {k: rel_err(a["bn"][k], oracle["bn"][k])
                     for k in oracle["bn"]}
        cat = lambda d: np.concatenate([d[k].ravel() for k in oracle["bn"]])
        stem = [e for k, e in per_layer.items() if "stem_bn" in k]
        return {
            "loss": abs(a["loss"] - oracle["loss"]) / abs(oracle["loss"]),
            "grad_norm": abs(a["grad_norm"] - oracle["grad_norm"])
            / abs(oracle["grad_norm"]),
            "bn_stem": max(stem),
            "bn_all": rel_err(cat(a["bn"]), cat(oracle["bn"])),
            "bn_worst_layer": max(per_layer.values()),
        }

    e_sync, e_local = errors(synced), errors(local)
    info = {
        "per_chip_batch": per_chip, "global_batch": n, "side": sz.side,
        "bn_layers": len(oracle["bn"]) // 2,
        "arms": {
            name: {k: a[k] for k in
                   ("loss", "grad_norm", "all_reduces", "compile_s")}
            for name, a in (("one_chip_oracle", oracle),
                            ("four_chip_syncbn", synced),
                            ("four_chip_per_replica_bn", local))
        },
        "rel_err_vs_oracle": {
            "four_chip_syncbn": e_sync, "four_chip_per_replica_bn": e_local,
        },
        "tolerance": {"loss": BF16_TOL, "grad_norm": GRAD_NORM_TOL,
                      "bn_stem": BF16_TOL, "bn_all": BF16_TOL},
        "control_ratio_required": CONTROL_RATIO,
        "control_ratio": {k: e_local[k] / max(e_sync[k], 1e-30)
                          for k in ("bn_stem", "bn_all")},
    }
    emit({"phase": "four_chip", **info})  # the three arms' errors, pass or not
    bad = {k: e_sync[k] for k, tol in info["tolerance"].items()
           if not e_sync[k] <= tol}
    if bad:
        raise AssertionError(
            f"four-chip SyncBN step differs from the one-chip oracle: {bad}")
    if not info["control_ratio"]["bn_stem"] >= CONTROL_RATIO:
        raise AssertionError(
            "per-replica BN is not further from the oracle than SyncBN by "
            f"{CONTROL_RATIO}x at the stem — did the statistics cross "
            f"chips? {info['control_ratio']}")
    return info


# ---------------------------------------------------------------------------


def device_line() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def run(sz: Sizes, chips: int, seed: int = 0) -> dict:
    """Every phase for ``chips`` chips, one JSON line each; returns the
    last line's object. A phase that fails raises."""
    import importlib.metadata as md

    import jax

    from tpu_syncbn import runtime
    from tpu_syncbn.runtime import distributed, native

    runtime.initialize()  # turns the compile cache on, like every entry point
    cache_dir = distributed.enable_persistent_compilation_cache()
    if jax.config.jax_compilation_cache_dir != cache_dir:
        raise AssertionError("compile cache is not where it was placed")
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu", "flax", "optax"):
        with contextlib.suppress(md.PackageNotFoundError):
            versions[pkg] = md.version(pkg)
    emit({
        "phase": "start", "versions": versions, "device": device_line(),
        "chips_used": chips, "seed": seed,
        "compile_cache_dir": cache_dir,
        "compile_cache_dir_from_env":
            bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "native_library": native.status(),
    })

    def timed(name, fn, *args):
        t0 = time.perf_counter()
        try:
            out = fn(*args)
        except Exception as e:
            e.add_note(f"phase: {name}")  # for the "ok": false line
            raise
        info = out[1] if isinstance(out, tuple) else out
        tail = {"wall_s": round(time.perf_counter() - t0, 2),
                "peak_bytes_in_use": peak_bytes()}
        if name != "four_chip":  # that phase prints its own errors first
            emit({"phase": name, **info, **tail})
        else:
            emit({"phase": name + ".done", **tail})
        return out

    if chips == 4:
        timed("four_chip", phase_four_chip, sz, seed)
    else:
        dp, _ = timed("train", phase_train, sz, seed)
        timed("serve", phase_serve, dp, sz, seed)
        timed("kernels", phase_kernels, sz, seed)
    return {"ok": True, "device": device_line()}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the four-chip SyncBN step and what it is "
        "compared with (default: the one-chip phases)")
    args = parser.parse_args(argv)

    # a directory that holds this file alone has no package: ImportError,
    # exit 1, nothing printed
    from tpu_syncbn.runtime import probe

    info = probe.ensure_backend(args.chips)  # raises: no TPU, too few chips
    if info.platform != "tpu":
        # JAX_PLATFORMS=cpu is an honest choice everywhere else; this
        # script exists to prove the chip, so here it is a refusal
        print(f"chip_smoke needs a TPU, found {info.platform!r}",
              file=sys.stderr)
        return 2
    try:
        last = run(Sizes(), args.chips)
    except Exception as e:
        traceback.print_exc()
        phase = [n[7:] for n in getattr(e, "__notes__", ())
                 if n.startswith("phase: ")]
        emit({"ok": False, "phase": phase[0] if phase else "start",
              "error": f"{type(e).__name__}: {e}"[:2000],
              "device": device_line()})
        return 1
    emit(last)
    return 0


if __name__ == "__main__":
    sys.exit(main())
