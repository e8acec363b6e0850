"""Layer 1 + layer 3 of the program auditor: trace every compiled
program the stack builds and hold it to its pinned
:class:`ProgramContract` (collectives/donation/callbacks/upcasts) and
the attached :class:`~tpu_syncbn.audit.contracts.ShardingContract`
(layout flow, replication, per-device memory).

The registry below builds each program the way the trainers/engine
actually build it — same step factories, same shard_map specs, same
donation — on tiny deterministic models over the standard meshes, then
extracts contracts **abstractly** (``jax.make_jaxpr`` + ``.lower()``;
nothing compiles or executes unless the caller asks for the
``memory_analysis`` cross-check). Audited programs:

* ``dataparallel.train_step`` — the paper's program: BN-stat psum +
  grad pmean + loss/metric reductions, full state donated.
* ``dataparallel.zero_guard.train_step`` — ``zero=True`` with the PR 1
  divergence guard armed: adds the param all_gather, the grad
  reduce_scatter, and the guard's world-consensus ``pmin``.
* ``gan.train_step`` — GANTrainer's fused D-then-G program (both
  updates, both networks' BN stats, replica-0 buffer broadcasts).
* ``dataparallel.scan_k{1,4}.train_steps`` — the fused K-step scan
  program at K=1 and K=4. Collectives live in the scan *body*, so the
  contract is K-invariant by construction.
* ``serve.eval_bucket8`` — the InferenceEngine bucket program: **zero
  collectives**, **no donation**, batch in and out ``P('data')``.
* ``tensor.tp_mlp`` — the Megatron MLP pairing (column → gelu → row):
  exactly ONE ``psum`` over the ``model`` axis, weights arriving
  pre-sharded ``P(None,'model')`` / ``P('model',None)``.
* ``pipeline.gpipe`` — the GPipe forward schedule: one ``ppermute`` in
  the scan body (the ring hand-off) and NOTHING else — the historical
  last-stage psum mask is gone (ISSUE 15: stage-stacked ``P('pipe')``
  out-spec; ``contract.pipeline_ring`` pins psum-free).
* ``pipeline.train_{gpipe,1f1b}`` — the fused pipeline TRAINING step
  on the 2-D (data x pipe) mesh: exactly two ``ppermute``s in the tick
  scan body (activations right, cotangents left), the loss psum +
  data-axis grad pmean, and — on the 1f1b program — the armed
  divergence guard's ``pmin``. The two contracts differ ONLY in the
  guard: collectives live in the tick body, so they are
  schedule-invariant by construction (the GPipe/1F1B tick tables are
  scan constants).
* ``expert.switch_moe`` — Switch MoE over the ``expert`` axis: exactly
  two ``all_to_all``s (dispatch + return) and the aux-loss ``pmean``.
* ``sequence.ring_attention`` — the KV ring: one ``ppermute`` in the
  scan body, sequence sharded ``P(None,'seq')`` end to end.

The last four are the previously-siloed strategies' first pinned ground
truth — the regression floor the ROADMAP item-1 SpecLayout refactor
must preserve.

Contracts are compared against goldens in ``tests/contracts/``
(re-pin with ``python -m tpu_syncbn.audit --write-goldens`` after an
*intentional* change — the CLI prints the old→new field diff and
refuses to overwrite a mismatching golden without ``--force``). Golden
byte estimates depend on the mesh world, so contracts record the world
they were pinned on (the CLI forces the 8-device CPU mesh the test
suite uses).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Sequence

from tpu_syncbn.audit.contracts import (
    ProgramContract,
    compare_contracts,
    extract_contract,
    load_contract,
    save_contract,
)
from tpu_syncbn.audit.srclint import Violation

#: Mesh world the goldens are pinned on (the test suite's virtual CPU
#: mesh — conftest.py and the audit CLI both force this device count).
PINNED_WORLD = 8

_GLOBAL_BATCH = 16
_FEATURES = 8
_LATENT = 4


def lossy_collective_bytes(contract: ProgramContract) -> int:
    """The ISSUE 12 'lossy-eligible' wire bytes of a program: every
    collective byte except the ``pmin`` family — the divergence guard's
    finiteness consensus is pinned exact-fp32 and excluded from the
    compression claim on both sides of the ratio."""
    return sum(v for k, v in contract.collective_bytes.items()
               if k != "pmin")


def default_golden_dir() -> str:
    """``tests/contracts/`` next to the package — valid for in-repo use
    (the CLI accepts ``--contracts-dir`` for anything else)."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), "tests", "contracts")


def golden_path(golden_dir: str, name: str) -> str:
    return os.path.join(golden_dir, f"{name}.json")


@dataclasses.dataclass
class ProgramSpec:
    """Everything the extractor needs about one registered program:
    the jitted callable, abstract example arguments, the per-argument
    labels/donation, and the mesh + per-argument prefix specs the
    layer-3 sharding pass propagates from."""

    name: str
    fn: Callable
    example_args: tuple
    arg_labels: tuple[str, ...]
    world: int
    mesh: Any
    in_specs: tuple
    declared_donated: tuple[str, ...] = ()


# ---------------------------------------------------------------------------
# tiny deterministic models (contract fixtures, not benchmarks)


def _tiny_model():
    from flax import nnx

    from tpu_syncbn import nn as tnn

    class Net(nnx.Module):
        def __init__(self, rngs):
            self.fc = nnx.Linear(_FEATURES, _FEATURES, rngs=rngs)
            self.bn = tnn.BatchNorm1d(_FEATURES)

        def __call__(self, x):
            return self.bn(self.fc(x))

    return tnn.convert_sync_batchnorm(Net(nnx.Rngs(0)))


def _tiny_gan():
    from flax import nnx

    from tpu_syncbn import nn as tnn

    class G(nnx.Module):
        def __init__(self, rngs):
            self.fc = nnx.Linear(_LATENT, _FEATURES, rngs=rngs)
            self.bn = tnn.BatchNorm1d(_FEATURES)

        def __call__(self, z):
            return self.bn(self.fc(z))

    class D(nnx.Module):
        def __init__(self, rngs):
            self.fc = nnx.Linear(_FEATURES, 1, rngs=rngs)
            self.bn = tnn.BatchNorm1d(1)

        def __call__(self, x):
            return self.bn(self.fc(x))

    return (tnn.convert_sync_batchnorm(G(nnx.Rngs(0))),
            tnn.convert_sync_batchnorm(D(nnx.Rngs(1))))


def _mse(m, b):
    return (m(b) ** 2).mean()


def _compress_mlp():
    """Wider BN-free MLP for the compressed-collective programs: the
    gradient payload (~2.2k params) dominates, and with no BatchStat
    buffers every byte in the program is either gradient/loss payload
    (lossy-eligible) or the guard's fp32 pmin (pinned exact) — which is
    what makes the ≥2×/≥3.5× bytes-on-wire invariant sharp instead of
    diluted by fixture constants. The SyncBN stats path has its own
    pinned program (``syncbn.compressed_stats``)."""
    import jax.numpy as jnp
    from flax import nnx

    class MLP(nnx.Module):
        def __init__(self, rngs):
            self.fc1 = nnx.Linear(_FEATURES, 16 * _FEATURES, rngs=rngs)
            self.fc2 = nnx.Linear(16 * _FEATURES, _FEATURES, rngs=rngs)

        def __call__(self, x):
            return self.fc2(jnp.tanh(self.fc1(x)))

    return MLP(nnx.Rngs(0))


def _batch_struct(*lead):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct((*lead, _FEATURES), jnp.float32)


def _axis_mesh(axis_name: str):
    """All devices on one named axis, through the canonical mesh
    factory (ROADMAP item 1: strategy modules consume the shared
    layout instead of building private meshes)."""
    from tpu_syncbn.runtime import distributed as dist

    return dist.make_mesh({axis_name: -1})


# ---------------------------------------------------------------------------
# program registry


def _dp_train_step() -> ProgramSpec:
    import optax
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn import parallel

    dp = parallel.DataParallel(
        _tiny_model(), optax.sgd(0.1, momentum=0.9), _mse
    )
    return ProgramSpec(
        name="dataparallel.train_step",
        fn=dp._train_step,
        example_args=(dp._param_store, dp.rest, dp.opt_state,
                      _batch_struct(_GLOBAL_BATCH)),
        arg_labels=("params", "rest", "opt_state", "batch"),
        declared_donated=("params", "rest", "opt_state"),
        world=dp.world,
        mesh=dp.mesh,
        in_specs=(dp._pspec, dp._rest_spec, dp._opt_spec,
                  P(dp.axis_name)),
    )


def _dp_zero_guard_train_step() -> ProgramSpec:
    import optax
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn import parallel

    dp = parallel.DataParallel(
        _tiny_model(), optax.adam(1e-3), _mse,
        zero=True, divergence_guard="skip_step",
    )
    return ProgramSpec(
        name="dataparallel.zero_guard.train_step",
        fn=dp._train_step,
        example_args=(dp._param_store, dp.rest, dp.opt_state,
                      _batch_struct(_GLOBAL_BATCH)),
        arg_labels=("params", "rest", "opt_state", "batch"),
        declared_donated=("params", "rest", "opt_state"),
        world=dp.world,
        mesh=dp.mesh,
        in_specs=(dp._pspec, dp._rest_spec, dp._opt_spec,
                  P(dp.axis_name)),
    )


def _dp_scan(k: int) -> ProgramSpec:
    import optax
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn import parallel
    from tpu_syncbn.parallel import scan_driver

    dp = parallel.DataParallel(
        _tiny_model(), optax.sgd(0.1, momentum=0.9), _mse
    )
    fn = dp._build_train_steps(k, stacked=True)
    return ProgramSpec(
        name=f"dataparallel.scan_k{k}.train_steps",
        fn=fn,
        example_args=(dp._param_store, dp.rest, dp.opt_state,
                      _batch_struct(k, _GLOBAL_BATCH)),
        arg_labels=("params", "rest", "opt_state", "batches"),
        declared_donated=("params", "rest", "opt_state"),
        world=dp.world,
        mesh=dp.mesh,
        in_specs=(dp._pspec, dp._rest_spec, dp._opt_spec,
                  scan_driver.stack_batch_spec(P(dp.axis_name))),
    )


def _layout_train_step(kind: str) -> ProgramSpec:
    """The ISSUE 20 trio: the SAME wide BN-free adam MLP train step
    under (a) plain DP (replicated params + opt state), (b) composed
    DP×FSDP on the 2-D ``(data=2, fsdp=4)`` mesh — batch
    ``P(('data','fsdp'))``, flat param/opt shards over ``fsdp`` — and
    (c) DP×FSDP with the int8 gradient wire. Adam's two moment slots
    make optimizer state the dominant resident tensor, so the
    composed contract's ``peak_bytes_per_device`` dropping below the
    ``contract.fsdp_peak_memory`` ceiling (≤ 0.6× DP-only) is the
    memory claim of the layout composition, machine-checked."""
    import optax
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn import parallel

    kw: dict = {}
    if kind == "dp":
        layout = parallel.SpecLayout.data_parallel()
    else:
        layout = parallel.SpecLayout.fsdp(data=-1, fsdp=4)
        if kind == "dp_fsdp_int8":
            kw["compress"] = "int8"
    dp = parallel.DataParallel(
        _compress_mlp(), optax.adam(1e-3), _mse, layout=layout, **kw
    )
    return ProgramSpec(
        name=f"layout.{kind}.train_step",
        fn=dp._train_step,
        example_args=(dp._param_store, dp.rest, dp.opt_state,
                      _batch_struct(_GLOBAL_BATCH)),
        arg_labels=("params", "rest", "opt_state", "batch"),
        # BN-free fixture: `rest` is an empty tree (see the compressed
        # trio above) — declaring it donated trips donation_lost
        declared_donated=("params", "opt_state"),
        world=int(dp.mesh.size),
        mesh=dp.mesh,
        in_specs=(dp._pspec, dp._rest_spec, dp._opt_spec,
                  P(dp.axis_name)),
    )


def _layout_serve_eval() -> ProgramSpec:
    """The fsdp-composed serving program (ISSUE 20 satellite bugfix):
    an engine derived from a param-sharding layout stores flat
    1/shard_world shards and gathers them INSIDE the eval program —
    the pinned ``max_replicated_bytes`` is the gathered tree, not a
    replicated resident input."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn import parallel
    from tpu_syncbn.serve.engine import InferenceEngine

    import jax

    layout = parallel.SpecLayout.fsdp(data=-1, fsdp=4)
    eng = InferenceEngine(_tiny_model(), layout=layout, buckets=(8,))
    fn = jax.jit(eng._sharded_fwd())
    batch = _batch_struct(8)
    pspec = {dt: P(layout.param_shard_axis)
             for dt in eng._flat.shard_sizes}
    return ProgramSpec(
        name="layout.serve.eval_fsdp",
        fn=fn,
        example_args=(eng._params, eng._rest, batch),
        arg_labels=("params", "rest", "batch"),
        declared_donated=(),
        world=int(eng.mesh.size),
        mesh=eng.mesh,
        in_specs=(pspec, P(), P(eng.axis_name)),
    )


def _dp_compressed_train_step(mode: str) -> ProgramSpec:
    """The ISSUE 12 trio: the same wide-MLP DataParallel train step at
    wire mode fp32 (``compress="none"``), bf16, and int8 — divergence
    guard armed on all three so every golden pins the guard's exact-fp32
    ``pmin`` next to the compressed gradient payload. The bf16/int8
    goldens' bytes-on-wire sit ≥2× / ≥3.5× below the fp32 golden
    (``contract.compression_ratio`` enforces the ratio live)."""
    import optax
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn import parallel

    compress = "none" if mode == "fp32" else mode
    dp = parallel.DataParallel(
        _compress_mlp(), optax.sgd(0.1, momentum=0.9), _mse,
        compress=compress, divergence_guard="skip_step",
        # monitors OFF: this trio exists to pin the bytes-on-wire ratio
        # SHARPLY — every byte either gradient/loss payload or the guard
        # pmin. The numerics monitor psum (ISSUE 13) adds equal exact-
        # fp32 bytes to both sides, diluting the ratio below its floor;
        # the monitors-cost-one-psum claim is pinned by the OTHER golden
        # programs (train_step/zero_guard/scan/gan all gained exactly +1
        # psum at the ISSUE 13 re-pin) and by tests/test_numerics.py's
        # live one-psum delta gate.
        monitors=False,
    )
    return ProgramSpec(
        name=f"dataparallel.compressed_{mode}.train_step",
        fn=dp._train_step,
        example_args=(dp._param_store, dp.rest, dp.opt_state,
                      _batch_struct(_GLOBAL_BATCH)),
        arg_labels=("params", "rest", "opt_state", "batch"),
        # the BN-free fixture's `rest` is an EMPTY tree — the trainer
        # still donates the argnum, but a zero-leaf arg has nothing to
        # alias, so declaring it would trip donation_lost vacuously
        declared_donated=("params", "opt_state"),
        world=dp.world,
        mesh=dp.mesh,
        in_specs=(dp._pspec, dp._rest_spec, dp._opt_spec,
                  P(dp.axis_name)),
    )


def _autopilot_train_step(mode: str) -> ProgramSpec:
    """ISSUE 17: the autopilot's selectable compress-mode trio, pinned
    exactly as the controller runs them — ONE trainer constructed at
    the lossiest rung with error feedback on, then ``set_compress``ed
    to the target rung. The EF residual therefore rides opt_state in
    all three programs (fixed pytree structure across actuations —
    checkpoints, donation aliases, and scan carries survive a
    mid-training mode switch), including the exact fp32 wire where
    ``ef_compressed_pmean(mode="none")`` passes it through untouched.
    Distinct from the ISSUE 12 trio above, which pins each mode at its
    *construction-time* default EF setting."""
    import optax
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn import parallel

    dp = parallel.DataParallel(
        _compress_mlp(), optax.sgd(0.1, momentum=0.9), _mse,
        compress="int8", error_feedback=True,
        divergence_guard="skip_step", monitors=False,
    )
    dp.set_compress("none" if mode == "fp32" else mode)
    return ProgramSpec(
        name=f"autopilot.compressed_{mode}.train_step",
        fn=dp._train_step,
        example_args=(dp._param_store, dp.rest, dp.opt_state,
                      _batch_struct(_GLOBAL_BATCH)),
        arg_labels=("params", "rest", "opt_state", "batch"),
        declared_donated=("params", "opt_state"),
        world=dp.world,
        mesh=dp.mesh,
        in_specs=(dp._pspec, dp._rest_spec, dp._opt_spec,
                  P(dp.axis_name)),
    )


def _syncbn_compressed_stats() -> ProgramSpec:
    """The compressed SyncBN moment reduction in isolation: (sum, sumsq)
    ride the bf16 wire, the count census stays an exact fp32 psum — the
    'stats compressed independently, count never lossy' contract as a
    pinned program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn.compat import shard_map
    from tpu_syncbn.parallel import collectives
    from tpu_syncbn.runtime.distributed import DATA_AXIS

    mesh = _axis_mesh(DATA_AXIS)
    world = int(mesh.shape[DATA_AXIS])

    def body(s, sq, c):
        mean, var, count = collectives.reduce_moments(
            s[0], sq[0], c[0], DATA_AXIS, mode="bf16"
        )
        return jnp.stack([mean, var])[None], count[None]

    in_specs = (P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS))
    fn = jax.jit(shard_map(
        body, mesh=mesh, in_specs=in_specs,
        out_specs=(P(DATA_AXIS), P(DATA_AXIS)),
    ))
    sds = jax.ShapeDtypeStruct
    args = (
        sds((world, _FEATURES), jnp.float32),
        sds((world, _FEATURES), jnp.float32),
        sds((world,), jnp.float32),
    )
    return ProgramSpec(
        name="syncbn.compressed_stats", fn=fn, example_args=args,
        arg_labels=("sum", "sumsq", "count"),
        world=world, mesh=mesh, in_specs=in_specs,
    )


def _gan_train_step() -> ProgramSpec:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn import parallel

    g, d = _tiny_gan()
    gan = parallel.GANTrainer(g, d, optax.adam(1e-4), optax.adam(1e-4))
    real = _batch_struct(_GLOBAL_BATCH)
    z = jax.ShapeDtypeStruct((_GLOBAL_BATCH, _LATENT), jnp.float32)
    return ProgramSpec(
        name="gan.train_step",
        fn=gan._step,
        example_args=(gan.g_params, gan.g_rest, gan.d_params, gan.d_rest,
                      gan.g_opt_state, gan.d_opt_state, real, z, z),
        arg_labels=("g_params", "g_rest", "d_params", "d_rest",
                    "g_opt_state", "d_opt_state", "real", "z_d", "z_g"),
        declared_donated=("g_params", "g_rest", "d_params", "d_rest",
                          "g_opt_state", "d_opt_state"),
        world=int(gan.mesh.shape[gan.axis_name]),
        mesh=gan.mesh,
        in_specs=(P(), P(), P(), P(), P(), P(),
                  P(gan.axis_name), P(gan.axis_name), P(gan.axis_name)),
    )


def _serve_eval_bucket() -> ProgramSpec:
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn.serve.engine import InferenceEngine

    eng = InferenceEngine(_tiny_model(), buckets=(8,))
    bucket = eng.buckets[0]
    example = np.zeros((bucket, _FEATURES), np.float32)
    treedef, leafspecs = eng._struct_key(example)
    fn = jax.jit(eng._sharded_fwd())
    return ProgramSpec(
        name="serve.eval_bucket8",
        fn=fn,
        example_args=(eng._params, eng._rest,
                      eng._bucket_struct(bucket, treedef, leafspecs)),
        arg_labels=("params", "rest", "batch"),
        declared_donated=(),
        world=eng.world,
        mesh=eng.mesh,
        in_specs=(P(), P(), P(eng.axis_name)),
    )


def _serve_redistribute() -> ProgramSpec:
    """The publication hot path (parallel.redistribute): ZeRO flat
    1/world shards → full replicated parameter pytree, entirely on the
    mesh. The pinned contract is the whole point of the path: one
    tiled ``all_gather`` per dtype group and NO replicated-input blowup
    — ``max_replicated_bytes`` stays the *output* tree, not a host
    gather smuggled back in as a giant constant."""
    import jax
    from flax import nnx
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn.parallel.layout import SpecLayout
    from tpu_syncbn.parallel.redistribute import build_redistribute
    from tpu_syncbn.parallel.zero import FlatLayout
    from tpu_syncbn.runtime.distributed import DATA_AXIS

    speclay = SpecLayout.zero()
    mesh = speclay.mesh
    world = int(mesh.shape[DATA_AXIS])
    model = _tiny_model()
    params = nnx.state(model, nnx.Param)
    layout = FlatLayout(params, world)
    store = jax.device_put(
        layout.flatten(params),
        speclay.sharding(P(DATA_AXIS)),
    )
    return ProgramSpec(
        name="serve.redistribute",
        fn=build_redistribute(layout, mesh),
        example_args=(store,),
        arg_labels=("store",),
        declared_donated=(),
        world=world,
        mesh=mesh,
        in_specs=(P(DATA_AXIS),),
    )


def _tensor_tp_mlp() -> ProgramSpec:
    """The Megatron MLP (tensor.py): column → gelu → row, ONE psum."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn.compat import shard_map
    from tpu_syncbn.mesh_axes import MODEL_AXIS
    from tpu_syncbn.parallel import tensor

    mesh = _axis_mesh(MODEL_AXIS)
    world = int(mesh.shape[MODEL_AXIS])
    d, h = _FEATURES, 2 * world  # H divides by world
    in_specs = (P(), P(None, MODEL_AXIS), P(MODEL_AXIS),
                P(MODEL_AXIS, None), P())
    fn = jax.jit(shard_map(
        tensor.tp_mlp, mesh=mesh, in_specs=in_specs, out_specs=P(),
    ))
    sds = jax.ShapeDtypeStruct
    args = (
        sds((_GLOBAL_BATCH, d), jnp.float32),   # x replicated
        sds((d, h), jnp.float32),               # w1 sharded on H
        sds((h,), jnp.float32),                 # b1 sharded on H
        sds((h, d), jnp.float32),               # w2 sharded on H (input)
        sds((d,), jnp.float32),                 # b2 replicated
    )
    return ProgramSpec(
        name="tensor.tp_mlp", fn=fn, example_args=args,
        arg_labels=("x", "w1", "b1", "w2", "b2"),
        world=world, mesh=mesh, in_specs=in_specs,
    )


def _pipeline_gpipe() -> ProgramSpec:
    """The GPipe schedule (pipeline.py): M microbatches through
    world stages — one ppermute hand-off per tick (scan body) plus the
    last-stage psum mask."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn.mesh_axes import PIPE_AXIS
    from tpu_syncbn.parallel import pipeline

    mesh = _axis_mesh(PIPE_AXIS)
    world = int(mesh.shape[PIPE_AXIS])
    d, m, mb = _FEATURES, 4, 2

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"] + params["b"])

    fn = jax.jit(pipeline.pipeline_parallel(stage_fn, mesh))
    sds = jax.ShapeDtypeStruct
    args = (
        {"w": sds((world, d, d), jnp.float32),
         "b": sds((world, d), jnp.float32)},    # stacked stage params
        sds((m, mb, d), jnp.float32),           # microbatches
    )
    return ProgramSpec(
        name="pipeline.gpipe", fn=fn, example_args=args,
        arg_labels=("stage_params", "microbatches"),
        world=world, mesh=mesh,
        in_specs=(P(PIPE_AXIS), P()),
    )


def _pipeline_train(schedule: str) -> ProgramSpec:
    """The fused pipeline-training step (ISSUE 15): forward/backward
    microbatch rings + grad accumulation + one optimizer update as ONE
    scanned program on the 2-D (data x pipe) mesh. The 1f1b variant
    arms the divergence guard, so its contract additionally pins the
    guard's exact-fp32 ``pmin`` riding next to the rings."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn.mesh_axes import DATA_AXIS, PIPE_AXIS
    from tpu_syncbn.parallel import pipeline

    n, m, mb = 4, 4, 2  # stages, microbatches, per-replica microbatch
    mesh = pipeline.pipeline_mesh(n)
    d = _FEATURES
    data_world = int(mesh.shape[DATA_AXIS])

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"] + params["b"])

    def loss_fn(y, t):
        return ((y - t) ** 2).mean()

    rng = np.random.default_rng(0)
    stacked = {
        "w": jnp.asarray(rng.standard_normal((n, d, d)).astype(np.float32)),
        "b": jnp.asarray(rng.standard_normal((n, d)).astype(np.float32)),
    }
    tr = pipeline.PipelineTrainer(
        stage_fn, loss_fn, stacked, optax.sgd(0.1, momentum=0.9),
        num_microbatches=m, schedule=schedule, mesh=mesh,
        divergence_guard="skip_step" if schedule == "1f1b" else None,
    )
    fn = tr._build_train_steps(1, stacked=False)
    sds = jax.ShapeDtypeStruct
    batch = (
        sds((m, mb * data_world, d), jnp.float32),
        sds((m, mb * data_world, d), jnp.float32),
    )
    return ProgramSpec(
        name=f"pipeline.train_{schedule}",
        fn=fn,
        example_args=(tr._param_store, tr.opt_state, batch),
        arg_labels=("params", "opt_state", "batch"),
        declared_donated=("params", "opt_state"),
        world=int(mesh.size),
        mesh=mesh,
        in_specs=(tr._pspec, tr._opt_spec, P(None, DATA_AXIS)),
    )


def _expert_switch_moe() -> ProgramSpec:
    """Switch MoE (expert.py): two all_to_alls move capacity slots to
    their expert's device and back; the aux loss is pmean'd."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn.compat import shard_map
    from tpu_syncbn.mesh_axes import EXPERT_AXIS
    from tpu_syncbn.parallel import expert

    mesh = _axis_mesh(EXPERT_AXIS)
    world = int(mesh.shape[EXPERT_AXIS])
    d, h = _FEATURES, 4
    e = world          # one expert per device
    t_global = 8 * world
    in_specs = (P(EXPERT_AXIS), P(), P(EXPERT_AXIS), P(EXPERT_AXIS))
    fn = jax.jit(shard_map(
        expert.expert_parallel_moe, mesh=mesh,
        in_specs=in_specs, out_specs=(P(EXPERT_AXIS), P()),
    ))
    sds = jax.ShapeDtypeStruct
    args = (
        sds((t_global, d), jnp.float32),        # tokens sharded
        sds((d, e), jnp.float32),               # router replicated
        sds((e, d, h), jnp.float32),            # w_in sharded on E
        sds((e, h, d), jnp.float32),            # w_out sharded on E
    )
    return ProgramSpec(
        name="expert.switch_moe", fn=fn, example_args=args,
        arg_labels=("x", "router_w", "w_in", "w_out"),
        world=world, mesh=mesh, in_specs=in_specs,
    )


def _sequence_ring_attention() -> ProgramSpec:
    """Ring attention (sequence.py): the KV pair rotates with one
    ppermute in the scan body; sequence stays sharded end to end."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn.compat import shard_map
    from tpu_syncbn.mesh_axes import SEQ_AXIS
    from tpu_syncbn.parallel import sequence

    mesh = _axis_mesh(SEQ_AXIS)
    world = int(mesh.shape[SEQ_AXIS])
    b, l, h, dh = 2, 4 * world, 2, 4
    spec = P(None, SEQ_AXIS, None, None)
    fn = jax.jit(shard_map(
        sequence.ring_attention, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
    ))
    sds = jax.ShapeDtypeStruct
    qkv = sds((b, l, h, dh), jnp.float32)
    return ProgramSpec(
        name="sequence.ring_attention", fn=fn,
        example_args=(qkv, qkv, qkv),
        arg_labels=("q", "k", "v"),
        world=world, mesh=mesh, in_specs=(spec, spec, spec),
    )


PROGRAM_BUILDERS: dict[str, Callable[[], ProgramSpec]] = {
    "dataparallel.train_step": _dp_train_step,
    "dataparallel.zero_guard.train_step": _dp_zero_guard_train_step,
    "dataparallel.scan_k1.train_steps": lambda: _dp_scan(1),
    "dataparallel.scan_k4.train_steps": lambda: _dp_scan(4),
    "dataparallel.compressed_fp32.train_step":
        lambda: _dp_compressed_train_step("fp32"),
    "dataparallel.compressed_bf16.train_step":
        lambda: _dp_compressed_train_step("bf16"),
    "dataparallel.compressed_int8.train_step":
        lambda: _dp_compressed_train_step("int8"),
    "autopilot.compressed_fp32.train_step":
        lambda: _autopilot_train_step("fp32"),
    "autopilot.compressed_bf16.train_step":
        lambda: _autopilot_train_step("bf16"),
    "autopilot.compressed_int8.train_step":
        lambda: _autopilot_train_step("int8"),
    "layout.dp.train_step": lambda: _layout_train_step("dp"),
    "layout.dp_fsdp.train_step": lambda: _layout_train_step("dp_fsdp"),
    "layout.dp_fsdp_int8.train_step":
        lambda: _layout_train_step("dp_fsdp_int8"),
    "layout.serve.eval_fsdp": _layout_serve_eval,
    "syncbn.compressed_stats": _syncbn_compressed_stats,
    "gan.train_step": _gan_train_step,
    "serve.eval_bucket8": _serve_eval_bucket,
    "serve.redistribute": _serve_redistribute,
    "tensor.tp_mlp": _tensor_tp_mlp,
    "pipeline.gpipe": _pipeline_gpipe,
    "pipeline.train_gpipe": lambda: _pipeline_train("gpipe"),
    "pipeline.train_1f1b": lambda: _pipeline_train("1f1b"),
    "expert.switch_moe": _expert_switch_moe,
    "sequence.ring_attention": _sequence_ring_attention,
}


def build_contracts(
    names: Sequence[str] | None = None,
    *,
    memory: bool = False,
) -> dict[str, ProgramContract]:
    """Trace the registered programs and return their live contracts
    (layer-1 fields + the layer-3 sharding block). ``memory=True``
    additionally compiles each program once so the sharding block
    carries the XLA ``memory_analysis`` cross-check — the ``--shardings``
    CLI mode. Extraction is memoized per (fingerprint, layout, world)
    through :mod:`tpu_syncbn.audit.contract_cache`, so a CLI run in a
    process that already planned (or audited) pays zero re-traces."""
    from tpu_syncbn.audit import contract_cache

    picked = list(PROGRAM_BUILDERS) if names is None else list(names)
    out: dict[str, ProgramContract] = {}
    for name in picked:
        spec = PROGRAM_BUILDERS[name]()
        out[name] = contract_cache.cached_contract(
            spec.fn, spec.example_args,
            name=spec.name, world=spec.world,
            arg_labels=spec.arg_labels,
            declared_donated=spec.declared_donated,
            mesh=spec.mesh, in_specs=spec.in_specs,
            memory=memory,
        )
    return out


# ---------------------------------------------------------------------------
# invariants + golden comparison


def check_invariants(
    contracts: dict[str, ProgramContract]
) -> list[Violation]:
    """Cross-program rules that hold regardless of what the goldens pin
    — the claims the subsystem exists to machine-check."""
    out: list[Violation] = []

    def v(rule: str, msg: str) -> None:
        out.append(Violation(rule=rule, message=msg, path="<jaxpr>", line=0))

    serve = contracts.get("serve.eval_bucket8")
    if serve is not None:
        if serve.total_collectives:
            v("contract.serve_collectives",
              "serve eval program must be collective-free, found "
              f"{serve.collectives} — eval BN must normalize with running "
              "stats (PR 5 claim)")
        if sum(serve.donated_aliased.values()):
            v("contract.serve_donation",
              "serve eval program must not donate any input "
              f"(batcher/staging may still own the buffers), found "
              f"{serve.donated_aliased}")

    rd = contracts.get("serve.redistribute")
    if rd is not None:
        if not rd.collectives.get("all_gather", 0):
            v("contract.redistribute_gather",
              "serve.redistribute must move shards with all_gather "
              f"(the on-mesh layout change), found {rd.collectives} — "
              "a host gather smuggled back in leaves no collectives")
        extra = {k: n for k, n in rd.collectives.items()
                 if k != "all_gather"}
        if extra:
            v("contract.redistribute_gather",
              "serve.redistribute is a pure layout change: all_gather "
              f"only, found extra collectives {extra}")

    k1 = contracts.get("dataparallel.scan_k1.train_steps")
    k4 = contracts.get("dataparallel.scan_k4.train_steps")
    if k1 is not None and k4 is not None and (
        k1.collectives != k4.collectives
        or k1.collective_bytes != k4.collective_bytes
    ):
        v("contract.scan_variance",
          "fused scan program's collectives must be K-invariant "
          f"(per logical step): K=1 {k1.collectives} vs K=4 "
          f"{k4.collectives}")

    tp = contracts.get("tensor.tp_mlp")
    if tp is not None and tp.collectives != {"psum": 1}:
        v("contract.tp_one_psum",
          "the Megatron column->row pairing costs exactly ONE psum "
          f"(tensor.py's whole point), found {tp.collectives}")

    gp = contracts.get("pipeline.gpipe")
    if gp is not None:
        if gp.collectives.get("psum", 0):
            v("contract.pipeline_ring",
              "pipeline.gpipe must be psum-free: the one-hot output mask "
              "was replaced by a P(pipe)-leading out-spec (ISSUE 15) — "
              f"found {gp.collectives} (the replication wire cost came "
              "back)")
        if not gp.collectives.get("ppermute", 0):
            v("contract.pipeline_ring",
              "pipeline.gpipe lost its ppermute ring — activations are "
              f"moving some other way: {gp.collectives}")
    for sched in ("gpipe", "1f1b"):
        c = contracts.get(f"pipeline.train_{sched}")
        if c is None:
            continue
        if c.collectives.get("ppermute", 0) != 2:
            v("contract.pipeline_ring",
              f"pipeline.train_{sched} must move activations/cotangents "
              "through exactly TWO ppermutes per tick (forward ring + "
              f"backward ring), found {c.collectives}")
        gathered = {k: n for k, n in c.collectives.items()
                    if k in ("all_gather", "all_to_all")}
        if gathered:
            v("contract.pipeline_ring",
              f"pipeline.train_{sched} gathers instead of ringing "
              f"({gathered}) — a stage materialized another stage's "
              "state")

    # ISSUE 20: the composed DP×FSDP layout's memory claim. Sharding
    # params + adam moments 1/fsdp-world has to show up as per-device
    # peak memory — if the composed program's peak creeps back toward
    # the DP-only program's (a gather that outlives its use, opt state
    # replicated by accident), the layout stopped paying for itself.
    dp_l = contracts.get("layout.dp.train_step")
    fs_l = contracts.get("layout.dp_fsdp.train_step")
    if (dp_l is not None and fs_l is not None
            and dp_l.sharding is not None and fs_l.sharding is not None):
        dp_peak = dp_l.sharding.peak_bytes_per_device
        fs_peak = fs_l.sharding.peak_bytes_per_device
        if fs_peak > 0.6 * dp_peak:
            v("contract.fsdp_peak_memory",
              "composed DP×FSDP train step must hold per-device peak "
              f"memory ≤ 0.6× the DP-only program, found {fs_peak} vs "
              f"{dp_peak} bytes (ratio {fs_peak / max(1, dp_peak):.2f})"
              " — flat param/opt shards are no longer paying for the "
              "composition")

    moe = contracts.get("expert.switch_moe")
    if moe is not None and moe.collectives.get("all_to_all", 0) != 2:
        v("contract.moe_two_all_to_all",
          "expert-parallel MoE relocates compute with exactly TWO "
          f"all_to_alls (dispatch + return), found {moe.collectives}")

    # the same floors bind the autopilot's actuation trio (ISSUE 17):
    # every rung the controller can select is ratio- and guard-checked
    for fam in ("dataparallel", "autopilot"):
        fp32c = contracts.get(f"{fam}.compressed_fp32.train_step")
        if fp32c is None:
            continue
        lossy_bytes = lossy_collective_bytes
        for mode, factor in (("bf16", 2.0), ("int8", 3.5)):
            c = contracts.get(f"{fam}.compressed_{mode}.train_step")
            if c is None:
                continue
            ratio = lossy_bytes(fp32c) / max(1, lossy_bytes(c))
            if ratio < factor:
                v("contract.compression_ratio",
                  f"{fam} compressed_{mode} train step puts "
                  f"{lossy_bytes(c)} lossy-eligible bytes on the wire vs "
                  f"{lossy_bytes(fp32c)} fp32 — ratio {ratio:.2f} < the "
                  f"ISSUE 12 floor {factor}× (quantization stopped "
                  "reaching the wire, or fp32 payload leaked in)")
            if (c.collectives.get("pmin", 0) !=
                    fp32c.collectives.get("pmin", 0)
                    or c.collective_bytes.get("pmin", 0) !=
                    fp32c.collective_bytes.get("pmin", 0)):
                v("contract.guard_stays_fp32",
                  f"{fam} compressed_{mode} train step's divergence-guard "
                  f"pmin ({c.collectives.get('pmin', 0)} call(s), "
                  f"{c.collective_bytes.get('pmin', 0)} B) differs from "
                  f"the fp32 program's — the finiteness consensus must "
                  "never ride a lossy wire (lossy_default_mode's "
                  "runtime counterpart)")

    stats = contracts.get("syncbn.compressed_stats")
    if stats is not None and not stats.collectives.get("pmax"):
        # the compressed stat reduction carries its quantize/cast wiring
        # plus the exact count psum; bf16 mode has no pmax, so assert the
        # psum split instead: at least 2 psum calls (payload + count)
        if stats.collectives.get("psum", 0) < 2:
            v("contract.stats_count_exact",
              "syncbn.compressed_stats must reduce the count census "
              "through its own exact psum next to the compressed "
              f"payload, found {stats.collectives}")

    for name, c in contracts.items():
        for label in c.donated_declared:
            if not c.donated_aliased.get(label):
                v("contract.donation_lost",
                  f"{name}: argument {label!r} is declared donated but "
                  "the lowering aliased none of its leaves — jax dropped "
                  "the donation silently (dtype/layout mismatch?)")
        if c.host_callbacks:
            v("contract.host_callback",
              f"{name}: host callback(s) {c.host_callbacks} inside a hot "
              "program — every execution pays a device→host round trip")
    return out


def check_sharding(
    contracts: dict[str, ProgramContract],
    *,
    mem_budget: int | None = None,
) -> list[Violation]:
    """Layer-3 detectors, independent of the goldens: accidental
    replication above the threshold, implicit resharding anywhere, and
    (when a budget is given) the per-device peak-memory contract. The
    golden comparison additionally pins the numeric fields, so drift
    *below* these detectors' bars is still caught."""
    out: list[Violation] = []

    def v(rule: str, msg: str) -> None:
        out.append(Violation(rule=rule, message=msg, path="<jaxpr>", line=0))

    for name, c in contracts.items():
        s = c.sharding
        if s is None:
            continue
        for detail in s.replication_detail:
            v("sharding.replication",
              f"{name}: intermediate materialized fully replicated on "
              f"every device above the {s.replication_threshold}-byte "
              f"threshold — {detail}. Shard it, or gather closer to its "
              "use site")
        for detail in s.reshard_detail:
            v("sharding.implicit_reshard",
              f"{name}: layout change not explained by a declared "
              f"collective — {detail}")
        if mem_budget is not None:
            peak = max(s.peak_bytes_per_device, s.xla_peak_bytes or 0)
            if peak > mem_budget:
                v("sharding.mem_budget",
                  f"{name}: per-device peak estimate {peak} B exceeds "
                  f"the --mem-budget contract of {mem_budget} B "
                  f"(flow estimate {s.peak_bytes_per_device} B, XLA "
                  f"{s.xla_peak_bytes} B)")
    return out


def check_goldens(
    contracts: dict[str, ProgramContract],
    golden_dir: str,
) -> tuple[list[Violation], list[str]]:
    """Compare live contracts to the pinned goldens. Returns
    ``(violations, unpinned)`` — programs with no golden file are
    reported separately so the CLI can treat them as warnings
    (default) or failures (``--strict``)."""
    violations: list[Violation] = []
    unpinned: list[str] = []
    for name, contract in contracts.items():
        path = golden_path(golden_dir, name)
        if not os.path.exists(path):
            unpinned.append(name)
            continue
        golden = load_contract(path)
        for diff in compare_contracts(contract, golden):
            violations.append(Violation(
                rule="contract.golden_mismatch", message=diff,
                path=os.path.relpath(path), line=0,
            ))
    return violations, unpinned


def golden_diffs(
    contracts: dict[str, ProgramContract], golden_dir: str
) -> dict[str, list[str]]:
    """Per-contract field-level old→new summary against the pinned
    goldens — what ``--write-goldens`` prints so a re-pin is reviewed,
    not rubber-stamped. New (unpinned) programs map to a single
    ``<new golden>`` marker."""
    out: dict[str, list[str]] = {}
    for name, contract in contracts.items():
        path = golden_path(golden_dir, name)
        if not os.path.exists(path):
            out[name] = ["<new golden — no previous pin>"]
            continue
        golden = load_contract(path)
        diffs = compare_contracts(contract, golden)
        # compare_contracts deliberately skips xla_peak_bytes when one
        # side did not compile (strict runs without --shardings must
        # stay quiet) — but a RE-PIN that would erase a previously
        # pinned cross-check is a reviewable change, not a silent one
        if golden.sharding is not None \
                and golden.sharding.xla_peak_bytes is not None \
                and contract.sharding is not None \
                and contract.sharding.xla_peak_bytes is None:
            diffs.append(
                f"{name}: sharding.xla_peak_bytes = None, golden pins "
                f"{golden.sharding.xla_peak_bytes} — re-pinning without "
                "--shardings would erase the memory cross-check (add "
                "--shardings, or --force to drop it deliberately)"
            )
        if diffs:
            out[name] = diffs
    return out


def write_goldens(
    contracts: dict[str, ProgramContract], golden_dir: str
) -> list[str]:
    """Pin (or re-pin) every contract as a golden JSON file. Returns the
    written paths. Only do this after an *intentional* program change —
    the diff review IS the contract review (docs/STATIC_ANALYSIS.md);
    the CLI wraps this with :func:`golden_diffs` + ``--force``."""
    os.makedirs(golden_dir, exist_ok=True)
    written = []
    for name, contract in contracts.items():
        path = golden_path(golden_dir, name)
        save_contract(contract, path)
        written.append(path)
    return written
