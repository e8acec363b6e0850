"""Contract-driven parallelism planner: predict step time without
compiling, pick the fastest layout, feed the autopilot planned
candidates.

Every parallelism decision this stack exposes — DP vs DP+ZeRO, the
composed DP×FSDP (``SpecLayout.fsdp``) and DP×TP factorizations,
tensor degree, pipeline stage count N / schedule / microbatch count M,
scan chunk K, wire compression — was until now chosen by a human, even
though the audit layer already computes everything a first-order cost
model needs *without compiling anything*: per-device collective bytes
and peak memory from :func:`tpu_syncbn.audit.contracts.extract_contract`,
executed flops from the execution-weighted jaxpr walk
(:func:`~tpu_syncbn.audit.contracts.weighted_cost_summary`), and exact
pipeline bubble arithmetic from the static tick tables
(:mod:`tpu_syncbn.parallel.pipeline_schedule`). This module turns
layout selection into the search problem ROADMAP item 4 and the
inter/intra-op planning line of arXiv:2204.10562 say it is:

1. **enumerate** candidate compositions over the existing strategy
   surface (mesh factorizations over :mod:`tpu_syncbn.mesh_axes` axes);
2. **build** each candidate exactly the way the trainers build it
   (same step factories, same shard_map specs, same donation — the
   audit registry discipline), and **trace** it abstractly, memoized
   through :mod:`tpu_syncbn.audit.contract_cache`;
3. **cost** each candidate statically — see :func:`assemble_cost` for
   how predicted step time decomposes into compute / collective /
   bubble / host shares against the attribution model's calibrated
   ``flop_rate`` / ``wire_rate``;
4. **reject** memory-infeasible plans against the per-device
   peak-memory contract, with a named reason per rejection;
5. **rank** the survivors by the objective.

The model the full surface plans over is a :class:`LayerStack` — a
layer-sequence description (N homogeneous residual-MLP blocks) from
which every strategy is *constructible*: DP/ZeRO train the whole
stack, pipeline candidates group blocks into stages, tensor candidates
shard each block's hidden dimension. An opaque ``nnx.Module`` can be
planned too, but only over the strategies that don't need to split it
(DP / DP+ZeRO / K / compression); the non-constructible kinds are
reported as structural rejections, never silently dropped.

Consumption paths:

* ``python -m tpu_syncbn.audit plan`` — ranked table with the
  per-candidate predicted-time breakdown (docs/PLANNER.md);
* :class:`tpu_syncbn.runtime.autopilot.Autopilot` — planner-backed
  candidate-set mode: the controller walks ``RankedPlans.top(k)``
  when the measured step time violates the current plan's prediction
  (the ``plan_change`` incident trigger).

Telemetry (``planner.*`` — docs/OBSERVABILITY.md "Planner"):
``planner.candidates_total`` / ``planner.candidates_feasible`` /
``planner.candidates_rejected`` gauges, ``planner.best_predicted_step_s``,
the ``planner.plan_s`` histogram, and the contract-cache
``planner.contract_cache_hits`` / ``_misses`` counters.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Sequence

from tpu_syncbn.mesh_axes import (
    DATA_AXIS,
    FSDP_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
)
from tpu_syncbn.parallel import pipeline_schedule

#: The compression surface the planner enumerates (CLI spelling:
#: ``fp32`` is the trainer's ``compress="none"`` exact wire).
COMPRESS_SURFACE = ("fp32", "bf16", "int8")

#: Ranking objectives: predicted wall-clock per optimizer step,
#: bytes-on-wire (interconnect-constrained pods), or per-device peak
#: memory (fit-first sizing).
OBJECTIVES = ("step_time", "wire_bytes", "peak_memory")

#: Host-side dispatch overhead charged per program launch — amortized
#: by the scan chunk K (one fused K-step program is one dispatch). The
#: default is a CPU host's order of magnitude; calibrate via
#: :class:`Rates` from a measured ``host_gap_s``.
DEFAULT_DISPATCH_S = 200e-6


@dataclasses.dataclass(frozen=True)
class Rates:
    """The calibrated rate model predicted time is assembled against —
    the same ``flop_rate`` / ``wire_rate`` vocabulary the incident
    attribution report uses (``obs.incident.attribution``), plus the
    per-dispatch host overhead the K knob amortizes."""

    flop_rate: float
    wire_rate: float
    dispatch_s: float = DEFAULT_DISPATCH_S


def default_rates() -> Rates:
    """The attribution model's default device rates
    (:data:`tpu_syncbn.obs.incident.DEFAULT_FLOP_RATE` /
    :data:`~tpu_syncbn.obs.incident.DEFAULT_WIRE_RATE`)."""
    from tpu_syncbn.obs import incident

    return Rates(
        flop_rate=float(incident.DEFAULT_FLOP_RATE),
        wire_rate=float(incident.DEFAULT_WIRE_RATE),
    )


@dataclasses.dataclass(frozen=True)
class LayerStack:
    """A planner-native model description: ``n_layers`` homogeneous
    residual MLP blocks ``x + tanh(x @ w1 + b1) @ w2 + b2`` of width
    ``d_model`` → ``d_hidden`` → ``d_model``. Small enough to trace in
    milliseconds, expressive enough that every strategy kind is
    constructible from it (DP trains the stack, pipeline groups blocks
    into stages, tensor shards ``d_hidden``)."""

    n_layers: int = 4
    d_model: int = 16
    d_hidden: int = 32
    name: str = "stack"

    def __post_init__(self):
        if self.n_layers < 1 or self.d_model < 1 or self.d_hidden < 1:
            raise ValueError(f"degenerate LayerStack {self!r}")

    @property
    def params_per_layer(self) -> int:
        d, h = self.d_model, self.d_hidden
        return 2 * d * h + h + d


def bench_stack() -> LayerStack:
    """The default planner description: a stack proxy sized to
    a ResNet's block structure (deep, hidden-dim-heavy) but
    traceable in milliseconds — what ``python -m tpu_syncbn.audit
    plan`` ranks by default (docs/PLANNER.md "The bench stack")."""
    return LayerStack(n_layers=8, d_model=64, d_hidden=256,
                      name="bench")


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point on the strategy surface. ``mesh_axes`` is the named
    factorization of the world; ``scan_k`` is a cost-model dimension
    only (the fused-scan contract is K-invariant per logical step —
    the pinned ``contract.scan_variance`` invariant — so K variants
    share one traced program and differ only in the host share)."""

    name: str
    kind: str  # "dp" | "dp_zero" | "dp_fsdp" | "dp_tensor" | "pipeline" | "tensor"
    mesh_axes: tuple[tuple[str, int], ...]
    compress: str = "fp32"
    scan_k: int = 1
    n_stages: int | None = None
    schedule: str | None = None
    microbatches: int | None = None

    def to_json(self) -> dict:
        return {
            "name": self.name, "kind": self.kind,
            "mesh_axes": {a: s for a, s in self.mesh_axes},
            "compress": self.compress, "scan_k": self.scan_k,
            "n_stages": self.n_stages, "schedule": self.schedule,
            "microbatches": self.microbatches,
        }


@dataclasses.dataclass(frozen=True)
class CostBreakdown:
    """Predicted per-optimizer-step seconds, decomposed. The planner's
    accounting identity: ``step_time_s == compute_s + collective_s +
    bubble_s + host_s`` (see :func:`assemble_cost` for how each term is
    derived from contract figures)."""

    compute_s: float
    collective_s: float
    bubble_s: float
    host_s: float

    @property
    def step_time_s(self) -> float:
        return (self.compute_s + self.collective_s + self.bubble_s
                + self.host_s)

    def shares(self) -> dict[str, float]:
        total = self.step_time_s or 1.0
        return {
            "compute": self.compute_s / total,
            "collective": self.collective_s / total,
            "bubble": self.bubble_s / total,
            "host": self.host_s / total,
        }

    def to_json(self) -> dict:
        return {
            "compute_s": self.compute_s,
            "collective_s": self.collective_s,
            "bubble_s": self.bubble_s,
            "host_s": self.host_s,
            "step_time_s": self.step_time_s,
        }


@dataclasses.dataclass
class PlannedCandidate:
    """A costed (or rejected) candidate. Infeasible candidates carry a
    named ``reject_reason`` — ``mem_budget: ...`` for peak-memory
    rejections, ``layout: ...`` / ``model: ...`` for structurally
    non-constructible points — and ``feasible=False``."""

    candidate: Candidate
    feasible: bool
    reject_reason: str | None = None
    cost: CostBreakdown | None = None
    predicted_step_s: float | None = None
    flops_per_device: int = 0
    wire_bytes_per_device: int = 0
    peak_bytes_per_device: int | None = None
    collectives: dict = dataclasses.field(default_factory=dict)

    @property
    def name(self) -> str:
        return self.candidate.name

    def to_json(self) -> dict:
        return {
            "candidate": self.candidate.to_json(),
            "feasible": self.feasible,
            "reject_reason": self.reject_reason,
            "cost": self.cost.to_json() if self.cost else None,
            "predicted_step_s": self.predicted_step_s,
            "flops_per_device": self.flops_per_device,
            "wire_bytes_per_device": self.wire_bytes_per_device,
            "peak_bytes_per_device": self.peak_bytes_per_device,
            "collectives": dict(sorted(self.collectives.items())),
        }


@dataclasses.dataclass
class RankedPlans:
    """The planner's output: feasible candidates ranked best-first by
    the objective, rejections with named reasons, and the contract
    cache's hit/miss story for the enumeration."""

    objective: str
    world: int
    batch: int
    plans: list[PlannedCandidate]
    rejected: list[PlannedCandidate]
    cache: dict
    plan_s: float

    @property
    def best(self) -> PlannedCandidate | None:
        return self.plans[0] if self.plans else None

    def top(self, k: int) -> list[PlannedCandidate]:
        """The autopilot's planned candidate set: the ``k`` best
        feasible plans, rank order."""
        return self.plans[:k]

    def to_json(self) -> dict:
        return {
            "schema": 1,
            "objective": self.objective,
            "world": self.world,
            "batch": self.batch,
            "plans": [p.to_json() for p in self.plans],
            "rejected": [p.to_json() for p in self.rejected],
            "cache": dict(self.cache),
            "plan_s": self.plan_s,
        }

    def table(self) -> str:
        """The ``audit plan`` CLI's ranked table: predicted step time
        with per-candidate compute/collective/bubble/host shares."""
        rows = [
            f"{'rank':>4}  {'candidate':<22} {'pred_ms':>9} "
            f"{'compute%':>8} {'coll%':>6} {'bubble%':>7} {'host%':>6} "
            f"{'peak_MiB':>8}"
        ]
        for i, p in enumerate(self.plans):
            s = p.cost.shares()
            peak = (f"{p.peak_bytes_per_device / (1 << 20):8.2f}"
                    if p.peak_bytes_per_device is not None else "       ?")
            rows.append(
                f"{i + 1:>4}  {p.name:<22} "
                f"{p.predicted_step_s * 1e3:9.3f} "
                f"{s['compute'] * 100:8.1f} {s['collective'] * 100:6.1f} "
                f"{s['bubble'] * 100:7.1f} {s['host'] * 100:6.1f} {peak}"
            )
        for p in self.rejected:
            rows.append(f"   -  {p.name:<22} rejected: {p.reject_reason}")
        rows.append(
            f"objective={self.objective} world={self.world} "
            f"batch={self.batch} contract_cache="
            f"{self.cache.get('hits', 0)}h/{self.cache.get('misses', 0)}m "
            f"plan_s={self.plan_s:.3f}"
        )
        return "\n".join(rows)


# ---------------------------------------------------------------------------
# cost assembly


def assemble_cost(
    *,
    flops: int,
    wire_bytes: int,
    rates: Rates,
    scan_k: int = 1,
    bubble_frac: float = 0.0,
) -> CostBreakdown:
    """Assemble predicted per-step seconds from per-device contract
    figures (docs/PLANNER.md "The cost model"):

    * ``compute_s`` — useful matmul seconds: executed flops over
      ``flop_rate``, with the schedule's masked-waste fraction split
      out (for a pipeline program the execution-weighted walk already
      counts all ``T`` ticks of lockstep compute, of which exactly
      ``M/T`` is useful — the tick tables' own arithmetic);
    * ``bubble_s`` — the remaining ``1 − M/T`` of executed compute:
      schedule bubble, zero for non-pipeline candidates;
    * ``collective_s`` — executed bytes-on-wire over ``wire_rate``;
    * ``host_s`` — one program dispatch per fused chunk, amortized by
      the scan chunk K.

    Monotone by construction: more bytes at fixed flops is never
    predicted faster (``collective_s`` is linear in bytes and nothing
    else reads them)."""
    if not 0.0 <= bubble_frac < 1.0:
        raise ValueError(f"bubble_frac must be in [0, 1), got "
                         f"{bubble_frac}")
    compute_total = flops / rates.flop_rate
    return CostBreakdown(
        compute_s=compute_total * (1.0 - bubble_frac),
        collective_s=wire_bytes / rates.wire_rate,
        bubble_s=compute_total * bubble_frac,
        host_s=rates.dispatch_s / max(1, int(scan_k)),
    )


def kendall_tau(order_a: Sequence[str], order_b: Sequence[str]) -> float:
    """Kendall rank correlation between two orderings of the same
    items: +1.0 when every pair agrees, −1.0 when every pair is
    inverted (predicted against measured ordering)."""
    if sorted(order_a) != sorted(order_b):
        raise ValueError(
            f"orderings rank different items: {order_a} vs {order_b}"
        )
    n = len(order_a)
    if n < 2:
        return 1.0
    pos = {name: i for i, name in enumerate(order_b)}
    concordant = discordant = 0
    for i in range(n):
        for j in range(i + 1, n):
            if pos[order_a[i]] < pos[order_a[j]]:
                concordant += 1
            else:
                discordant += 1
    return (concordant - discordant) / (n * (n - 1) / 2)


# ---------------------------------------------------------------------------
# candidate builders (audit-registry discipline: build each program the
# way the trainers build it, trace abstractly)


def _sq_loss(m, b):
    return (m(b) ** 2).mean()


def _stack_module(stack: LayerStack):
    import jax.numpy as jnp
    from flax import nnx

    class _Block(nnx.Module):
        def __init__(self, d, h, rngs):
            self.up = nnx.Linear(d, h, rngs=rngs)
            self.down = nnx.Linear(h, d, rngs=rngs)

        def __call__(self, x):
            return x + self.down(jnp.tanh(self.up(x)))

    class _Stack(nnx.Module):
        def __init__(self, cfg, rngs):
            self.n_layers = cfg.n_layers
            for i in range(cfg.n_layers):
                setattr(self, f"block{i}",
                        _Block(cfg.d_model, cfg.d_hidden, rngs))

        def __call__(self, x):
            for i in range(self.n_layers):
                x = getattr(self, f"block{i}")(x)
            return x

    return _Stack(stack, nnx.Rngs(0))


def _dp_spec(model: Any, batch_shape: tuple, *, zero: bool,
             compress: str, layout: Any | None = None,
             name: str | None = None):
    import jax
    import jax.numpy as jnp
    import optax

    from tpu_syncbn import parallel
    from tpu_syncbn.audit.jaxpr_audit import ProgramSpec

    module = (_stack_module(model) if isinstance(model, LayerStack)
              else model)
    dp = parallel.DataParallel(
        module, optax.sgd(0.1, momentum=0.9), _sq_loss,
        compress=("none" if compress == "fp32" else compress),
        zero=zero, layout=layout, monitors=False,
    )
    kind = "dp_zero" if zero else "dp"
    batch = jax.ShapeDtypeStruct(batch_shape, jnp.float32)
    return ProgramSpec(
        name=name if name is not None else f"planner.{kind}.{compress}",
        fn=dp._train_step,
        example_args=(dp._param_store, dp.rest, dp.opt_state, batch),
        arg_labels=("params", "rest", "opt_state", "batch"),
        declared_donated=("params", "opt_state"),
        world=int(dp.mesh.size),
        mesh=dp.mesh,
        in_specs=(dp._pspec, dp._rest_spec, dp._opt_spec,
                  dp.layout.batch_spec),
    )


def _pipeline_spec(stack: LayerStack, batch_shape: tuple, *,
                   n_stages: int, schedule: str, microbatches: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn.audit.jaxpr_audit import ProgramSpec
    from tpu_syncbn.parallel import pipeline

    n, m = n_stages, microbatches
    per_stage = stack.n_layers // n
    d, h = stack.d_model, stack.d_hidden
    mesh = pipeline.pipeline_mesh(n)

    def stage_fn(params, x):
        for i in range(per_stage):
            x = (x + jnp.tanh(x @ params["w1"][i] + params["b1"][i])
                 @ params["w2"][i] + params["b2"][i])
        return x

    def loss_fn(y, t):
        return ((y - t) ** 2).mean()

    rng = np.random.default_rng(0)

    def init(*shape):
        return jnp.asarray(
            rng.standard_normal(shape).astype(np.float32)
        )

    stacked = {
        "w1": init(n, per_stage, d, h), "b1": init(n, per_stage, h),
        "w2": init(n, per_stage, h, d), "b2": init(n, per_stage, d),
    }
    tr = pipeline.PipelineTrainer(
        stage_fn, loss_fn, stacked, optax.sgd(0.1, momentum=0.9),
        num_microbatches=m, schedule=schedule, mesh=mesh,
    )
    fn = tr._build_train_steps(1, stacked=False)
    rows = batch_shape[0] // m
    sds = jax.ShapeDtypeStruct
    batch = (sds((m, rows, d), jnp.float32),
             sds((m, rows, d), jnp.float32))
    return ProgramSpec(
        name=f"planner.pipe.{schedule}.n{n}.m{m}",
        fn=fn,
        example_args=(tr._param_store, tr.opt_state, batch),
        arg_labels=("params", "opt_state", "batch"),
        declared_donated=("params", "opt_state"),
        world=int(mesh.size),
        mesh=mesh,
        in_specs=(tr._pspec, tr._opt_spec, P(None, DATA_AXIS)),
    )


def _tensor_spec(stack: LayerStack, batch_shape: tuple):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_syncbn.audit.jaxpr_audit import ProgramSpec
    from tpu_syncbn.compat import shard_map
    from tpu_syncbn.parallel import tensor
    from tpu_syncbn.runtime import distributed as dist

    mesh = dist.make_mesh({MODEL_AXIS: -1})
    world = int(mesh.shape[MODEL_AXIS])
    d, h, n_layers = stack.d_model, stack.d_hidden, stack.n_layers

    def fwd(x, w1, b1, w2, b2):
        for i in range(n_layers):
            x = x + tensor.tp_mlp(x, w1[i], b1[i], w2[i], b2[i])
        return x

    in_specs = (P(), P(None, None, MODEL_AXIS), P(None, MODEL_AXIS),
                P(None, MODEL_AXIS, None), P())
    sharded = shard_map(
        fwd, mesh=mesh, in_specs=in_specs, out_specs=P(),
    )

    def train(x, w1, b1, w2, b2):
        def loss(ws):
            return (sharded(x, *ws) ** 2).mean()

        return jax.grad(loss)((w1, b1, w2, b2))

    fn = jax.jit(train)
    sds = jax.ShapeDtypeStruct
    args = (
        sds(batch_shape, jnp.float32),
        sds((n_layers, d, h), jnp.float32),
        sds((n_layers, h), jnp.float32),
        sds((n_layers, h, d), jnp.float32),
        sds((n_layers, d), jnp.float32),
    )
    return ProgramSpec(
        name=f"planner.tp.model{world}", fn=fn, example_args=args,
        arg_labels=("x", "w1", "b1", "w2", "b2"),
        world=world, mesh=mesh, in_specs=in_specs,
    )


def _dp_tensor_spec(stack: LayerStack, batch_shape: tuple, *,
                    data: int, model_ways: int):
    """Composed DP×TP: the :meth:`SpecLayout.tensor_parallel` 2-D mesh,
    batch sharded over ``data``, each block's hidden dim sharded over
    ``model`` — the 1-D :func:`_tensor_spec` program lifted onto the
    composed layout (separate builder so the 1-D golden stays pinned)."""
    import jax
    import jax.numpy as jnp

    from tpu_syncbn.audit.jaxpr_audit import ProgramSpec
    from tpu_syncbn.compat import shard_map
    from tpu_syncbn.parallel import tensor
    from tpu_syncbn.parallel.layout import SpecLayout, P

    lay = SpecLayout.tensor_parallel(data=data, model=model_ways,
                                     rules=())
    d, h, n_layers = stack.d_model, stack.d_hidden, stack.n_layers

    def fwd(x, w1, b1, w2, b2):
        for i in range(n_layers):
            x = x + tensor.tp_mlp(x, w1[i], b1[i], w2[i], b2[i])
        return x

    in_specs = (lay.batch_spec, P(None, None, MODEL_AXIS),
                P(None, MODEL_AXIS), P(None, MODEL_AXIS, None), P())
    sharded = shard_map(
        fwd, mesh=lay.mesh, in_specs=in_specs,
        out_specs=lay.batch_spec,
    )

    def train(x, w1, b1, w2, b2):
        def loss(ws):
            return (sharded(x, *ws) ** 2).mean()

        return jax.grad(loss)((w1, b1, w2, b2))

    fn = jax.jit(train)
    sds = jax.ShapeDtypeStruct
    args = (
        sds(batch_shape, jnp.float32),
        sds((n_layers, d, h), jnp.float32),
        sds((n_layers, h), jnp.float32),
        sds((n_layers, h, d), jnp.float32),
        sds((n_layers, d), jnp.float32),
    )
    return ProgramSpec(
        name=f"planner.dp_tp.d{data}.m{model_ways}", fn=fn,
        example_args=args, arg_labels=("x", "w1", "b1", "w2", "b2"),
        world=lay.world, mesh=lay.mesh, in_specs=in_specs,
    )


# ---------------------------------------------------------------------------
# enumeration


def _reject(cand: Candidate, reason: str) -> PlannedCandidate:
    return PlannedCandidate(candidate=cand, feasible=False,
                            reject_reason=reason)


def enumerate_candidates(
    model: Any,
    *,
    world: int,
    batch: int,
    compress_modes: Sequence[str] = COMPRESS_SURFACE,
    scan_ks: Sequence[int] = (1, 8),
    stage_counts: Sequence[int] | None = None,
    schedules: Sequence[str] = ("gpipe", "1f1b"),
    microbatches: Sequence[int] | None = None,
    include: Sequence[str] | None = None,
) -> tuple[list[Candidate], list[PlannedCandidate]]:
    """Walk the strategy surface; returns ``(candidates, rejected)``
    where ``rejected`` carries the structurally non-constructible
    points with named ``layout:`` / ``model:`` reasons (divisibility,
    opaque model). ``include`` filters by kind name."""
    unknown = [m for m in compress_modes if m not in COMPRESS_SURFACE]
    if unknown:
        raise ValueError(
            f"compress modes {unknown} not in {COMPRESS_SURFACE}"
        )
    stack = model if isinstance(model, LayerStack) else None
    wanted = set(include) if include is not None else {
        "dp", "dp_zero", "dp_fsdp", "dp_tensor", "pipeline", "tensor",
    }
    out: list[Candidate] = []
    rejected: list[PlannedCandidate] = []

    dp_axes = ((DATA_AXIS, world),)
    if "dp" in wanted:
        for mode in compress_modes:
            for k in scan_ks:
                out.append(Candidate(
                    name=f"dp.{mode}.k{k}", kind="dp",
                    mesh_axes=dp_axes, compress=mode, scan_k=int(k),
                ))
    if "dp_zero" in wanted:
        for k in scan_ks:
            out.append(Candidate(
                name=f"zero.fp32.k{k}", kind="dp_zero",
                mesh_axes=dp_axes, scan_k=int(k),
            ))

    if "dp_fsdp" in wanted:
        # every (D, F) factorization of the world with a real shard
        # axis — F == world is ZeRO-over-a-2D-spelling and still a
        # distinct traced program (batch over ('data','fsdp'))
        from tpu_syncbn.parallel.layout import _INT8_MAX_WORLD

        for f in (f for f in range(2, world + 1) if world % f == 0):
            d = world // f
            for mode in compress_modes:
                for k in scan_ks:
                    cand = Candidate(
                        name=f"fsdp.{mode}.d{d}f{f}.k{k}",
                        kind="dp_fsdp",
                        mesh_axes=((DATA_AXIS, d), (FSDP_AXIS, f)),
                        compress=mode, scan_k=int(k),
                    )
                    if batch % world:
                        rejected.append(_reject(
                            cand, f"layout: batch {batch} does not "
                            f"divide over the {world}-device composed "
                            f"('data','fsdp') batch axes"))
                    elif mode == "int8" and f > _INT8_MAX_WORLD:
                        rejected.append(_reject(
                            cand, "layout: int8 accumulator budget "
                            f"needs shard world <= {_INT8_MAX_WORLD}, "
                            f"got {f}"))
                    elif mode == "int8" and d > _INT8_MAX_WORLD:
                        rejected.append(_reject(
                            cand, "layout: int8 accumulator budget "
                            f"needs reduce world <= {_INT8_MAX_WORLD}, "
                            f"got {d}"))
                    else:
                        out.append(cand)

    if "dp_tensor" in wanted:
        # composed DP×TP factorizations with both axes real (M == world
        # is the 1-D "tensor" kind below)
        for m in (m for m in range(2, world) if world % m == 0):
            d = world // m
            cand = Candidate(
                name=f"dp_tp.d{d}.m{m}", kind="dp_tensor",
                mesh_axes=((DATA_AXIS, d), (MODEL_AXIS, m)),
            )
            if stack is None:
                rejected.append(_reject(
                    cand, "model: dp×tensor candidates need a "
                    "LayerStack description (opaque module cannot be "
                    "re-sharded)"))
            elif stack.d_hidden % m:
                rejected.append(_reject(
                    cand, f"layout: hidden dim {stack.d_hidden} does "
                    f"not divide over the {m}-way model axis"))
            elif batch % d:
                rejected.append(_reject(
                    cand, f"layout: batch {batch} does not divide "
                    f"over the {d}-way data axis"))
            else:
                out.append(cand)

    if "pipeline" in wanted:
        counts = (
            tuple(stage_counts) if stage_counts is not None
            else tuple(n for n in range(2, world + 1) if world % n == 0)
        )
        for n in counts:
            ms = tuple(microbatches) if microbatches is not None \
                else (n, 2 * n)
            for sched in schedules:
                for m in ms:
                    cand = Candidate(
                        name=f"pipe.{sched}.n{n}.m{m}",
                        kind="pipeline",
                        mesh_axes=((DATA_AXIS, world // n),
                                   (PIPE_AXIS, n)),
                        scan_k=1, n_stages=n, schedule=sched,
                        microbatches=m,
                    )
                    if stack is None:
                        rejected.append(_reject(
                            cand, "model: pipeline candidates need a "
                            "LayerStack description (opaque module "
                            "cannot be split into stages)"))
                    elif world % n:
                        rejected.append(_reject(
                            cand, f"layout: {n} stages do not divide "
                            f"world {world}"))
                    elif stack.n_layers % n:
                        rejected.append(_reject(
                            cand, f"layout: {stack.n_layers} layers do "
                            f"not divide into {n} stages"))
                    elif batch % m:
                        rejected.append(_reject(
                            cand, f"layout: batch {batch} does not "
                            f"divide into {m} microbatches"))
                    elif (batch // m) % (world // n):
                        rejected.append(_reject(
                            cand, f"layout: microbatch rows "
                            f"{batch // m} do not divide over the "
                            f"{world // n}-way data axis"))
                    else:
                        out.append(cand)

    if "tensor" in wanted:
        cand = Candidate(
            name=f"tp.model{world}", kind="tensor",
            mesh_axes=((MODEL_AXIS, world),),
        )
        if stack is None:
            rejected.append(_reject(
                cand, "model: tensor candidates need a LayerStack "
                "description (opaque module cannot be re-sharded)"))
        elif stack.d_hidden % world:
            rejected.append(_reject(
                cand, f"layout: hidden dim {stack.d_hidden} does not "
                f"divide over the {world}-way model axis"))
        else:
            out.append(cand)
    return out, rejected


# ---------------------------------------------------------------------------
# the planner


def _resolve_world(mesh_devices) -> int:
    import jax

    if isinstance(mesh_devices, int):
        world = mesh_devices
    else:
        world = len(list(mesh_devices))
    ndev = len(jax.devices())
    if world != ndev:
        raise ValueError(
            f"planner needs the live mesh: asked for world={world} but "
            f"jax sees {ndev} device(s) — candidates are built with the "
            "real trainers, so force the device count first (the audit "
            "CLI's virtual 8-device mesh, or "
            "--xla_force_host_platform_device_count)"
        )
    return world


def _resolve_batch(model: Any, batch_spec) -> tuple[int, tuple]:
    shape = getattr(batch_spec, "shape", batch_spec)
    if isinstance(shape, int):
        if not isinstance(model, LayerStack):
            raise ValueError(
                "an int batch_spec only works with a LayerStack (the "
                "feature shape is unknown for an opaque module) — pass "
                "the batch shape or a ShapeDtypeStruct"
            )
        shape = (shape, model.d_model)
    shape = tuple(int(s) for s in shape)
    if not shape:
        raise ValueError("batch_spec has no leading batch dimension")
    return shape[0], shape


def plan(
    model: Any,
    batch_spec,
    mesh_devices,
    *,
    objective: str = "step_time",
    mem_budget: int | None = None,
    rates: Rates | None = None,
    compress_modes: Sequence[str] = COMPRESS_SURFACE,
    scan_ks: Sequence[int] = (1, 8),
    stage_counts: Sequence[int] | None = None,
    schedules: Sequence[str] = ("gpipe", "1f1b"),
    microbatches: Sequence[int] | None = None,
    include: Sequence[str] | None = None,
) -> RankedPlans:
    """Enumerate → trace (memoized) → cost → reject → rank. Nothing
    compiles or executes: contracts come from ``jax.make_jaxpr`` +
    ``.lower()`` text only.

    ``model`` is a :class:`LayerStack` (full surface) or an
    ``nnx.Module`` (DP/ZeRO subset); ``batch_spec`` the global batch
    (int rows, shape tuple, or ShapeDtypeStruct); ``mesh_devices`` the
    world size (int) or device list — it must match the live backend,
    because candidates are built with the real trainer entry points.
    ``mem_budget`` (bytes per device) turns on memory-feasibility
    rejection against each candidate's ``peak_bytes_per_device``
    contract."""
    from tpu_syncbn.audit import contract_cache
    from tpu_syncbn.obs import telemetry

    if objective not in OBJECTIVES:
        raise ValueError(
            f"objective must be one of {OBJECTIVES}, got {objective!r}"
        )
    t0 = time.perf_counter()
    rates = rates if rates is not None else default_rates()
    world = _resolve_world(mesh_devices)
    batch, batch_shape = _resolve_batch(model, batch_spec)
    cache_before = contract_cache.stats()
    candidates, rejected = enumerate_candidates(
        model, world=world, batch=batch,
        compress_modes=compress_modes, scan_ks=scan_ks,
        stage_counts=stage_counts, schedules=schedules,
        microbatches=microbatches, include=include,
    )
    spec_memo: dict[tuple, Any] = {}

    def spec_for(cand: Candidate):
        # scan-K variants share one traced program (K-invariant
        # contract), so the build key deliberately drops scan_k; it
        # keeps mesh_axes so composed (D, F) / (D, M) factorizations
        # of the same kind stay distinct programs
        key = (cand.kind, cand.mesh_axes, cand.compress, cand.n_stages,
               cand.schedule, cand.microbatches)
        if key not in spec_memo:
            if cand.kind in ("dp", "dp_zero"):
                spec_memo[key] = _dp_spec(
                    model, batch_shape, zero=cand.kind == "dp_zero",
                    compress=cand.compress,
                )
            elif cand.kind == "dp_fsdp":
                from tpu_syncbn.parallel.layout import SpecLayout

                axes = dict(cand.mesh_axes)
                d, f = axes[DATA_AXIS], axes[FSDP_AXIS]
                spec_memo[key] = _dp_spec(
                    model, batch_shape, zero=False,
                    compress=cand.compress,
                    layout=SpecLayout.fsdp(data=d, fsdp=f),
                    name=f"planner.fsdp.{cand.compress}.d{d}f{f}",
                )
            elif cand.kind == "dp_tensor":
                axes = dict(cand.mesh_axes)
                spec_memo[key] = _dp_tensor_spec(
                    model, batch_shape, data=axes[DATA_AXIS],
                    model_ways=axes[MODEL_AXIS],
                )
            elif cand.kind == "pipeline":
                spec_memo[key] = _pipeline_spec(
                    model, batch_shape, n_stages=cand.n_stages,
                    schedule=cand.schedule,
                    microbatches=cand.microbatches,
                )
            else:
                spec_memo[key] = _tensor_spec(model, batch_shape)
        return spec_memo[key]

    plans: list[PlannedCandidate] = []
    for cand in candidates:
        spec = spec_for(cand)
        contract = contract_cache.cached_contract(
            spec.fn, spec.example_args, name=spec.name,
            world=spec.world, arg_labels=spec.arg_labels,
            declared_donated=spec.declared_donated, mesh=spec.mesh,
            in_specs=spec.in_specs,
        )
        summary = contract_cache.cached_cost(
            spec.fn, spec.example_args, name=spec.name,
            world=spec.world, mesh=spec.mesh, in_specs=spec.in_specs,
        )
        peak = (contract.sharding.peak_bytes_per_device
                if contract.sharding is not None else None)
        if mem_budget is not None and peak is not None \
                and peak > mem_budget:
            plans_entry = _reject(
                cand, f"mem_budget: predicted per-device peak {peak} B "
                f"exceeds the {mem_budget} B contract")
            plans_entry.peak_bytes_per_device = peak
            rejected.append(plans_entry)
            continue
        bubble = 0.0
        if cand.kind == "pipeline":
            bubble = pipeline_schedule.get_schedule(
                cand.schedule, cand.microbatches, cand.n_stages
            ).predicted_bubble_frac
        cost = assemble_cost(
            flops=summary["flops"], wire_bytes=summary["bytes_total"],
            rates=rates, scan_k=cand.scan_k, bubble_frac=bubble,
        )
        plans.append(PlannedCandidate(
            candidate=cand, feasible=True, cost=cost,
            predicted_step_s=cost.step_time_s,
            flops_per_device=summary["flops"],
            wire_bytes_per_device=summary["bytes_total"],
            peak_bytes_per_device=peak,
            collectives=dict(contract.collectives),
        ))

    inf = float("inf")
    if objective == "step_time":
        keyer: Callable = lambda p: (p.predicted_step_s, p.name)  # noqa: E731
    elif objective == "wire_bytes":
        keyer = lambda p: (p.wire_bytes_per_device, p.name)  # noqa: E731
    else:
        keyer = lambda p: (  # noqa: E731
            p.peak_bytes_per_device if p.peak_bytes_per_device
            is not None else inf, p.name)
    plans.sort(key=keyer)

    cache_after = contract_cache.stats()
    cache = {
        "hits": cache_after["hits"] - cache_before["hits"],
        "misses": cache_after["misses"] - cache_before["misses"],
    }
    plan_s = time.perf_counter() - t0
    telemetry.set_gauge("planner.candidates_total",
                        len(candidates) + len(rejected))
    telemetry.set_gauge("planner.candidates_feasible", len(plans))
    telemetry.set_gauge("planner.candidates_rejected", len(rejected))
    if plans:
        telemetry.set_gauge("planner.best_predicted_step_s",
                            plans[0].predicted_step_s)
    telemetry.observe("planner.plan_s", plan_s)
    return RankedPlans(
        objective=objective, world=world, batch=batch, plans=plans,
        rejected=rejected, cache=cache, plan_s=plan_s,
    )


_ = math  # re-exported convenience for cost tooling; keeps flake quiet
