"""Program contracts: what a compiled program is *allowed* to do on the
wire and with its buffers, extracted statically from the closed jaxpr and
the StableHLO lowering — never by executing the program.

The paper's claim is that SyncBN changes exactly one thing about the
compiled step: it inserts a cross-replica reduction of the BN statistics.
A :class:`ProgramContract` makes that claim (and its siblings — "eval is
collective-free", "the whole training state is donated") machine-checked:

* **collectives** — named-axis collective primitives counted by kind
  (``psum``/``all_gather``/``reduce_scatter``/``ppermute``/…), with a
  statically-estimated bytes-on-wire figure per kind (per-shard input
  payload × itemsize, the same estimate ``parallel.collectives`` tallies
  at trace time). Loop bodies (``lax.scan``/``while``/``cond`` branches)
  are counted ONCE — program text, not execution count — which is exactly
  what makes the fused K-step contract K-invariant.
* **donation** — the *declared* donation (the ``donate_argnums`` the
  trainer asked for) versus the *effective* donation: input leaves the
  StableHLO lowering actually marked donatable (``tf.aliasing_output`` /
  ``jax.buffer_donor`` arg attributes). A donation jax silently dropped
  (dtype/layout mismatch, aliasing conflict) shows up as a declared arg
  with zero aliased leaves.
* **host callbacks** — ``pure_callback``/``io_callback``/
  ``debug_callback`` equations anywhere in the program: a host round-trip
  in a hot program is a regression, not a feature.
* **upcasts** — widening float ``convert_element_type`` equations by
  dtype pair. The BN-stat math accumulates in f32 on purpose
  (``collectives.reduce_moments``, ``obs.stepstats``); losing those
  upcasts silently would change numerics, so the count is pinned.

Contracts serialize to JSON and are pinned as goldens under
``tests/contracts/`` (see :mod:`tpu_syncbn.audit.jaxpr_audit` for the
program registry and docs/STATIC_ANALYSIS.md for the re-pin workflow).
"""

from __future__ import annotations

import dataclasses
import json
import math
import re
from typing import Any, Callable, Iterable, Sequence

#: Bump when the contract JSON shape changes incompatibly.
CONTRACT_SCHEMA = 1

#: Bump when the layer-3 sharding block's shape changes incompatibly
#: (the block is optional inside the contract JSON, so adding it did not
#: bump CONTRACT_SCHEMA).
SHARDING_SCHEMA = 1

#: Relative tolerance when comparing the XLA ``memory_analysis`` figure
#: against a golden: buffer assignment is deterministic for one backend
#: build, but the figure is a cross-check, not a number we control.
XLA_PEAK_RTOL = 0.10

#: Named-axis collective primitives, under the name a contract counts
#: them by (an unknown collective should fail the contract, not slip
#: past it).
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "ppermute", "pgather",
    "all_gather", "all_to_all", "reduce_scatter", "psum_scatter",
})

#: Under ``check_vma`` jax 0.9 types a collective by what it does to the
#: value's varying set and names the primitive after it: a psum of a
#: varying value is ``psum_invariant``. The wire operation is the same,
#: so it is counted under the classic name and the goldens do not move
#: with the checker.
_PRIM_ALIASES = {
    "psum_invariant": "psum",
    "all_gather_invariant": "all_gather",
}


def prim_name(eqn) -> str:
    """The equation's primitive name, VMA-typed collective variants
    folded onto the name :data:`COLLECTIVE_PRIMS` knows."""
    name = eqn.primitive.name
    return _PRIM_ALIASES.get(name, name)

#: Host-callback primitives: any of these in a hot program means a
#: device→host→device round trip per execution.
HOST_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
})


@dataclasses.dataclass
class ShardingContract:
    """The layer-3 sharding-flow contract of one compiled program
    (docs/STATIC_ANALYSIS.md "Layer 3"): declared entry layouts, the
    propagated output layouts, every layout change accounted to a
    declared collective, and the memory story — how big the biggest
    fully-replicated intermediate is, how many exceed the replication
    threshold, and the per-device peak estimate cross-checked against
    XLA's ``memory_analysis`` when the extractor compiled.

    ``in_specs`` maps each top-level argument label to the *distinct*
    canonical spec strings of its leaves (one entry for a uniformly
    sharded arg); ``out_specs`` is the distinct specs over all outputs.
    Detail lists are capped, human-readable, and deterministic — they
    make golden diffs reviewable."""

    name: str
    mesh_axes: dict[str, int]
    in_specs: dict[str, list[str]]
    out_specs: list[str]
    collectives_explained: int
    implicit_reshards: int
    reshard_detail: list[str]
    replicated_intermediates: int
    replication_detail: list[str]
    max_replicated_bytes: int
    peak_bytes_per_device: int
    replication_threshold: int
    xla_peak_bytes: int | None = None

    def to_json(self) -> dict:
        return {
            "schema": SHARDING_SCHEMA,
            "mesh_axes": dict(sorted(self.mesh_axes.items())),
            "in_specs": {k: list(v) for k, v in sorted(
                self.in_specs.items())},
            "out_specs": list(self.out_specs),
            "collectives_explained": self.collectives_explained,
            "implicit_reshards": self.implicit_reshards,
            "reshard_detail": list(self.reshard_detail),
            "replicated_intermediates": self.replicated_intermediates,
            "replication_detail": list(self.replication_detail),
            "max_replicated_bytes": self.max_replicated_bytes,
            "peak_bytes_per_device": self.peak_bytes_per_device,
            "replication_threshold": self.replication_threshold,
            "xla_peak_bytes": self.xla_peak_bytes,
        }

    @classmethod
    def from_json(cls, name: str, blob: dict) -> "ShardingContract":
        if blob.get("schema") != SHARDING_SCHEMA:
            raise ValueError(
                f"sharding schema {blob.get('schema')!r} != "
                f"{SHARDING_SCHEMA} — re-pin the golden "
                "(docs/STATIC_ANALYSIS.md)"
            )
        xla = blob.get("xla_peak_bytes")
        return cls(
            name=name,
            mesh_axes={k: int(v) for k, v in blob["mesh_axes"].items()},
            in_specs={k: list(v) for k, v in blob["in_specs"].items()},
            out_specs=list(blob["out_specs"]),
            collectives_explained=int(blob["collectives_explained"]),
            implicit_reshards=int(blob["implicit_reshards"]),
            reshard_detail=list(blob["reshard_detail"]),
            replicated_intermediates=int(blob["replicated_intermediates"]),
            replication_detail=list(blob["replication_detail"]),
            max_replicated_bytes=int(blob["max_replicated_bytes"]),
            peak_bytes_per_device=int(blob["peak_bytes_per_device"]),
            replication_threshold=int(blob["replication_threshold"]),
            xla_peak_bytes=int(xla) if xla is not None else None,
        )


@dataclasses.dataclass
class ProgramContract:
    """The statically-verifiable communication/memory contract of one
    compiled program. ``donated_declared`` is per top-level argument
    label; ``donated_aliased`` maps each label to how many of its leaves
    the lowering actually marked donatable. ``sharding`` carries the
    optional layer-3 flow block (:class:`ShardingContract`) when the
    extractor was given the program's mesh and entry specs."""

    name: str
    world: int
    collectives: dict[str, int]
    collective_bytes: dict[str, int]
    donated_declared: list[str]
    donated_aliased: dict[str, int]
    host_callbacks: dict[str, int]
    upcasts: dict[str, int]
    sharding: ShardingContract | None = None

    def to_json(self) -> dict:
        out = {
            "schema": CONTRACT_SCHEMA,
            "name": self.name,
            "world": self.world,
            "collectives": dict(sorted(self.collectives.items())),
            "collective_bytes": dict(sorted(self.collective_bytes.items())),
            "donated_declared": list(self.donated_declared),
            "donated_aliased": dict(sorted(self.donated_aliased.items())),
            "host_callbacks": dict(sorted(self.host_callbacks.items())),
            "upcasts": dict(sorted(self.upcasts.items())),
        }
        if self.sharding is not None:
            out["sharding"] = self.sharding.to_json()
        return out

    @classmethod
    def from_json(cls, blob: dict) -> "ProgramContract":
        if blob.get("schema") != CONTRACT_SCHEMA:
            raise ValueError(
                f"contract schema {blob.get('schema')!r} != {CONTRACT_SCHEMA}"
                " — re-pin the golden (docs/STATIC_ANALYSIS.md)"
            )
        sharding = None
        if blob.get("sharding") is not None:
            sharding = ShardingContract.from_json(
                blob["name"], blob["sharding"]
            )
        return cls(
            name=blob["name"],
            world=int(blob["world"]),
            collectives={k: int(v) for k, v in blob["collectives"].items()},
            collective_bytes={
                k: int(v) for k, v in blob["collective_bytes"].items()
            },
            donated_declared=list(blob["donated_declared"]),
            donated_aliased={
                k: int(v) for k, v in blob["donated_aliased"].items()
            },
            host_callbacks={
                k: int(v) for k, v in blob["host_callbacks"].items()
            },
            upcasts={k: int(v) for k, v in blob["upcasts"].items()},
            sharding=sharding,
        )

    @property
    def total_collectives(self) -> int:
        return sum(self.collectives.values())


# ---------------------------------------------------------------------------
# jaxpr walking


def iter_eqns(jaxpr) -> Iterable[Any]:
    """Depth-first over every equation of a (closed) jaxpr, recursing
    into sub-jaxprs carried in equation params (``pjit``/``shard_map``
    call jaxprs, ``scan``/``while`` bodies, ``cond`` branches, custom-vjp
    jaxprs). Within one equation, a sub-jaxpr object reachable through
    several params is visited once — counts are program text, not
    execution traces (a scan body counts once regardless of length)."""
    if hasattr(jaxpr, "jaxpr"):  # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        seen: set[int] = set()
        for value in eqn.params.values():
            subs = value if isinstance(value, (list, tuple)) else (value,)
            for sub in subs:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns") and id(inner) not in seen:
                    seen.add(id(inner))
                    yield from iter_eqns(inner)


def _aval_bytes(aval) -> int:
    try:
        import numpy as np

        shape = tuple(getattr(aval, "shape", ()))
        dtype = getattr(aval, "dtype", None)
        if dtype is None:
            return 0
        return int(math.prod(shape)) * np.dtype(dtype).itemsize
    except (TypeError, ValueError):
        return 0


def _is_float_upcast(src_dtype, dst_dtype) -> bool:
    import numpy as np
    from jax import numpy as jnp

    try:
        src, dst = jnp.dtype(src_dtype), jnp.dtype(dst_dtype)
    except TypeError:
        return False
    return (
        jnp.issubdtype(src, np.floating)
        and jnp.issubdtype(dst, np.floating)
        and dst.itemsize > src.itemsize
    )


def summarize_jaxpr(closed_jaxpr) -> dict:
    """One pass over the program text: collective counts + per-shard
    payload-byte estimates, host-callback counts, and widening-float
    convert counts by dtype pair."""
    collectives: dict[str, int] = {}
    coll_bytes: dict[str, int] = {}
    callbacks: dict[str, int] = {}
    upcasts: dict[str, int] = {}
    for eqn in iter_eqns(closed_jaxpr):
        prim = prim_name(eqn)
        if prim in COLLECTIVE_PRIMS:
            collectives[prim] = collectives.get(prim, 0) + 1
            nbytes = sum(_aval_bytes(v.aval) for v in eqn.invars
                         if hasattr(v, "aval"))
            coll_bytes[prim] = coll_bytes.get(prim, 0) + nbytes
        elif prim in HOST_CALLBACK_PRIMS:
            callbacks[prim] = callbacks.get(prim, 0) + 1
        elif prim == "convert_element_type":
            invar = eqn.invars[0] if eqn.invars else None
            src = getattr(getattr(invar, "aval", None), "dtype", None)
            dst = eqn.params.get("new_dtype")
            if src is not None and _is_float_upcast(src, dst):
                key = f"{src}->{dst}"
                upcasts[key] = upcasts.get(key, 0) + 1
    return {
        "collectives": collectives,
        "collective_bytes": coll_bytes,
        "host_callbacks": callbacks,
        "upcasts": upcasts,
    }


# ---------------------------------------------------------------------------
# execution-weighted costing (the planner's static cost oracle)

#: Matmul-shaped primitives the weighted walk assigns flops to. Every
#: other primitive is treated as free — on the accelerators this stack
#: targets the MXU work dominates and elementwise ops ride along fused,
#: so the planner's *relative* ordering does not need them.
FLOP_PRIMS = frozenset({"dot_general", "conv_general_dilated"})


def _eqn_flops(eqn) -> int:
    """Multiply-add flop estimate (2·MACs) for one matmul-shaped
    equation, from the operand avals and dimension numbers. Returns 0
    for anything outside :data:`FLOP_PRIMS`."""
    prim = eqn.primitive.name
    if prim == "dot_general":
        lhs = getattr(eqn.invars[0], "aval", None)
        rhs = getattr(eqn.invars[1], "aval", None)
        if lhs is None or rhs is None:
            return 0
        (lc, _rc), (lb, _rb) = eqn.params["dimension_numbers"]
        contract = math.prod(lhs.shape[i] for i in lc) or 1
        batch = math.prod(lhs.shape[i] for i in lb) or 1
        lhs_free = max(1, math.prod(lhs.shape) // (contract * batch))
        rhs_free = max(1, math.prod(rhs.shape) // (contract * batch))
        return 2 * batch * lhs_free * rhs_free * contract
    if prim == "conv_general_dilated":
        rhs = getattr(eqn.invars[1], "aval", None)
        out = getattr(eqn.outvars[0], "aval", None)
        if rhs is None or out is None:
            return 0
        dn = eqn.params.get("dimension_numbers")
        rhs_spec = getattr(dn, "rhs_spec", None)
        out_ch = rhs.shape[rhs_spec[0]] if rhs_spec else max(rhs.shape)
        macs_per_out = max(1, math.prod(rhs.shape) // max(1, out_ch))
        groups = int(eqn.params.get("feature_group_count", 1) or 1)
        return 2 * math.prod(out.shape) * macs_per_out // max(1, groups)
    return 0


def weighted_cost_summary(closed_jaxpr) -> dict:
    """Execution-weighted pass over the program text: unlike
    :func:`summarize_jaxpr` (program text — a scan body counts once),
    this walk multiplies by the ``lax.scan`` trip count when it
    descends into a scan body, so a fused K-step program or a T-tick
    pipeline schedule is costed by what it *executes*, not what it
    spells. Returns per-device figures (shard_map bodies carry
    per-shard avals):

    * ``flops`` — 2·MAC estimate over :data:`FLOP_PRIMS`;
    * ``collective_bytes`` — per-primitive executed bytes-on-wire;
    * ``bytes_total`` — their sum;
    * ``host_callbacks`` — executed host round trips.

    ``while`` bodies are weighted by one trip (the count is not in the
    program text — a known under-estimate, stated in docs/PLANNER.md);
    ``cond`` contributes its most expensive branch."""

    def walk(jaxpr, weight: int):
        jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
        flops = 0
        cbytes: dict[str, int] = {}
        callbacks = 0

        def merge(f, cb, hb):
            nonlocal flops, callbacks
            flops += f
            callbacks += hb
            for k, v in cb.items():
                cbytes[k] = cbytes.get(k, 0) + v

        for eqn in jaxpr.eqns:
            prim = prim_name(eqn)
            if prim in COLLECTIVE_PRIMS:
                nbytes = sum(_aval_bytes(v.aval) for v in eqn.invars
                             if hasattr(v, "aval"))
                cbytes[prim] = cbytes.get(prim, 0) + weight * nbytes
            elif prim in HOST_CALLBACK_PRIMS:
                callbacks += weight
            elif prim in FLOP_PRIMS:
                flops += weight * _eqn_flops(eqn)
            if prim == "cond":
                branches = [
                    walk(b, weight)
                    for b in eqn.params.get("branches", ())
                    if hasattr(getattr(b, "jaxpr", b), "eqns")
                ]
                if branches:
                    merge(*max(branches, key=lambda c: c[0]))
                continue
            sub_w = weight
            if prim == "scan":
                sub_w = weight * int(eqn.params.get("length", 1) or 1)
            seen: set[int] = set()
            for value in eqn.params.values():
                subs = value if isinstance(value, (list, tuple)) \
                    else (value,)
                for sub in subs:
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns") and id(inner) not in seen:
                        seen.add(id(inner))
                        merge(*walk(inner, sub_w))
        return flops, cbytes, callbacks

    flops, cbytes, callbacks = walk(closed_jaxpr, 1)
    return {
        "flops": flops,
        "collective_bytes": cbytes,
        "bytes_total": sum(cbytes.values()),
        "host_callbacks": callbacks,
    }


# ---------------------------------------------------------------------------
# donation (StableHLO arg attributes)

_MAIN_SIG_RE = re.compile(
    r"func\.func\s+(?:public\s+)?@main\((.*?)\)\s*->", re.S
)
_ARG_RE = re.compile(r"%arg(\d+): tensor<[^>]*>\s*(\{[^}]*\})?")


def aliased_arg_indices(mlir_text: str) -> set[int]:
    """Flat input indices the lowering marked donatable: args whose
    attribute dict carries ``tf.aliasing_output`` (aliased to a specific
    output) or ``jax.buffer_donor`` (donated, XLA chooses the reuse)."""
    sig = _MAIN_SIG_RE.search(mlir_text)
    if sig is None:
        raise ValueError("no @main function signature in lowering text")
    out: set[int] = set()
    for idx, attrs in _ARG_RE.findall(sig.group(1)):
        if attrs and ("tf.aliasing_output" in attrs
                      or "jax.buffer_donor" in attrs):
            out.add(int(idx))
    return out


def donation_by_arg(
    mlir_text: str, arg_labels: Sequence[str], example_args: Sequence[Any]
) -> dict[str, int]:
    """Map the lowering's flat donated-arg indices back onto the
    top-level argument labels via each argument's pytree leaf count.
    Falls back to an aggregate ``__total__`` entry if the flat arity
    does not line up (e.g. a lowering that hoisted constants)."""
    import jax

    aliased = aliased_arg_indices(mlir_text)
    if not aliased:
        return {}
    leaf_counts = [
        len(jax.tree_util.tree_leaves(a)) for a in example_args
    ]
    sig = _MAIN_SIG_RE.search(mlir_text)
    n_args = len(_ARG_RE.findall(sig.group(1))) if sig else -1
    if sum(leaf_counts) != n_args:
        return {"__total__": len(aliased)}
    out: dict[str, int] = {}
    offset = 0
    for label, count in zip(arg_labels, leaf_counts):
        hit = sum(1 for i in range(offset, offset + count) if i in aliased)
        if hit:
            out[label] = hit
        offset += count
    return out


# ---------------------------------------------------------------------------
# extraction + comparison


def extract_contract(
    fn: Callable,
    example_args: Sequence[Any],
    *,
    name: str,
    world: int,
    arg_labels: Sequence[str],
    declared_donated: Sequence[str] = (),
    mesh: Any | None = None,
    in_specs: Sequence[Any] | None = None,
    memory: bool = False,
    replication_threshold: int | None = None,
) -> ProgramContract:
    """Abstractly trace ``fn`` (a jitted callable) on ``example_args``
    (arrays or ShapeDtypeStructs) and assemble its contract. Nothing is
    compiled or executed — ``jax.make_jaxpr`` for the program text,
    ``fn.lower(...)`` for the donation attributes.

    With ``mesh`` and ``in_specs`` (one prefix spec tree per argument —
    the same shapes the trainers hand to ``shard_map``), the layer-3
    sharding-flow pass (:mod:`tpu_syncbn.audit.sharding_audit`) runs
    over the same trace and its :class:`ShardingContract` is attached.
    ``memory=True`` additionally compiles the program once to record
    XLA's ``memory_analysis`` figure as the peak-memory cross-check —
    the only path here that compiles anything."""
    import jax

    closed = jax.make_jaxpr(fn)(*example_args)
    summary = summarize_jaxpr(closed)
    lowered = fn.lower(*example_args)
    aliased = donation_by_arg(lowered.as_text(), arg_labels, example_args)
    sharding = None
    if mesh is not None and in_specs is not None:
        from tpu_syncbn.audit import sharding_audit

        kwargs: dict = {}
        if replication_threshold is not None:
            kwargs["replication_threshold"] = replication_threshold
        flow = sharding_audit.analyze_program(
            fn, example_args, mesh=mesh, in_specs=in_specs,
            closed_jaxpr=closed, **kwargs,
        )
        leaf_specs: dict[str, list[str]] = {}
        for label, arg, spec in zip(arg_labels, example_args, in_specs):
            strs = sorted({
                sharding_audit.spec_leaf_str(s)
                for s in sharding_audit.broadcast_spec(spec, arg)
            })
            leaf_specs[label] = strs
        sharding = ShardingContract(
            name=name,
            mesh_axes=flow.mesh_axes,
            in_specs=leaf_specs,
            out_specs=flow.out_spec_strs(),
            collectives_explained=flow.collectives_explained,
            implicit_reshards=flow.implicit_reshards,
            reshard_detail=flow.reshard_detail,
            replicated_intermediates=flow.replicated_intermediates,
            replication_detail=flow.replication_detail,
            max_replicated_bytes=flow.max_replicated_bytes,
            peak_bytes_per_device=flow.peak_bytes_per_device,
            replication_threshold=flow.replication_threshold,
            xla_peak_bytes=(
                sharding_audit.xla_peak_bytes(fn, example_args)
                if memory else None
            ),
        )
    return ProgramContract(
        name=name,
        world=world,
        collectives=summary["collectives"],
        collective_bytes=summary["collective_bytes"],
        donated_declared=list(declared_donated),
        donated_aliased=aliased,
        host_callbacks=summary["host_callbacks"],
        upcasts=summary["upcasts"],
        sharding=sharding,
    )


def compare_sharding(
    actual: ShardingContract, golden: ShardingContract, name: str
) -> list[str]:
    """Field-by-field diff of two layer-3 blocks. ``xla_peak_bytes`` is
    compared with :data:`XLA_PEAK_RTOL` relative tolerance and skipped
    when either side did not compile (None); everything else is exact —
    the pass is deterministic arithmetic over the program text."""
    diffs: list[str] = []

    def _ne(field: str, a, g) -> None:
        if a != g:
            diffs.append(
                f"{name}: sharding.{field} = {a!r}, golden pins {g!r}"
            )

    _ne("mesh_axes", dict(sorted(actual.mesh_axes.items())),
        dict(sorted(golden.mesh_axes.items())))
    for label in sorted(set(actual.in_specs) | set(golden.in_specs)):
        _ne(f"in_specs[{label}]", actual.in_specs.get(label, []),
            golden.in_specs.get(label, []))
    _ne("out_specs", list(actual.out_specs), list(golden.out_specs))
    _ne("collectives_explained", actual.collectives_explained,
        golden.collectives_explained)
    _ne("implicit_reshards", actual.implicit_reshards,
        golden.implicit_reshards)
    _ne("reshard_detail", list(actual.reshard_detail),
        list(golden.reshard_detail))
    _ne("replicated_intermediates", actual.replicated_intermediates,
        golden.replicated_intermediates)
    _ne("replication_detail", list(actual.replication_detail),
        list(golden.replication_detail))
    _ne("max_replicated_bytes", actual.max_replicated_bytes,
        golden.max_replicated_bytes)
    _ne("peak_bytes_per_device", actual.peak_bytes_per_device,
        golden.peak_bytes_per_device)
    _ne("replication_threshold", actual.replication_threshold,
        golden.replication_threshold)
    if actual.xla_peak_bytes is not None \
            and golden.xla_peak_bytes is not None:
        hi = max(actual.xla_peak_bytes, golden.xla_peak_bytes)
        if hi and abs(actual.xla_peak_bytes - golden.xla_peak_bytes) \
                > XLA_PEAK_RTOL * hi:
            diffs.append(
                f"{name}: sharding.xla_peak_bytes = "
                f"{actual.xla_peak_bytes}, golden pins "
                f"{golden.xla_peak_bytes} (>±{XLA_PEAK_RTOL:.0%})"
            )
    return diffs


def compare_contracts(
    actual: ProgramContract, golden: ProgramContract
) -> list[str]:
    """Field-by-field diff; empty list means the program still honors
    its pinned contract. Messages name the drift precisely — they are
    the violation text the CLI and the tier-1 tests surface."""
    diffs: list[str] = []

    def _dict_diff(field: str, a: dict, g: dict) -> None:
        for key in sorted(set(a) | set(g)):
            av, gv = a.get(key, 0), g.get(key, 0)
            if av != gv:
                diffs.append(
                    f"{actual.name}: {field}[{key}] = {av}, golden pins {gv}"
                )

    if actual.world != golden.world:
        diffs.append(
            f"{actual.name}: traced on world={actual.world} but golden "
            f"was pinned on world={golden.world} — contracts are only "
            "comparable on the pinned mesh"
        )
        return diffs
    _dict_diff("collectives", actual.collectives, golden.collectives)
    _dict_diff("collective_bytes", actual.collective_bytes,
               golden.collective_bytes)
    _dict_diff("host_callbacks", actual.host_callbacks,
               golden.host_callbacks)
    _dict_diff("upcasts", actual.upcasts, golden.upcasts)
    if list(actual.donated_declared) != list(golden.donated_declared):
        diffs.append(
            f"{actual.name}: declared donation {actual.donated_declared} "
            f"!= golden {golden.donated_declared}"
        )
    _dict_diff("donated_aliased", actual.donated_aliased,
               golden.donated_aliased)
    if actual.sharding is not None and golden.sharding is not None:
        diffs.extend(compare_sharding(
            actual.sharding, golden.sharding, actual.name
        ))
    elif actual.sharding is not None:
        diffs.append(
            f"{actual.name}: program has a layer-3 sharding block "
            "but the golden pins none — re-pin with --write-goldens "
            "(docs/STATIC_ANALYSIS.md 'Layer 3')"
        )
    elif golden.sharding is not None:
        # the inverse is just as dangerous: a registry edit that stops
        # supplying mesh/in_specs would otherwise silently disable
        # every pinned layer-3 invariant for this program
        diffs.append(
            f"{actual.name}: golden pins a layer-3 sharding block but "
            "the program was traced without one — the extractor lost "
            "its mesh/in_specs (registry regression), or re-pin "
            "deliberately with --write-goldens"
        )
    return diffs


def save_contract(contract: ProgramContract, path: str) -> None:
    with open(path, "w") as f:
        json.dump(contract.to_json(), f, indent=1, sort_keys=False)
        f.write("\n")


def load_contract(path: str) -> ProgramContract:
    with open(path) as f:
        return ProgramContract.from_json(json.load(f))
