"""Layer 3 of the program auditor: sharding-flow analysis.

Layer 1 (:mod:`tpu_syncbn.audit.jaxpr_audit`) counts what a program puts
on the wire; this layer tracks **where every value lives** — an abstract
interpretation over the closed jaxpr that propagates per-value named
sharding from the declared ``in_shardings`` through elementwise ops,
reductions, collectives, ``scan``/``while``/``cond`` bodies, and
``shard_map`` boundaries, the way GSPMD-style propagation does inside
XLA (PAPERS.md: "Automatic Cross-Replica Sharding of Weight Update",
arXiv:2004.13336) but *statically*, on the program text — no array is
ever materialized (the redistribution-planning stance of arXiv:2112.01075).

Two abstract domains, one per view:

* **global view** (outside ``shard_map``): each value carries a
  per-dimension tuple of mesh-axis names — a :class:`PartitionSpec`
  shadow. Elementwise ops merge operand layouts (a sharded operand
  meeting a replicated one wins for free — replicated→sharded is local
  slicing); two operands sharded *differently* on the same dimension, or
  a ``sharding_constraint`` that un-shards a sharded value, force data
  movement no declared collective explains — an **implicit reshard**.
* **local view** (inside a ``shard_map`` body): values are per-device
  shards, so the useful fact is the set of mesh axes a value is
  *replicated over* (the VMA complement). Collectives move values
  between the two poles explicitly — ``psum``/``all_gather`` end
  replicated over their axes, ``reduce_scatter``/``ppermute``/
  ``all_to_all`` end varying — and every such hop is counted as an
  *explained* layout change.

On top of the propagated layouts the pass reports:

* **accidental replication** — an intermediate (an equation output, not
  a program input) that is fully replicated on every device while its
  per-device footprint exceeds a byte threshold. Replicating the full
  value on all chips is the memory blow-up ZeRO exists to avoid; doing
  it *by accident* (a gather that outlived its use, a constant built at
  full size inside the body) is exactly what this detector pins.
* **implicit resharding** — a layout change not explained by a declared
  collective (see above), including entering a ``shard_map`` whose
  ``in_specs`` disagree with the operand's propagated layout in a way
  that requires communication (sharded→replicated or axis-to-axis;
  replicated→sharded is free slicing and is not flagged).
* **per-device peak memory** — a liveness scan over the program text:
  at every program point, the sum of per-device bytes of all live
  values (global values divided by their sharding factor, local values
  at shard size), with sub-jaxpr frames (scan/while/cond bodies, pjit
  calls, shard_map bodies) contributing their own peak minus the
  operand bytes already live in the caller. An *upper-bound-shaped
  estimate* — XLA fuses, rematerializes, and reuses donated buffers, so
  the cross-check against ``memory_analysis()`` (recorded as
  ``xla_peak_bytes`` when the caller compiles) is the honesty anchor,
  not a number this pass can hit exactly.

Approximations (deliberate, documented): global-view propagation is
conservative for rank-changing ops (reshape/dot/reduce fall back to
"unsharded" without counting a reshard — our programs do their math
inside ``shard_map``, where the local domain is exact); donation-driven
buffer reuse is ignored by the peak estimate; ``ppermute`` of an
actually-replicated value is treated as varying (under-claiming
replication can only *miss* a detection, never invent one).

Results serialize as a :class:`~tpu_syncbn.audit.contracts.ShardingContract`
block inside each program's golden (docs/STATIC_ANALYSIS.md "Layer 3").
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Sequence

from tpu_syncbn.audit.contracts import prim_name

#: Fully-replicated intermediates at or above this per-device footprint
#: are reported as accidental replication (``sharding.replication``).
#: 1 MiB: big enough that every pinned tiny-model program is quiet, small
#: enough that a real gathered layer or full-size constant trips it.
REPLICATION_THRESHOLD_BYTES = 1 << 20

#: How many detail strings each detector keeps (counts are exact; the
#: details are for humans and golden review, not accounting).
_MAX_DETAIL = 8

# -- abstract domains --------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GlobalLayout:
    """Global-view layout: per-dimension tuple of mesh-axis names (the
    PartitionSpec shadow). ``dims[d] == ()`` means dimension ``d`` is
    not sharded; all dims ``()`` means the value is fully replicated."""

    dims: tuple[tuple[str, ...], ...]

    @property
    def sharded_axes(self) -> frozenset:
        return frozenset(a for d in self.dims for a in d)


@dataclasses.dataclass(frozen=True)
class LocalLayout:
    """Local-view (shard_map body) layout: the set of mesh axes this
    per-device value is *replicated over* (identical across). Empty set
    = fully device-varying; the full axis set = every device holds the
    same bytes."""

    replicated: frozenset


def _norm_entry(entry) -> tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def spec_to_dims(spec, rank: int) -> tuple[tuple[str, ...], ...]:
    """A PartitionSpec (or None) to a rank-padded dims tuple."""
    entries = tuple(spec) if spec is not None else ()
    dims = [_norm_entry(e) for e in entries[:rank]]
    dims += [()] * (rank - len(dims))
    return tuple(dims)


def dims_to_spec_str(dims: Sequence[tuple[str, ...]]) -> str:
    """Canonical spec string for a dims tuple — trailing unsharded dims
    trimmed, so ``P('data')`` and ``P('data', None)`` print the same."""
    dims = list(dims)
    while dims and dims[-1] == ():
        dims.pop()
    if not dims:
        return "P()"
    parts = []
    for d in dims:
        if not d:
            parts.append("None")
        elif len(d) == 1:
            parts.append(f"'{d[0]}'")
        else:
            parts.append("(" + ", ".join(f"'{a}'" for a in d) + ")")
    return f"P({', '.join(parts)})"


def spec_leaf_str(spec) -> str:
    """Canonical string for a declared PartitionSpec leaf."""
    entries = tuple(spec) if spec is not None else ()
    return dims_to_spec_str([_norm_entry(e) for e in entries])


def broadcast_spec(spec, example) -> list:
    """Expand a prefix spec tree (a single ``P`` covering a whole
    argument subtree, or a container of such prefixes — the trainers'
    ``_pspec``/``_opt_spec`` shapes) into one spec per leaf of
    ``example``, in ``tree_flatten`` order."""
    import jax
    from jax.sharding import PartitionSpec as P

    def is_spec(s) -> bool:
        return s is None or isinstance(s, P)

    def rec(s, e) -> list:
        if is_spec(s):
            return [s] * len(jax.tree_util.tree_leaves(e))
        if isinstance(s, dict):
            if set(s) != set(e):
                raise ValueError(
                    f"spec keys {sorted(s)} do not match arg keys "
                    f"{sorted(e)}"
                )
            # jax flattens dicts in sorted-key order
            return [x for k in sorted(s) for x in rec(s[k], e[k])]
        if isinstance(s, (tuple, list)):
            if len(s) != len(e):
                raise ValueError(
                    f"spec arity {len(s)} does not match arg arity {len(e)}"
                )
            return [x for ss, ee in zip(s, e) for x in rec(ss, ee)]
        raise TypeError(
            f"unsupported spec node {type(s).__name__} — specs are "
            "PartitionSpecs or dict/tuple/list containers of them"
        )

    return rec(spec, example)


# -- byte accounting ---------------------------------------------------------


def _aval_bytes(aval) -> int:
    import numpy as np

    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0
    try:
        return int(math.prod(tuple(getattr(aval, "shape", ())))) \
            * np.dtype(dtype).itemsize
    except (TypeError, ValueError):
        return 0


def _shard_factor(layout, mesh_axes: dict) -> int:
    if isinstance(layout, GlobalLayout):
        f = 1
        for d in layout.dims:
            for a in d:
                f *= mesh_axes.get(a, 1)
        return max(1, f)
    return 1  # local avals are already per-device


def _value_bytes(aval, layout, mesh_axes: dict) -> int:
    return _aval_bytes(aval) // _shard_factor(layout, mesh_axes)


def _fully_replicated(aval, layout, mesh_axes: dict) -> bool:
    """Every device holds the complete value."""
    if getattr(aval, "shape", None) is None:
        return False
    if isinstance(layout, LocalLayout):
        return layout.replicated == frozenset(mesh_axes)
    return not layout.sharded_axes


# -- the flow result ---------------------------------------------------------


@dataclasses.dataclass
class ShardingFlow:
    """What one analysis pass learned about one program."""

    mesh_axes: dict[str, int]
    out_layouts: list
    collectives_explained: int
    implicit_reshards: int
    reshard_detail: list[str]
    replicated_intermediates: int
    replication_detail: list[str]
    max_replicated_bytes: int
    peak_bytes_per_device: int
    replication_threshold: int

    def out_spec_strs(self) -> list[str]:
        """Distinct canonical spec strings over the program outputs."""
        strs = set()
        for lo in self.out_layouts:
            if isinstance(lo, GlobalLayout):
                strs.add(dims_to_spec_str(lo.dims))
            else:  # pragma: no cover - outputs are always global-view
                strs.add(f"<local:{sorted(lo.replicated)}>")
        return sorted(strs)


class _Collector:
    """Mutable event sink for one analysis; the recording passes append
    here, the fixpoint passes run with recording off."""

    def __init__(self, mesh_axes: dict[str, int], threshold: int):
        self.mesh_axes = dict(mesh_axes)
        self.threshold = int(threshold)
        self.collectives_explained = 0
        self.implicit_reshards = 0
        self.reshard_detail: list[str] = []
        self.replicated_count = 0
        self.replication_detail: list[str] = []
        self.max_replicated_bytes = 0

    def reshard(self, prim: str, msg: str) -> None:
        self.implicit_reshards += 1
        if len(self.reshard_detail) < _MAX_DETAIL:
            self.reshard_detail.append(f"{prim}: {msg}")

    def replicated(self, prim: str, aval, nbytes: int) -> None:
        self.max_replicated_bytes = max(self.max_replicated_bytes, nbytes)
        if nbytes >= self.threshold:
            self.replicated_count += 1
            if len(self.replication_detail) < _MAX_DETAIL:
                self.replication_detail.append(
                    f"{prim}: {aval.dtype}{list(aval.shape)} "
                    f"({nbytes} B/device)"
                )


# -- primitive tables --------------------------------------------------------

#: local-view collective effects: axes named by the eqn end up in
#: (``add``) or out of (``sub``) the output's replicated set.
_COLLECTIVE_EFFECT = {
    "psum": "add", "pmax": "add", "pmin": "add", "all_gather": "add",
    "reduce_scatter": "sub", "psum_scatter": "sub", "ppermute": "sub",
    "pgather": "sub", "all_to_all": "sub",
}

_SUBJAXPR_CALLS = {
    "jit", "pjit", "closed_call", "core_call", "remat", "checkpoint",
    "remat2", "custom_jvp_call", "custom_vjp_call",
    "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr",
}

_CALL_JAXPR_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def _eqn_axes(eqn) -> tuple[str, ...]:
    """Named mesh axes a collective eqn operates over (positional int
    axes from vmap are ignored — they are not mesh axes)."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(axes, (str, int)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def _spec_names(spec) -> dict[int, tuple[str, ...]]:
    """A shard_map eqn's ``PartitionSpec`` as ``{dim: mesh axes}`` over
    the dims it splits."""
    names = {}
    for dim, entry in enumerate(spec):
        if entry is not None:
            names[dim] = entry if isinstance(entry, tuple) else (entry,)
    return names


def _call_jaxpr(eqn):
    for key in _CALL_JAXPR_PARAMS:
        sub = eqn.params.get(key)
        if sub is not None:
            return getattr(sub, "jaxpr", sub)
    return None


# -- the interpreter ---------------------------------------------------------


class _Interp:
    def __init__(self, col: _Collector):
        self.col = col
        self.all_axes = frozenset(col.mesh_axes)

    # .. env plumbing ........................................................

    def _read(self, env: dict, var, *, local: bool):
        from jax._src import core as jcore

        if isinstance(var, jcore.Literal):
            return self._default(var.aval, local=local)
        return env[var]

    def _default(self, aval, *, local: bool):
        """Layout for a value with no tracked producer (literals,
        constants): the same computation runs on every device, so it is
        replicated / unsharded."""
        if local:
            return LocalLayout(self.all_axes)
        return GlobalLayout(((),) * len(getattr(aval, "shape", ())))

    def _join(self, a, b):
        if isinstance(a, LocalLayout):
            return LocalLayout(a.replicated & b.replicated)
        dims = tuple(
            da if da == db else ()
            for da, db in zip(a.dims, b.dims)
        )
        return GlobalLayout(dims)

    # .. walking .............................................................

    def walk(self, jaxpr, in_layouts: Sequence, *, local: bool,
             record: bool) -> tuple[list, int]:
        """Propagate through one (open) jaxpr. Returns
        ``(out_layouts, peak_bytes)``; events are appended to the
        collector only when ``record``."""
        env: dict = {}
        for var, lo in zip(jaxpr.invars, in_layouts):
            env[var] = lo
        for var in jaxpr.constvars:
            env[var] = self._default(var.aval, local=local)

        # liveness: last use index per var (program-text order). A
        # ``pvary`` retypes its operand for the VMA checker and moves
        # nothing: its result is the operand's buffer (``alias``), so it
        # weighs nothing itself and keeps the operand alive instead.
        last_use: dict = {}
        alias: dict = {}
        from jax._src import core as jcore

        def use(v, idx):
            if not isinstance(v, jcore.Literal):
                last_use[v] = idx
                if alias.get(v) is not None:
                    last_use[alias[v]] = idx

        for idx, eqn in enumerate(jaxpr.eqns):
            for v in eqn.invars:
                use(v, idx)
            if eqn.primitive.name == "pvary":
                for out, src in zip(eqn.outvars, eqn.invars):
                    alias[out] = None if isinstance(src, jcore.Literal) \
                        else alias.get(src, src)
        for v in jaxpr.outvars:
            use(v, len(jaxpr.eqns))

        def vbytes(var) -> int:
            lo = env.get(var)
            if lo is None or var in alias:
                return 0
            return _value_bytes(var.aval, lo, self.col.mesh_axes)

        live_bytes = sum(
            vbytes(v) for v in (*jaxpr.invars, *jaxpr.constvars)
        )
        peak = live_bytes

        for idx, eqn in enumerate(jaxpr.eqns):
            in_los = [self._read(env, v, local=local) for v in eqn.invars]
            out_los, extra = self._eqn(eqn, in_los, local=local,
                                       record=record)
            for var, lo in zip(eqn.outvars, out_los):
                if type(var).__name__ == "DropVar":
                    continue
                env[var] = lo
                if record and var not in alias \
                        and _fully_replicated(var.aval, lo,
                                                self.col.mesh_axes) \
                        and len(self.col.mesh_axes) \
                        and math.prod(self.col.mesh_axes.values()) > 1:
                    self.col.replicated(
                        eqn.primitive.name, var.aval,
                        _value_bytes(var.aval, lo, self.col.mesh_axes),
                    )
            live_bytes += sum(
                vbytes(v) for v in eqn.outvars
                if type(v).__name__ != "DropVar"
            )
            peak = max(peak, live_bytes + extra)
            # free values whose last use was this eqn
            for v in set(alias.get(v, v) for v in eqn.invars
                         if not isinstance(v, jcore.Literal)):
                if last_use.get(v) == idx and v in env:
                    live_bytes -= vbytes(v)
            for v in eqn.outvars:
                if type(v).__name__ != "DropVar" \
                        and last_use.get(v, -1) < idx + 1 and v in env:
                    live_bytes -= vbytes(v)  # dead on arrival

        outs = [self._read(env, v, local=local) for v in jaxpr.outvars]
        return outs, peak

    # .. one equation ........................................................

    def _eqn(self, eqn, in_los: list, *, local: bool,
             record: bool) -> tuple[list, int]:
        prim = eqn.primitive.name

        if prim == "shard_map":
            return self._shard_map(eqn, in_los, record=record)
        if prim == "scan":
            return self._scan(eqn, in_los, local=local, record=record)
        if prim == "while":
            return self._while(eqn, in_los, local=local, record=record)
        if prim == "cond":
            return self._cond(eqn, in_los, local=local, record=record)
        sub = _call_jaxpr(eqn) if prim in _SUBJAXPR_CALLS else None
        if sub is not None and len(sub.invars) == len(in_los):
            outs, peak = self.walk(sub, in_los, local=local, record=record)
            return outs, self._frame_extra(peak, sub, in_los, outs)

        if local:
            return self._local_eqn(eqn, in_los, record=record), 0
        return self._global_eqn(eqn, in_los, record=record), 0

    def _local_eqn(self, eqn, in_los: list, *, record: bool) -> list:
        prim = prim_name(eqn)
        effect = _COLLECTIVE_EFFECT.get(prim)
        # only MESH axes move data between devices: a vmap-minted named
        # axis ('batch') on the same primitive is intra-device and must
        # neither pollute the replicated-set lattice nor count as an
        # explained mesh collective
        if effect is not None:
            axes = frozenset(_eqn_axes(eqn)) & self.all_axes
            if axes:
                # tuple collectives (ppermute of (k, v), multi-operand
                # psum) act leaf-wise: pair each output with ITS input
                # when the arity matches; otherwise fall back to the
                # intersection of all inputs (the under-claiming
                # direction — a miss, never an invention)
                if in_los and len(in_los) == len(eqn.outvars):
                    bases = [lo.replicated for lo in in_los]
                elif in_los:
                    common = frozenset.intersection(
                        *[lo.replicated for lo in in_los]
                    )
                    bases = [common] * len(eqn.outvars)
                else:
                    bases = [frozenset()] * len(eqn.outvars)
                if record:
                    self.col.collectives_explained += 1
                if effect == "add":
                    return [LocalLayout(b | axes) for b in bases]
                return [LocalLayout(b - axes) for b in bases]
            # vmap-only collective: a pure function of its inputs
        if prim == "axis_index":
            axes = frozenset(_eqn_axes(eqn)) & self.all_axes
            if axes:
                return [LocalLayout(self.all_axes - axes)]
        if not in_los:
            return [LocalLayout(self.all_axes) for _ in eqn.outvars]
        repl = frozenset.intersection(*[lo.replicated for lo in in_los])
        return [LocalLayout(repl) for _ in eqn.outvars]

    def _global_eqn(self, eqn, in_los: list, *, record: bool) -> list:
        prim = eqn.primitive.name
        if prim == "sharding_constraint":
            (src,) = in_los
            sharding = eqn.params.get("sharding")
            spec = getattr(sharding, "spec", None)
            rank = len(eqn.outvars[0].aval.shape)
            dst = GlobalLayout(spec_to_dims(spec, rank))
            if record and self._needs_move(src, dst):
                self.col.reshard(
                    prim,
                    f"{dims_to_spec_str(src.dims)} -> "
                    f"{dims_to_spec_str(dst.dims)} forced by a sharding "
                    "constraint with no collective to explain it",
                )
            return [dst]
        if prim == "transpose":
            (src,) = in_los
            perm = eqn.params.get("permutation", ())
            return [GlobalLayout(tuple(src.dims[p] for p in perm))]
        if prim == "broadcast_in_dim":
            src = in_los[0]
            out_aval = eqn.outvars[0].aval
            bdims = eqn.params.get("broadcast_dimensions", ())
            dims = [()] * len(out_aval.shape)
            src_shape = getattr(eqn.invars[0].aval, "shape", ())
            for i, od in enumerate(bdims):
                if i < len(src.dims) and i < len(src_shape) \
                        and src_shape[i] == out_aval.shape[od]:
                    dims[od] = src.dims[i]
            return [GlobalLayout(tuple(dims))]
        if prim in ("reduce_sum", "reduce_max", "reduce_min",
                    "reduce_prod", "reduce_and", "reduce_or", "argmax",
                    "argmin"):
            (src,) = in_los[:1]
            axes = set(eqn.params.get("axes", ()))
            dims = tuple(d for i, d in enumerate(src.dims)
                         if i not in axes)
            return [GlobalLayout(dims)
                    for _ in eqn.outvars]
        if prim == "convert_element_type" or prim == "copy":
            return [in_los[0]]

        out_aval = eqn.outvars[0].aval
        out_shape = getattr(out_aval, "shape", ())
        arrayish = [
            (v, lo) for v, lo in zip(eqn.invars, in_los)
            if tuple(getattr(v.aval, "shape", ())) == tuple(out_shape)
            and len(out_shape) > 0
        ]
        if arrayish and len(arrayish) == sum(
            1 for v in eqn.invars
            if len(getattr(v.aval, "shape", ())) > 0
        ):
            # same-shape elementwise: merge, flagging true conflicts
            dims = list(arrayish[0][1].dims)
            for _, lo in arrayish[1:]:
                for d in range(len(dims)):
                    a, b = dims[d], lo.dims[d]
                    if a and b and a != b:
                        if record:
                            self.col.reshard(
                                prim,
                                f"operands sharded {a} vs {b} on dim {d} "
                                "meet with no collective between them",
                            )
                        dims[d] = a
                    elif b and not a:
                        dims[d] = b
            return [GlobalLayout(tuple(dims)) for _ in eqn.outvars]
        # rank-changing / contracting op: conservative unsharded output
        # (documented approximation — real programs do this inside
        # shard_map, where the local domain is exact)
        return [
            GlobalLayout(((),) * len(getattr(v.aval, "shape", ())))
            for v in eqn.outvars
        ]

    def _frame_extra(self, inner_peak: int, sub_jaxpr, in_los: Sequence,
                     out_los: Sequence) -> int:
        """What a sub-frame adds to the caller's liveness at its call
        site. The frame's inputs alias values the caller already counts
        live, and its outputs alias the call equation's outvars (which
        the caller adds itself) — both are subtracted so passthrough
        frames contribute zero instead of double-counting. A mid-frame
        peak before the outputs exist is slightly over-charged (the
        caller has pre-added the output bytes) — the conservative
        direction for an upper-bound-shaped estimate."""
        inner_in = sum(
            _value_bytes(v.aval, lo, self.col.mesh_axes)
            for v, lo in zip(sub_jaxpr.invars, in_los)
        )
        inner_out = sum(
            _value_bytes(v.aval, lo, self.col.mesh_axes)
            for v, lo in zip(sub_jaxpr.outvars, out_los)
        )
        return max(0, inner_peak - inner_in - inner_out)

    @staticmethod
    def _needs_move(src: GlobalLayout, dst: GlobalLayout) -> bool:
        """Does going src→dst require communication? Replicated→sharded
        is local slicing (free); sharded→anything-else moves bytes."""
        for a, b in zip(src.dims, dst.dims):
            if a and a != b:
                return True
        return False

    # .. structured prims ....................................................

    def _fixpoint_cap(self, carry: Sequence) -> int:
        """Iteration bound for a carry-layout fixpoint. The join is
        monotone on a finite lattice: each carry can strictly descend
        at most once per mesh axis (local view: the replicated set only
        shrinks) or once per dimension (global view: each dim widens to
        unsharded once) — but a descent can take one *iteration per
        carry* to propagate along a carry chain (c2'=c1, c3'=c2, …), so
        the bound is the total possible descents, not the axis count."""
        total = 2
        for lo in carry:
            if isinstance(lo, GlobalLayout):
                total += max(1, len(lo.dims))
            else:
                total += max(1, len(self.col.mesh_axes))
        return total

    def _shard_map(self, eqn, in_los: list, *, record: bool):
        mesh = eqn.params["mesh"]
        mesh_axes = {str(k): int(v) for k, v in dict(mesh.shape).items()}
        in_names = [_spec_names(s) for s in eqn.params["in_specs"]]
        out_names = [_spec_names(s) for s in eqn.params["out_specs"]]
        body = getattr(eqn.params["jaxpr"], "jaxpr", eqn.params["jaxpr"])

        # boundary check: operand global layout vs the declared in_names
        body_in: list = []
        inner_axes = frozenset(mesh_axes)
        for var, lo, names in zip(eqn.invars, in_los, in_names):
            rank = len(getattr(var.aval, "shape", ()))
            want = GlobalLayout(tuple(
                tuple(names.get(d, ())) for d in range(rank)
            ))
            if record and isinstance(lo, GlobalLayout) \
                    and self._needs_move(lo, want):
                self.col.reshard(
                    "shard_map",
                    f"operand arrives {dims_to_spec_str(lo.dims)} but the "
                    f"in_spec wants {dims_to_spec_str(want.dims)} — jit "
                    "reshards it silently before entry",
                )
            split = frozenset(a for axs in names.values() for a in axs)
            body_in.append(LocalLayout(inner_axes - split))

        # analyze the body in the (possibly different) inner mesh
        saved_axes, saved_all = self.col.mesh_axes, self.all_axes
        self.col.mesh_axes = mesh_axes
        self.all_axes = frozenset(mesh_axes)
        try:
            body_outs, body_peak = self.walk(
                body, body_in, local=True, record=record
            )
            extra = self._frame_extra(body_peak, body, body_in, body_outs)
        finally:
            self.col.mesh_axes, self.all_axes = saved_axes, saved_all

        outs = []
        for var, names in zip(eqn.outvars, out_names):
            rank = len(getattr(var.aval, "shape", ()))
            outs.append(GlobalLayout(tuple(
                tuple(names.get(d, ())) for d in range(rank)
            )))
        return outs, extra

    def _scan(self, eqn, in_los: list, *, local: bool, record: bool):
        body = getattr(eqn.params["jaxpr"], "jaxpr", eqn.params["jaxpr"])
        n_consts = eqn.params["num_consts"]
        n_carry = eqn.params["num_carry"]
        consts = in_los[:n_consts]
        carry = list(in_los[n_consts:n_consts + n_carry])
        xs = in_los[n_consts + n_carry:]
        # an xs slice drops the leading (scan) axis
        xs_slices = []
        for lo in xs:
            if isinstance(lo, GlobalLayout):
                xs_slices.append(GlobalLayout(lo.dims[1:]))
            else:
                xs_slices.append(lo)

        def run(carry_los, *, rec):
            outs, peak = self.walk(
                body, [*consts, *carry_los, *xs_slices],
                local=local, record=rec,
            )
            return outs[:n_carry], outs[n_carry:], peak

        for _ in range(self._fixpoint_cap(carry)):
            new_carry, _, _ = run(carry, rec=False)
            joined = [self._join(a, b) for a, b in zip(carry, new_carry)]
            if joined == carry:
                break
            carry = joined
        carry_out, ys, body_peak = run(carry, rec=record)
        # stacked ys: leading axis is unsharded
        ys_out = []
        for lo in ys:
            if isinstance(lo, GlobalLayout):
                ys_out.append(GlobalLayout(((),) + lo.dims))
            else:
                ys_out.append(lo)
        extra = self._frame_extra(
            body_peak, body, [*consts, *carry, *xs_slices],
            [*carry_out, *ys],
        )
        return [*carry_out, *ys_out], extra

    def _while(self, eqn, in_los: list, *, local: bool, record: bool):
        cond_j = getattr(eqn.params["cond_jaxpr"], "jaxpr",
                         eqn.params["cond_jaxpr"])
        body_j = getattr(eqn.params["body_jaxpr"], "jaxpr",
                         eqn.params["body_jaxpr"])
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        cond_consts = in_los[:cn]
        body_consts = in_los[cn:cn + bn]
        carry = list(in_los[cn + bn:])

        for _ in range(self._fixpoint_cap(carry)):
            new_carry, _ = self.walk(
                body_j, [*body_consts, *carry], local=local, record=False
            )
            joined = [self._join(a, b) for a, b in zip(carry, new_carry)]
            if joined == carry:
                break
            carry = joined
        out, body_peak = self.walk(
            body_j, [*body_consts, *carry], local=local, record=record
        )
        cond_out, cond_peak = self.walk(
            cond_j, [*cond_consts, *carry], local=local, record=record
        )
        return out, max(
            self._frame_extra(body_peak, body_j,
                              [*body_consts, *carry], out),
            self._frame_extra(cond_peak, cond_j,
                              [*cond_consts, *carry], cond_out),
        )

    def _cond(self, eqn, in_los: list, *, local: bool, record: bool):
        branches = eqn.params["branches"]
        op_los = in_los[1:]  # first invar is the predicate/index
        outs = None
        extra = 0
        for br in branches:
            bj = getattr(br, "jaxpr", br)
            b_outs, b_peak = self.walk(
                bj, op_los, local=local, record=record
            )
            extra = max(extra, self._frame_extra(
                b_peak, bj, op_los, b_outs
            ))
            outs = b_outs if outs is None else [
                self._join(a, b) for a, b in zip(outs, b_outs)
            ]
        return outs or [], extra


# -- entry points ------------------------------------------------------------


def analyze_jaxpr(
    closed_jaxpr,
    mesh_axes: dict[str, int],
    in_layouts: Sequence[GlobalLayout],
    *,
    replication_threshold: int = REPLICATION_THRESHOLD_BYTES,
) -> ShardingFlow:
    """Run the propagation over a closed jaxpr whose flat inputs carry
    ``in_layouts`` (global view)."""
    col = _Collector(mesh_axes, replication_threshold)
    interp = _Interp(col)
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    outs, peak = interp.walk(
        jaxpr, list(in_layouts), local=False, record=True
    )
    return ShardingFlow(
        mesh_axes=dict(mesh_axes),
        out_layouts=outs,
        collectives_explained=col.collectives_explained,
        implicit_reshards=col.implicit_reshards,
        reshard_detail=col.reshard_detail,
        replicated_intermediates=col.replicated_count,
        replication_detail=col.replication_detail,
        max_replicated_bytes=col.max_replicated_bytes,
        peak_bytes_per_device=peak,
        replication_threshold=col.threshold,
    )


def _flat_in_layouts(example_args: Sequence, in_specs: Sequence,
                     closed_jaxpr) -> list[GlobalLayout]:
    import jax

    leaf_specs: list = []
    for arg, spec in zip(example_args, in_specs):
        leaf_specs.extend(broadcast_spec(spec, arg))
    flat_avals = [v.aval for v in closed_jaxpr.jaxpr.invars]
    if len(leaf_specs) != len(flat_avals):
        raise ValueError(
            f"{len(leaf_specs)} spec leaves for {len(flat_avals)} "
            "traced inputs — in_specs must mirror example_args"
        )
    return [
        GlobalLayout(spec_to_dims(s, len(getattr(a, "shape", ()))))
        for s, a in zip(leaf_specs, flat_avals)
    ]


def analyze_program(
    fn: Callable,
    example_args: Sequence,
    *,
    mesh,
    in_specs: Sequence,
    replication_threshold: int = REPLICATION_THRESHOLD_BYTES,
    closed_jaxpr=None,
) -> ShardingFlow:
    """Trace ``fn`` abstractly and run the sharding-flow pass.

    ``in_specs`` is one prefix spec tree per argument (a ``P`` covering
    the whole arg, or a container of prefixes — the same shapes the
    trainers hand to ``shard_map``). ``mesh`` supplies the axis sizes;
    pass ``closed_jaxpr`` to reuse an existing trace."""
    import jax

    if closed_jaxpr is None:
        closed_jaxpr = jax.make_jaxpr(fn)(*example_args)
    mesh_axes = {str(k): int(v) for k, v in dict(mesh.shape).items()}
    in_layouts = _flat_in_layouts(example_args, in_specs, closed_jaxpr)
    return analyze_jaxpr(
        closed_jaxpr, mesh_axes, in_layouts,
        replication_threshold=replication_threshold,
    )


def xla_peak_bytes(fn: Callable, example_args: Sequence) -> int | None:
    """The compile-time cross-check: XLA's own per-device memory figure
    (argument + temp + output) from ``memory_analysis()``, or ``None``
    on backends that don't report one. This is the only layer-3 path
    that compiles anything."""
    try:
        compiled = fn.lower(*example_args).compile()
        mem = compiled.memory_analysis()
    except Exception:
        return None
    if mem is None:
        return None
    total = 0
    for attr in ("argument_size_in_bytes", "temp_size_in_bytes",
                 "output_size_in_bytes"):
        v = getattr(mem, attr, None)
        if isinstance(v, int) and v > 0:
            total += v
    return total or None
