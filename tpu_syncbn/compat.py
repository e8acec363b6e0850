"""Version-compat shims: the few jax/flax APIs this codebase reaches
through one audited door (srclint ``raw_api_bypass``), so a toolchain
move is repaired here and not at every call site. The installation there
is: jax 0.9.0, flax 0.12.3. Arms for versions that are not installed are
removed when the toolchain moves past them (PR 21 removed the pre-VMA
``jax.experimental.shard_map``/``check_rep`` arm and ``HAS_VMA``).

Robustness contract (docs/RESILIENCE.md): a missing optional API selects a
documented fallback path once, at import; it never raises mid-step:

* ``nnx_merge(..., copy=True)`` falls back to plain ``nnx.merge`` (flax
  versions without the kwarg construct fresh Variables already).
"""

from __future__ import annotations

from typing import Any

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with the VMA checker on unless a caller turns it
    off by name."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def axis_size(axis_name):
    """``lax.axis_size`` where available; otherwise the classic
    ``psum(1, axis)`` identity (folded to a static constant at trace
    time — no runtime collective). A tuple/list of axis names yields the
    product of the per-axis sizes — the total replica count of a composed
    layout like ``('data', 'fsdp')`` — and raises the same
    NameError/KeyError as the single-axis form when *any* member axis is
    out of scope (callers probing scope rely on that)."""
    if isinstance(axis_name, (tuple, list)):
        size = 1
        for a in axis_name:
            size = size * axis_size(a)
        return size
    if hasattr(jax.lax, "axis_size"):
        return jax.lax.axis_size(axis_name)
    return jax.lax.psum(1, axis_name)


def vma_of(x) -> frozenset:
    """The VMA (varying axes) set of a traced value."""
    return getattr(jax.typeof(x), "vma", frozenset()) or frozenset()


def nnx_list(items):
    """``nnx.List`` where flax has it; a plain Python list otherwise
    (older nnx registers plain lists as graph nodes, so child modules
    and their params stay visible to split/merge either way)."""
    from flax import nnx

    if hasattr(nnx, "List"):
        return nnx.List(items)
    return list(items)


def nnx_dict(mapping):
    """``nnx.Dict`` where flax has it; a plain dict otherwise (older nnx
    registers plain dicts as graph nodes)."""
    from flax import nnx

    if hasattr(nnx, "Dict"):
        return nnx.Dict(mapping)
    return dict(mapping)


def nnx_data(value):
    """``nnx.data`` (explicit data-attribute annotation on current flax)
    — identity on older flax, which treats container attributes as graph
    data without annotation."""
    from flax import nnx

    if hasattr(nnx, "data"):
        return nnx.data(value)
    return value


_MERGE_HAS_COPY: bool | None = None


def nnx_merge(graphdef, *states, copy: bool = True):
    """``nnx.merge`` forwarding ``copy=`` only where flax supports it
    (the kwarg exists to force fresh trace-local Variables on flax
    versions whose merge aliases the originals; older merges already
    materialize fresh Variables). Support is probed from the signature
    once — NOT by catching TypeError, which would silently retry a merge
    whose *real* failure was elsewhere and reintroduce the aliasing bug
    ``copy=True`` exists to prevent."""
    import inspect

    from flax import nnx

    global _MERGE_HAS_COPY
    if _MERGE_HAS_COPY is None:
        try:
            params = inspect.signature(nnx.merge).parameters
            _MERGE_HAS_COPY = "copy" in params or any(
                p.kind is inspect.Parameter.VAR_KEYWORD
                for p in params.values()
            )
        except (TypeError, ValueError):
            _MERGE_HAS_COPY = True  # unsignaturable: assume modern flax
    if _MERGE_HAS_COPY:
        return nnx.merge(graphdef, *states, copy=copy)
    return nnx.merge(graphdef, *states)


def nnx_to_pure_dict(state) -> Any:
    """``nnx.to_pure_dict`` (module function on current flax, ``State``
    method on older)."""
    from flax import nnx

    if hasattr(nnx, "to_pure_dict"):
        return nnx.to_pure_dict(state)
    return state.to_pure_dict()


def nnx_replace_by_pure_dict(state, pure) -> None:
    """``nnx.replace_by_pure_dict`` (module function on current flax,
    ``State`` method on older). Mutates ``state`` in place."""
    from flax import nnx

    if hasattr(nnx, "replace_by_pure_dict"):
        nnx.replace_by_pure_dict(state, pure)
    else:
        state.replace_by_pure_dict(pure)
