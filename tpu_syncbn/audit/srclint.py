"""Layer 2 of the program auditor: an AST lint enforcing the
repo-specific hazard rules PRs 1–5 learned the hard way. Each rule is a
class of bug that actually bit (or nearly bit) this codebase; the rule
docstrings cite the incident. Every rule has a planted-violation fixture
under ``tests/audit_fixtures/`` proving it can fire — a rule that cannot
fire is dead weight (tests/test_audit_srclint.py enforces this).

Suppression: a source line ending in ``# audit: ok`` suppresses every
rule on that line; ``# audit: ok[rule_id]`` suppresses one rule. Use it
the way the rule catalog (docs/STATIC_ANALYSIS.md) documents — with a
reason in a nearby comment.

The rules themselves are stdlib-only (``ast``): no tracing, no
compilation, no device — fast enough for a pre-commit hook. (The CLI
still imports the package for file discovery, which pulls in jax; use
``lint_file``/``lint_source`` directly to lint in isolation.)
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Callable, Iterable, Sequence

#: Telemetry metric-name schema: dotted lowercase with a subsystem
#: prefix (``serve.latency_s``, ``collectives.psum.bytes``).
METRIC_NAME_RE = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_]+)+$")
#: CounterGroup prefixes are a single schema token (the dot is added
#: when mirroring into the registry).
PREFIX_RE = re.compile(r"^[a-z0-9_]+$")

#: The subsystem vocabulary: the first dotted token of every literal
#: metric name (and every CounterGroup prefix) must come from here.
#: This is what keeps the export/merge/trend tooling's keyspace closed —
#: a typo'd subsystem (``sevre.latency_s``) would otherwise mint a new
#: top-level family that every dashboard and docs table silently lacks.
#: Extending the vocabulary is a deliberate act: add the token here AND
#: a docs/OBSERVABILITY.md table for it. ``obs`` / ``slo`` /
#: ``monitor`` are ISSUE 8's live-monitoring families
#: (``obs.server.*`` / ``obs.alert.*``, ``slo.*``,
#: ``monitor.heartbeat_age_s`` — pinned in obs.server.MONITOR_METRICS);
#: ``numerics`` is ISSUE 13's drift/compression-health family
#: (``obs.numerics`` — docs/OBSERVABILITY.md "Numerics & drift").
#: ``mem`` / ``compile`` are ISSUE 14's memory-and-compile families
#: (``obs.memwatch`` / ``obs.profiling`` — docs/OBSERVABILITY.md
#: "Memory & compile").
#: ``autopilot`` is ISSUE 17's closed-loop controller family
#: (``runtime.autopilot`` — docs/OBSERVABILITY.md "Autopilot").
#: ``planner`` is ISSUE 19's contract-driven layout search family
#: (``parallel.planner`` + ``audit.contract_cache`` —
#: docs/OBSERVABILITY.md "Planner").
#: ``telemetry`` is the registry's own meta family
#: (``telemetry.cardinality_dropped`` — the label-cap overflow tally,
#: docs/OBSERVABILITY.md "Labels & cardinality").
KNOWN_METRIC_PREFIXES = frozenset({
    "audit", "autopilot", "checkpoint", "collectives", "compile",
    "data", "events", "gan", "incident", "loader", "mem", "monitor",
    "numerics", "obs", "pipeline", "planner", "probe", "rendezvous",
    "resilience", "scan", "serve", "slo", "step", "telemetry", "train",
})

#: The closed label-key vocabulary: every literal ``labels={...}`` key
#: in the tree must come from here (docs/OBSERVABILITY.md "Labels &
#: cardinality"). A closed key set is what keeps selectors writable —
#: ``{tenant="a"}`` only works if every producer spells the dimension
#: the same way — and it is the first line of cardinality defense: a
#: new key is a new dimension, added deliberately, here AND in the docs
#: vocabulary table.
LABEL_KEYS = frozenset({
    "tenant", "model", "version", "mode", "family", "device", "knob",
})
LABEL_KEY_RE = re.compile(r"^[a-z][a-z0-9_]*$")

_SUPPRESS_RE = re.compile(r"#\s*audit:\s*ok(?:\[([a-z0-9_,\s]+)\])?")


@dataclasses.dataclass
class Violation:
    """One finding — from either audit layer (srclint rules use real
    file/line positions; jaxpr-layer rules use ``path='<jaxpr>'``)."""

    rule: str
    message: str
    path: str
    line: int
    col: int = 0

    def format(self) -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        return f"{loc}: [{self.rule}] {self.message}"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# AST helpers


def _attach_parents(tree: ast.AST) -> None:
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child._audit_parent = node  # type: ignore[attr-defined]


def _parent(node: ast.AST):
    return getattr(node, "_audit_parent", None)


def _dotted(node: ast.AST) -> str | None:
    """``a.b.c`` for a Name/Attribute chain; None for anything dynamic."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _enclosing_functions(node: ast.AST) -> Iterable[ast.AST]:
    cur = _parent(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield cur
        cur = _parent(cur)


def _in_with_on(node: ast.AST, attr_names: set[str]) -> bool:
    """Is ``node`` lexically inside a ``with self.<lock>:`` block for any
    lock attribute in ``attr_names``?"""
    cur = _parent(node)
    while cur is not None:
        if isinstance(cur, (ast.With, ast.AsyncWith)):
            for item in cur.items:
                d = _dotted(item.context_expr)
                if d is None and isinstance(item.context_expr, ast.Call):
                    d = _dotted(item.context_expr.func)
                if d and d.startswith("self.") and d[5:] in attr_names:
                    return True
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return False
        cur = _parent(cur)
    return False


def _first_str_arg(call: ast.Call) -> tuple[str, ast.AST] | None:
    if call.args and isinstance(call.args[0], ast.Constant) \
            and isinstance(call.args[0].value, str):
        return call.args[0].value, call.args[0]
    return None


# ---------------------------------------------------------------------------
# rule: raw_api_bypass

#: APIs that MUST route through compat.py (PR 1: the package has to
#: import and degrade on the container's jax 0.4.37 / flax 0.10 —
#: calling the new API directly crashes there). Maps dotted pattern →
#: the compat replacement to name in the message.
RAW_APIS: dict[str, str] = {
    "jax.shard_map": "compat.shard_map",
    "jax.experimental.shard_map.shard_map": "compat.shard_map",
    "nnx.merge": "compat.nnx_merge",
    "nnx.List": "compat.nnx_list",
    "nnx.Dict": "compat.nnx_dict",
    "nnx.data": "compat.nnx_data",
    "nnx.to_pure_dict": "compat.nnx_to_pure_dict",
    "nnx.replace_by_pure_dict": "compat.nnx_replace_by_pure_dict",
    "lax.pvary": "collectives.pcast_varying",
    "jax.lax.pvary": "collectives.pcast_varying",
    "lax.pcast": "collectives.pcast_varying",
    "jax.lax.pcast": "collectives.pcast_varying",
    "lax.axis_size": "compat.axis_size",
    "jax.lax.axis_size": "compat.axis_size",
    # not a compat shim but the same discipline (ISSUE 14): the raw
    # profiler is a process singleton with no duration/size bound —
    # obs.profiling owns the bounded, single-flight capture path
    "jax.profiler.start_trace": "obs.profiling.profiler_trace / .capture",
    "jax.profiler.stop_trace": "obs.profiling.profiler_trace / .capture",
}

#: ``from <module> import <name>`` forms of the same bypasses — the
#: repo's dominant form in practice (the PR 6 sweep fixed exactly this
#: in examples/ and benchmarks/). Keyed ``(module, name)``; the dotted
#: equivalent is used for the allowlist and the message.
RAW_IMPORT_FROMS: dict[tuple[str, str], str] = {
    ("jax", "shard_map"): "compat.shard_map",
    ("jax.experimental", "shard_map"): "compat.shard_map",
    ("jax.lax", "pvary"): "collectives.pcast_varying",
    ("jax.lax", "pcast"): "collectives.pcast_varying",
    ("jax.lax", "axis_size"): "compat.axis_size",
    ("flax.nnx", "merge"): "compat.nnx_merge",
    ("jax.profiler", "start_trace"):
        "obs.profiling.profiler_trace / .capture",
    ("jax.profiler", "stop_trace"):
        "obs.profiling.profiler_trace / .capture",
}

#: (file suffix, dotted api) pairs allowed to touch the raw API — the
#: compat shims themselves, and collectives.py as the one documented
#: home of the VMA cast (``pcast_varying``).
RAW_API_ALLOW: tuple[tuple[str, str], ...] = (
    ("tpu_syncbn/compat.py", "*"),
    ("tpu_syncbn/parallel/collectives.py", "lax.pcast"),
    ("tpu_syncbn/parallel/collectives.py", "jax.lax.pcast"),
    # obs/profiling.py is the one documented home of the raw profiler
    # start/stop (bounded capture + the library context manager)
    ("tpu_syncbn/obs/profiling.py", "jax.profiler.start_trace"),
    ("tpu_syncbn/obs/profiling.py", "jax.profiler.stop_trace"),
)


def _raw_api_allowed(path: str, api: str) -> bool:
    norm = path.replace(os.sep, "/")
    for suffix, allowed in RAW_API_ALLOW:
        if norm.endswith(suffix) and allowed in ("*", api):
            return True
    return False


def check_raw_api_bypass(
    tree: ast.AST, path: str, src_lines: Sequence[str]
) -> list[Violation]:
    """``raw_api_bypass``: a current-jax/flax API called directly instead
    of through ``compat.py``. PR 1's whole point: the raw call is an
    ImportError/AttributeError on the baked toolchain; the shim picks a
    documented fallback once at import."""
    out: list[Violation] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module.startswith("jax.experimental.shard_map"):
                if not _raw_api_allowed(
                    path, "jax.experimental.shard_map.shard_map"
                ):
                    out.append(Violation(
                        rule="raw_api_bypass", path=path, line=node.lineno,
                        col=node.col_offset,
                        message="import of jax.experimental.shard_map — "
                                "route through compat.shard_map",
                    ))
                continue
            for alias in node.names:
                repl = RAW_IMPORT_FROMS.get((node.module, alias.name))
                if repl is None:
                    continue
                dotted = f"{node.module}.{alias.name}"
                if _raw_api_allowed(path, dotted):
                    continue
                out.append(Violation(
                    rule="raw_api_bypass", path=path, line=node.lineno,
                    col=node.col_offset,
                    message=f"`from {node.module} import {alias.name}` — "
                            f"route through {repl} (compat gate for the "
                            "baked jax/flax toolchain)",
                ))
            continue
        if not isinstance(node, ast.Attribute):
            continue
        if isinstance(_parent(node), ast.Attribute):
            continue  # only the top of each chain
        dotted = _dotted(node)
        if dotted is None or dotted not in RAW_APIS:
            continue
        if _raw_api_allowed(path, dotted):
            continue
        out.append(Violation(
            rule="raw_api_bypass", path=path, line=node.lineno,
            col=node.col_offset,
            message=f"raw API {dotted} — route through {RAW_APIS[dotted]} "
                    "(compat gate for the baked jax/flax toolchain)",
        ))
    return out


# ---------------------------------------------------------------------------
# rule: host_sync_in_step

#: Function names whose *nested* functions are step bodies / traced
#: closures — the step factories of the stack. A host sync inside one
#: executes at TRACE time (usually an error under jit) or, worse, forces
#: a device sync per step.
STEP_BUILDER_RE = re.compile(
    r"^(_make_step_fn|_build_train_steps?|_build_eval_step|_build_step"
    r"|build_scan_steps|_microbatch_grads|_sharded_fwd|_program|generate)$"
)

#: Call targets that trace their function argument (marking it, and
#: everything nested in it, as device code).
TRACE_ENTRIES = {
    "shard_map", "compat.shard_map", "jax.jit", "jax.checkpoint",
    "jax.remat", "jax.grad", "jax.value_and_grad", "jax.vmap",
    "jax.lax.scan", "lax.scan",
}

#: Host-sync calls that must never appear in traced code: each one
#: either fails at trace time or forces a device→host roundtrip.
HOST_SYNC_DOTTED = {"np.asarray", "np.array", "numpy.asarray",
                    "numpy.array", "jax.device_get"}
HOST_SYNC_ATTRS = {"item", "block_until_ready"}


def _walk_own_body(fdef: ast.AST) -> Iterable[ast.AST]:
    """Every node of ``fdef`` EXCLUDING the subtrees of nested
    function/class definitions (lambdas are descended into — they share
    the enclosing trace context)."""
    stack = list(ast.iter_child_nodes(fdef))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _traced_functions(tree: ast.AST) -> set[ast.AST]:
    """FunctionDefs that end up inside a compiled program: nested in a
    step-builder method, or passed by name to a tracing entry point."""
    traced: set[ast.AST] = set()
    defs_by_name: dict[str, list[ast.AST]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(node.name, []).append(node)
            if any(STEP_BUILDER_RE.match(f.name)
                   for f in _enclosing_functions(node)):
                traced.add(node)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func)
        if dotted not in TRACE_ENTRIES:
            continue
        for arg in node.args:
            if isinstance(arg, ast.Name):
                for fdef in defs_by_name.get(arg.id, ()):
                    traced.add(fdef)
    # close over nesting: anything inside a traced def is traced
    closed: set[ast.AST] = set(traced)
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(f in traced for f in _enclosing_functions(node)):
                closed.add(node)
    return closed


def check_host_sync_in_step(
    tree: ast.AST, path: str, src_lines: Sequence[str]
) -> list[Violation]:
    """``host_sync_in_step``: ``.item()`` / ``np.asarray`` /
    ``.block_until_ready()`` / ``jax.device_get`` inside step-building
    code. Inside a trace these either fail (ConcretizationTypeError) or
    silently pin a per-step host sync — the exact overhead class PR 4
    moved off the hot path."""
    out: list[Violation] = []
    traced = _traced_functions(tree)
    for fdef in traced:
        # shallow walk: nested defs are their own traced entries — a
        # hit inside one must be reported exactly once
        for node in _walk_own_body(fdef):
            if not isinstance(node, ast.Call):
                continue
            dotted = _dotted(node.func)
            hit = None
            if dotted in HOST_SYNC_DOTTED:
                hit = dotted
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in HOST_SYNC_ATTRS:
                hit = f".{node.func.attr}()"
            if hit:
                out.append(Violation(
                    rule="host_sync_in_step", path=path, line=node.lineno,
                    col=node.col_offset,
                    message=f"host-sync call {hit} inside step-building "
                            f"function {fdef.name!r} — this code is traced "
                            "into the compiled program",
                ))
    return out


# ---------------------------------------------------------------------------
# rule: donate_after_use

#: Internal dispatch attributes whose calls consume (donate) the state
#: buffers passed to them — after the call those arrays are invalid.
DONATING_ATTRS = {"_train_step", "_step", "_gen_step"}
#: Factory calls whose result is a donating compiled program.
DONATING_FACTORIES = ("cached_program", "build_scan_steps")


def check_donate_after_use(
    tree: ast.AST, path: str, src_lines: Sequence[str]
) -> list[Violation]:
    """``donate_after_use``: a ``self.<state>`` buffer read after being
    passed to a donating dispatch without being rebound — the PR 4
    ``snapshot_to_host`` hazard class (donated jit invalidates the
    input buffers; a snapshot that merely references them reads garbage
    or crashes). Aliases (``snap = self._param_store``) taken before
    the dispatch are tracked too."""
    out: list[Violation] = []
    for fdef in ast.walk(tree):
        if not isinstance(fdef, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        donating_names: set[str] = set()
        aliases: dict[str, str] = {}  # local name -> self.<attr> expr
        donated: dict[str, int] = {}  # dotted expr -> donating lineno
        statements = list(_statements_in_order(fdef))
        for stmt in statements:
            # a compound statement's own expressions only: its nested
            # statements come up on their own, in order (a dispatch
            # inside a ``with`` is rebound by the assignment that
            # makes it, not donated by the ``with``)
            nodes = list(_walk_own(stmt))
            calls = [n for n in nodes if isinstance(n, ast.Call)]
            # 1. reads of already-donated buffers in this statement
            for node in nodes:
                dotted = None
                if isinstance(node, ast.Attribute) \
                        and isinstance(node.ctx, ast.Load):
                    dotted = _dotted(node)
                elif isinstance(node, ast.Name) \
                        and isinstance(node.ctx, ast.Load):
                    dotted = aliases.get(node.id)
                if dotted and dotted in donated:
                    out.append(Violation(
                        rule="donate_after_use", path=path,
                        line=node.lineno, col=node.col_offset,
                        message=f"{dotted} read after being donated to a "
                                f"compiled dispatch on line "
                                f"{donated[dotted]} — copy before donation "
                                "(utils.checkpoint.snapshot_to_host) or "
                                "rebind from the dispatch result",
                    ))
            # 2. donations made by this statement
            for call in calls:
                if not _is_donating_call(call, donating_names):
                    continue
                for arg in call.args:
                    d = _dotted(arg) if isinstance(arg, ast.Attribute) \
                        else aliases.get(arg.id) \
                        if isinstance(arg, ast.Name) else None
                    if d and d.startswith("self."):
                        donated[d] = call.lineno
            # 3. rebinds clear the donated/alias state
            for target_expr in _assigned_exprs(stmt):
                donated.pop(target_expr, None)
                for alias, ref in list(aliases.items()):
                    if ref == target_expr:
                        aliases.pop(alias)
            # 4. track new aliases and donating-factory bindings
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name):
                name = stmt.targets[0].id
                val = stmt.value
                vd = _dotted(val)
                if vd and vd.startswith("self."):
                    if vd[5:].split(".")[0] in DONATING_ATTRS:
                        donating_names.add(name)
                    else:
                        aliases[name] = vd
                elif isinstance(val, ast.Call):
                    fd = _dotted(val.func) or ""
                    if fd.split(".")[-1] in DONATING_FACTORIES:
                        donating_names.add(name)
                    else:
                        aliases.pop(name, None)
                        donating_names.discard(name)
                else:
                    aliases.pop(name, None)
                    donating_names.discard(name)
    return out


def _is_donating_call(call: ast.Call, donating_names: set[str]) -> bool:
    if isinstance(call.func, ast.Attribute):
        d = _dotted(call.func)
        return bool(d and d.startswith("self.")
                    and call.func.attr in DONATING_ATTRS)
    if isinstance(call.func, ast.Name):
        return call.func.id in donating_names
    return False


def _statements_in_order(fdef: ast.AST) -> Iterable[ast.stmt]:
    """The function's statements in source order, recursing into control
    flow but NOT into nested function definitions."""
    def rec(body):
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            yield stmt
            for field in ("body", "orelse", "finalbody"):
                yield from rec(getattr(stmt, field, []) or [])
            for handler in getattr(stmt, "handlers", []) or []:
                yield from rec(handler.body)
    yield from rec(fdef.body)


def _walk_own(stmt: ast.stmt) -> Iterable[ast.AST]:
    """``ast.walk`` over ``stmt`` without the statements nested in it
    (the bodies ``_statements_in_order`` yields separately)."""
    todo: list[ast.AST] = [stmt]
    while todo:
        node = todo.pop()
        yield node
        todo.extend(child for child in ast.iter_child_nodes(node)
                    if not isinstance(child, (ast.stmt, ast.ExceptHandler)))


def _assigned_exprs(stmt: ast.stmt) -> list[str]:
    out: list[str] = []
    targets: list[ast.AST] = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        targets = [stmt.target]
    flat: list[ast.AST] = []
    while targets:
        t = targets.pop()
        if isinstance(t, (ast.Tuple, ast.List)):
            targets.extend(t.elts)
        else:
            flat.append(t)
    for t in flat:
        d = _dotted(t)
        if d:
            out.append(d)
    return out


# ---------------------------------------------------------------------------
# rule: unlocked_shared_state

#: Methods of a lock-owning class that mutate a shared container in
#: place must do it under the lock. These are the in-place mutators.
CONTAINER_MUTATORS = {
    "append", "extend", "insert", "remove", "pop", "clear", "update",
    "add", "discard", "popitem", "setdefault", "appendleft", "popleft",
}
_LOCK_FACTORIES = {"Lock", "RLock", "Condition"}


def check_unlocked_shared_state(
    tree: ast.AST, path: str, src_lines: Sequence[str]
) -> list[Violation]:
    """``unlocked_shared_state``: in a class that owns a lock (it
    created ``threading.Lock/RLock/Condition`` in ``__init__``), an
    in-place mutation of a shared container attribute — or a
    ``+=``/``-=`` on a shared numeric counter (non-atomic
    read-modify-write, the AsyncCheckpointer ``_pending`` discipline) —
    outside a ``with self.<lock>:`` block. The threaded modules
    (serve/batcher.py, AsyncCheckpointer, loader staging) live and die
    by this discipline — a torn dict update under a watchdog thread is
    a heisenbug, not a test failure."""
    out: list[Violation] = []
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        init = next((n for n in cls.body
                     if isinstance(n, ast.FunctionDef)
                     and n.name == "__init__"), None)
        if init is None:
            continue
        lock_attrs: set[str] = set()
        container_attrs: set[str] = set()
        counter_attrs: set[str] = set()
        for stmt in ast.walk(init):
            if isinstance(stmt, ast.Assign):
                targets, value = stmt.targets, stmt.value
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                targets, value = [stmt.target], stmt.value
            else:
                continue
            for target in targets:
                d = _dotted(target)
                if not d or not d.startswith("self.") or "." in d[5:]:
                    continue
                attr = d[5:]
                if _creates_lock(value):
                    lock_attrs.add(attr)
                elif _creates_container(value):
                    container_attrs.add(attr)
                elif isinstance(value, ast.Constant) \
                        and isinstance(value.value, (int, float)) \
                        and not isinstance(value.value, bool):
                    counter_attrs.add(attr)
        if not lock_attrs or not (container_attrs or counter_attrs):
            continue
        for method in cls.body:
            if not isinstance(method, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)) \
                    or method.name == "__init__":
                continue
            for node in ast.walk(method):
                attr = _mutated_container_attr(node, container_attrs)
                if attr is None and isinstance(node, ast.AugAssign):
                    d = _dotted(node.target)
                    if d and d.startswith("self.") \
                            and d[5:] in counter_attrs:
                        attr = d[5:]
                if attr is None:
                    continue
                if _in_with_on(node, lock_attrs):
                    continue
                out.append(Violation(
                    rule="unlocked_shared_state", path=path,
                    line=node.lineno, col=node.col_offset,
                    message=f"self.{attr} mutated outside "
                            f"`with self.<lock>:` in {cls.name}."
                            f"{method.name} — this class owns "
                            f"{sorted(lock_attrs)} precisely because its "
                            "state is shared across threads",
                ))
    return out


def _creates_lock(value: ast.AST) -> bool:
    if not isinstance(value, ast.Call):
        return False
    d = _dotted(value.func) or ""
    return d.split(".")[-1] in _LOCK_FACTORIES


def _creates_container(value: ast.AST) -> bool:
    if isinstance(value, (ast.Dict, ast.List, ast.Set, ast.DictComp,
                          ast.ListComp, ast.SetComp)):
        return True
    if isinstance(value, ast.Call):
        d = _dotted(value.func) or ""
        return d.split(".")[-1] in {"dict", "list", "set", "deque",
                                    "defaultdict", "OrderedDict"}
    if isinstance(value, ast.BinOp):  # e.g. [0] * (n + 1)
        return _creates_container(value.left) \
            or _creates_container(value.right)
    return False


def _mutated_container_attr(
    node: ast.AST, container_attrs: set[str]
) -> str | None:
    def attr_of(expr: ast.AST) -> str | None:
        d = _dotted(expr)
        if d and d.startswith("self.") and d[5:] in container_attrs:
            return d[5:]
        return None

    if isinstance(node, (ast.Assign, ast.AugAssign)):
        targets = node.targets if isinstance(node, ast.Assign) \
            else [node.target]
        for t in targets:
            if isinstance(t, ast.Subscript):
                hit = attr_of(t.value)
                if hit:
                    return hit
    elif isinstance(node, ast.Delete):
        for t in node.targets:
            if isinstance(t, ast.Subscript):
                hit = attr_of(t.value)
                if hit:
                    return hit
    elif isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
        if node.func.attr in CONTAINER_MUTATORS:
            return attr_of(node.func.value)
    return None


# ---------------------------------------------------------------------------
# rule: telemetry_name_schema

_TELEMETRY_HELPERS = {"count", "observe", "set_gauge", "timed"}
_REGISTRY_METHODS = {"counter", "gauge", "histogram"}


def _is_label_sink(attr: str, base: str) -> bool:
    """Is this call a telemetry sink whose ``labels={...}`` kwarg mints
    registry series? Module helpers (``telemetry.count(...)`` and
    friends, plus ``inc_gauge``), Registry instrument getters, and
    ``CounterGroup.bump``."""
    if (attr in _TELEMETRY_HELPERS or attr == "inc_gauge") \
            and base.endswith("telemetry"):
        return True
    if attr in _REGISTRY_METHODS and (
        "registry" in base.lower() or base.endswith("REGISTRY")
    ):
        return True
    return attr == "bump"


def check_telemetry_name_schema(
    tree: ast.AST, path: str, src_lines: Sequence[str]
) -> list[Violation]:
    """``telemetry_name_schema``: literal metric names must be dotted
    lowercase with a subsystem prefix (``serve.latency_s``) and
    ``CounterGroup`` prefixes a single token — the export/merge
    contract (docs/OBSERVABILITY.md) keys on it."""
    out: list[Violation] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        func_name = func.id if isinstance(func, ast.Name) else (
            func.attr if isinstance(func, ast.Attribute) else None
        )
        if func_name == "CounterGroup":
            for kw in node.keywords:
                if kw.arg == "prefix" and isinstance(kw.value, ast.Constant) \
                        and isinstance(kw.value.value, str):
                    if not PREFIX_RE.match(kw.value.value):
                        out.append(Violation(
                            rule="telemetry_name_schema", path=path,
                            line=kw.value.lineno, col=kw.value.col_offset,
                            message=f"CounterGroup prefix "
                                    f"{kw.value.value!r} must match "
                                    f"{PREFIX_RE.pattern}",
                        ))
                    elif kw.value.value not in KNOWN_METRIC_PREFIXES:
                        out.append(Violation(
                            rule="telemetry_name_schema", path=path,
                            line=kw.value.lineno, col=kw.value.col_offset,
                            message=f"CounterGroup prefix "
                                    f"{kw.value.value!r} is not a known "
                                    "subsystem token — typo, or extend "
                                    "KNOWN_METRIC_PREFIXES (and the docs "
                                    "table) deliberately",
                        ))
            continue
        if not isinstance(func, ast.Attribute):
            continue
        base = _dotted(func.value) or ""
        # labeled series: literal label keys must come from the closed
        # vocabulary — a producer minting a private key breaks every
        # selector that spells the dimension the standard way
        if _is_label_sink(func.attr, base):
            for kw in node.keywords:
                if kw.arg != "labels" or not isinstance(kw.value, ast.Dict):
                    continue
                for k in kw.value.keys:
                    if not isinstance(k, ast.Constant) \
                            or not isinstance(k.value, str):
                        continue
                    if not LABEL_KEY_RE.match(k.value):
                        out.append(Violation(
                            rule="telemetry_name_schema", path=path,
                            line=k.lineno, col=k.col_offset,
                            message=f"label key {k.value!r} does not "
                                    f"match {LABEL_KEY_RE.pattern}",
                        ))
                    elif k.value not in LABEL_KEYS:
                        out.append(Violation(
                            rule="telemetry_name_schema", path=path,
                            line=k.lineno, col=k.col_offset,
                            message=f"label key {k.value!r} is not in "
                                    "the closed label vocabulary "
                                    f"{sorted(LABEL_KEYS)} — a new "
                                    "dimension is added deliberately: "
                                    "LABEL_KEYS AND the docs vocabulary "
                                    "table",
                        ))
        checked = None
        if func.attr in _TELEMETRY_HELPERS and base.endswith("telemetry"):
            checked = _first_str_arg(node)
        elif func.attr in _REGISTRY_METHODS and (
            "registry" in base.lower() or base.endswith("REGISTRY")
        ):
            checked = _first_str_arg(node)
        if checked is None:
            continue
        name, lit = checked
        if not METRIC_NAME_RE.match(name):
            out.append(Violation(
                rule="telemetry_name_schema", path=path, line=lit.lineno,
                col=lit.col_offset,
                message=f"telemetry name {name!r} does not match the "
                        f"schema {METRIC_NAME_RE.pattern} "
                        "(subsystem-dotted lowercase)",
            ))
        elif name.split(".", 1)[0] not in KNOWN_METRIC_PREFIXES:
            out.append(Violation(
                rule="telemetry_name_schema", path=path, line=lit.lineno,
                col=lit.col_offset,
                message=f"telemetry name {name!r} has unknown subsystem "
                        f"prefix {name.split('.', 1)[0]!r} — typo, or "
                        "extend KNOWN_METRIC_PREFIXES (and the docs "
                        "table) deliberately",
            ))
    return out


# ---------------------------------------------------------------------------
# rule: unbounded_label_value

#: String literals shaped like per-request identity: long hex runs,
#: uuid prefixes, long digit runs. A label value like this is one
#: series per request — the cardinality cap will eat it, but the code
#: is wrong before the runtime has to defend itself.
_REQUEST_ID_LITERAL_RE = re.compile(
    r"(?i)(?:[0-9a-f]{12,}|[0-9a-f]{8}-[0-9a-f]{4}-[0-9a-f]{4}|\d{6,})"
)

#: Call names whose result is per-call-unique (or arbitrarily wide)
#: when fed to a label value.
_UNBOUNDED_VALUE_CALLS = {"str", "format", "hex", "uuid1", "uuid4"}


def check_unbounded_label_value(
    tree: ast.AST, path: str, src_lines: Sequence[str]
) -> list[Violation]:
    """``unbounded_label_value``: a label value built per-request — an
    f-string, string concatenation/formatting, a ``str()``/``.format()``
    conversion, or a literal shaped like a request id. Labels are
    *dimensions* (tenant, model, mode — a small closed set of values);
    per-request identity belongs in trace spans and flight-recorder
    rings, not the registry keyspace, where each distinct value mints a
    series that lives forever. The runtime cardinality cap bounds the
    damage (overflow collapses into ``other``); this rule catches the
    mistake at review time instead."""
    out: list[Violation] = []

    def flag(node: ast.AST, key: str, what: str) -> None:
        out.append(Violation(
            rule="unbounded_label_value", path=path,
            line=node.lineno, col=node.col_offset,
            message=f"label {key!r} gets {what} as its value — label "
                    "values must be a small closed set (per-request "
                    "identity belongs in traces/rings, not the registry "
                    "keyspace; overflow collapses into 'other')",
        ))

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) \
                or not isinstance(node.func, ast.Attribute):
            continue
        base = _dotted(node.func.value) or ""
        if not _is_label_sink(node.func.attr, base):
            continue
        for kw in node.keywords:
            if kw.arg != "labels" or not isinstance(kw.value, ast.Dict):
                continue
            for k, v in zip(kw.value.keys, kw.value.values):
                key = (k.value if isinstance(k, ast.Constant)
                       and isinstance(k.value, str) else "?")
                if isinstance(v, ast.JoinedStr):
                    flag(v, key, "an f-string")
                elif isinstance(v, ast.BinOp):
                    flag(v, key, "string concatenation/%-formatting")
                elif isinstance(v, ast.Call):
                    cf = v.func
                    cname = cf.id if isinstance(cf, ast.Name) else (
                        cf.attr if isinstance(cf, ast.Attribute) else ""
                    )
                    if cname in _UNBOUNDED_VALUE_CALLS:
                        flag(v, key, f"a {cname}() result")
                elif isinstance(v, ast.Constant) \
                        and isinstance(v.value, str) \
                        and _REQUEST_ID_LITERAL_RE.search(v.value):
                    flag(v, key, "a request-id-shaped literal")
    return out


# ---------------------------------------------------------------------------
# rule: unpaired_trace_span

_SPAN_MAKERS_ATTR = {"span", "timed", "timed_span"}


def check_unpaired_trace_span(
    tree: ast.AST, path: str, src_lines: Sequence[str]
) -> list[Violation]:
    """``unpaired_trace_span``: a span/timer context manager created and
    discarded (``tracer.span("x")`` as a bare statement) — the span is
    never entered, so it never closes, and the trace silently loses the
    region. Spans must be ``with``-entered (or returned/stored for a
    caller's ``with``)."""
    out: list[Violation] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Expr) or not isinstance(node.value,
                                                            ast.Call):
            continue
        call = node.value
        name = None
        if isinstance(call.func, ast.Attribute) \
                and call.func.attr in _SPAN_MAKERS_ATTR:
            base = _dotted(call.func.value) or ""
            # tracer.span / tracing.span / telemetry.timed /
            # obs_stepstats.timed_span — not arbitrary .timed attrs
            if call.func.attr == "timed" and not base.endswith("telemetry"):
                continue
            name = _dotted(call.func)
        elif isinstance(call.func, ast.Name) \
                and call.func.id == "timed_span":
            name = "timed_span"
        if name is None:
            continue
        out.append(Violation(
            rule="unpaired_trace_span", path=path, line=node.lineno,
            col=node.col_offset,
            message=f"{name}(...) creates a context manager that is "
                    "immediately discarded — the span is never "
                    "entered/closed; use `with {0}(...):`".format(name),
        ))
    return out


# ---------------------------------------------------------------------------
# rule: wallclock_duration

def _is_wallclock_call(node: ast.AST) -> bool:
    """``time.time()`` in either spelling (``import time`` /
    ``from time import time``)."""
    if not isinstance(node, ast.Call):
        return False
    d = _dotted(node.func)
    return d == "time.time" or (
        isinstance(node.func, ast.Name) and node.func.id == "time"
    )


def check_wallclock_duration(
    tree: ast.AST, path: str, src_lines: Sequence[str]
) -> list[Violation]:
    """``wallclock_duration``: a duration computed by subtracting
    ``time.time()`` readings. Wall clock steps and slews under NTP (and
    jumps across suspend), so a "duration" from it can be negative or
    minutes off — harmless in a log line's timestamp, catastrophic in a
    deadline/watchdog/rate computation (the alert engine in ``obs.slo``
    and every rate window in ``obs.timeseries`` key off elapsed time).
    Durations must come from ``time.monotonic()`` /
    ``time.perf_counter()``; ``time.time()`` is for *timestamps* only
    (never subtracted).

    Detected forms: a ``-`` expression with a ``time.time()`` call on
    either side, and subtraction of names/attributes previously bound
    from ``time.time()`` in the same function (``t0 = time.time(); ...;
    elapsed = time.time() - t0`` — the classic shape)."""
    out: list[Violation] = []

    def scan(scope_body: Iterable[ast.AST]) -> None:
        nodes = list(scope_body)
        # pass 1: names/attrs bound from time.time() anywhere in the
        # scope (walk order is not source order; binding-before-use is
        # over-approximated, which for a lint errs the right way)
        wall_names: set[str] = set()
        for node in nodes:
            if isinstance(node, ast.Assign) and _is_wallclock_call(node.value):
                for t in node.targets:
                    d = _dotted(t)
                    if d:
                        wall_names.add(d)
            elif isinstance(node, ast.AnnAssign) and node.value is not None \
                    and _is_wallclock_call(node.value):
                d = _dotted(node.target)
                if d:
                    wall_names.add(d)
        # pass 2: subtractions touching a wall-clock reading
        for node in nodes:
            if not (isinstance(node, ast.BinOp)
                    and isinstance(node.op, ast.Sub)):
                continue
            sides = (node.left, node.right)
            hit = any(_is_wallclock_call(s) for s in sides) or any(
                (d := _dotted(s)) and d in wall_names for s in sides
            )
            if hit:
                out.append(Violation(
                    rule="wallclock_duration", path=path,
                    line=node.lineno, col=node.col_offset,
                    message="duration computed from time.time() — wall "
                            "clock steps/slews under NTP; use "
                            "time.monotonic() or time.perf_counter() "
                            "for elapsed time (time.time() is for "
                            "timestamps only)",
                ))

    # one scope per function (bindings don't leak across defs), plus the
    # module top level
    for fdef in ast.walk(tree):
        if isinstance(fdef, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scan(_walk_own_body(fdef))
    module_nodes = [
        n for n in ast.walk(tree)
        if not any(True for _ in _enclosing_functions(n))
    ]
    scan(module_nodes)
    return out


# ---------------------------------------------------------------------------
# rule: unbounded_blocking

def _constructs_thread(scope: ast.AST) -> bool:
    """Does this class/function body construct a ``threading.Thread``
    anywhere? Those are the scopes whose blocking calls can deadlock a
    whole subsystem instead of one caller."""
    for node in ast.walk(scope):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func) or ""
        if d == "threading.Thread" or d == "Thread" \
                or d.endswith(".Thread"):
            return True
    return False


def _has_timeout(call: ast.Call) -> bool:
    return any(kw.arg == "timeout" for kw in call.keywords)


def check_unbounded_blocking(
    tree: ast.AST, path: str, src_lines: Sequence[str]
) -> list[Violation]:
    """``unbounded_blocking``: a blocking queue ``get()``/``put(item)``
    or thread ``join()`` with no timeout, inside a thread-owning scope
    (a class or function that constructs ``threading.Thread``). The
    incident class: the serving batcher's ``close()`` joined its
    collector with a caller timeout but never checked ``is_alive()``
    after — a wedged engine masqueraded as a clean shutdown — and any
    no-timeout ``get``/``put``/``join`` in the same position blocks
    *forever* when the peer thread has died (no error, no log, just a
    stuck subsystem). Bound the wait and handle expiry, or suppress
    with a comment explaining why the peer provably always answers
    (e.g. a sentinel protocol that enqueues from a ``finally``).

    Detected forms (timeouts make each one clean): ``x.get()`` with no
    arguments, ``x.put(item)`` with a single argument, and ``x.join()``
    with no arguments — the exact spellings whose stdlib semantics are
    "wait forever". ``get_nowait``/``put_nowait``/positional timeouts
    are fine; ``dict.get(k)``/``str.join(xs)``/``os.path.join(...)``
    all carry arguments, so they never match."""
    out: list[Violation] = []
    scopes = [
        node for node in ast.walk(tree)
        if isinstance(node, (ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef))
        and _constructs_thread(node)
    ]
    seen: set[int] = set()
    for scope in scopes:
        for node in ast.walk(scope):
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            func = node.func
            if not isinstance(func, ast.Attribute):
                continue
            attr = func.attr
            if _has_timeout(node):
                continue
            hit = None
            if attr == "get" and not node.args and not node.keywords:
                hit = ("queue-style .get() with no timeout blocks "
                       "forever if the producer thread died")
            elif attr == "put" and len(node.args) == 1 \
                    and not node.keywords:
                hit = ("bounded-queue .put(item) with no timeout blocks "
                       "forever if the consumer thread died")
            elif attr == "join" and not node.args and not node.keywords:
                hit = (".join() with no timeout blocks forever if the "
                       "thread is wedged — bound it and check "
                       "is_alive() after")
            if hit is None:
                continue
            seen.add(id(node))
            out.append(Violation(
                rule="unbounded_blocking", path=path,
                line=node.lineno, col=node.col_offset,
                message=f"{_dotted(func) or attr}: {hit}",
            ))
    return out


# ---------------------------------------------------------------------------
# rule: hardcoded_mesh_axis

#: Axis-name literals the rule polices (pre-work for the ROADMAP item-1
#: SpecLayout: a mesh refactor can only rename/compose axes mechanically
#: if no call site spells its own). The canonical constants live in
#: tpu_syncbn/mesh_axes.py — the ONE module allowed to contain these.
MESH_AXIS_LITERALS = frozenset({"data", "model", "fsdp"})

#: Call targets whose string arguments are mesh-axis names: sharding
#: constructors and the named-axis collective surface.
_AXIS_CALL_NAMES = frozenset({
    "PartitionSpec", "P", "Mesh", "AbstractMesh", "NamedSharding",
    "make_mesh",
    "psum", "pmean", "pmin", "pmax", "all_gather", "all_to_all",
    "reduce_scatter", "psum_scatter", "ppermute", "pgather",
    "axis_index", "axis_size", "pcast_varying", "broadcast",
})

#: Keyword names that carry axis names in any call (shard_map specs are
#: P(...) calls and covered above; these catch axis_name="data" forms).
_AXIS_KWARGS = frozenset({"axis_name", "axis_names", "axis"})

#: File suffixes allowed to contain the literals: the constants module
#: itself.
_MESH_AXIS_ALLOW = ("tpu_syncbn/mesh_axes.py",)


def _axis_literals_under(node: ast.AST) -> Iterable[ast.Constant]:
    """String constants in the policed set, looking through tuples/lists
    (``Mesh(devs, ("data",))`` / ``axis_names=["data"]``)."""
    stack = [node]
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.Tuple, ast.List)):
            stack.extend(n.elts)
        elif isinstance(n, ast.Constant) and isinstance(n.value, str) \
                and n.value in MESH_AXIS_LITERALS:
            yield n


def check_hardcoded_mesh_axis(
    tree: ast.AST, path: str, src_lines: Sequence[str]
) -> list[Violation]:
    """``hardcoded_mesh_axis``: a mesh-axis name (``"data"`` /
    ``"model"`` / ``"fsdp"``) spelled as a string literal in an
    axis-naming position — a sharding/mesh constructor argument, a
    collective's axis argument, an ``axis_name=`` keyword or default, or
    an ``*_AXIS`` constant assignment — anywhere outside
    ``tpu_syncbn/mesh_axes.py``. Import the constant instead: the
    item-1 SpecLayout refactor renames/composes axes centrally, and a
    private literal is the coupling that breaks it silently."""
    norm = path.replace(os.sep, "/")
    if any(norm.endswith(suffix) for suffix in _MESH_AXIS_ALLOW):
        return []
    out: list[Violation] = []

    def hit(lit: ast.Constant, where: str) -> None:
        out.append(Violation(
            rule="hardcoded_mesh_axis", path=path, line=lit.lineno,
            col=lit.col_offset,
            message=f"mesh-axis literal {lit.value!r} {where} — import "
                    "the constant from tpu_syncbn.mesh_axes (the one "
                    "module allowed to spell axis names)",
        ))

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            func = node.func
            fname = func.id if isinstance(func, ast.Name) else (
                func.attr if isinstance(func, ast.Attribute) else None
            )
            if fname in _AXIS_CALL_NAMES:
                for arg in node.args:
                    for lit in _axis_literals_under(arg):
                        hit(lit, f"as a {fname}(...) argument")
            for kw in node.keywords:
                if kw.arg in _AXIS_KWARGS:
                    for lit in _axis_literals_under(kw.value):
                        hit(lit, f"as the {kw.arg}= keyword")
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # defaults align with the TAIL of posonly+positional args
            pos = list(node.args.posonlyargs) + list(node.args.args)
            pairs = list(zip(
                pos[len(pos) - len(node.args.defaults):],
                node.args.defaults,
            )) + list(zip(node.args.kwonlyargs, node.args.kw_defaults))
            for arg, default in pairs:
                if arg.arg in _AXIS_KWARGS and default is not None:
                    for lit in _axis_literals_under(default):
                        hit(lit, f"as the default of {arg.arg!r}")
        elif isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            if any(isinstance(t, ast.Name) and t.id.endswith("_AXIS")
                   for t in targets) and node.value is not None:
                for lit in _axis_literals_under(node.value):
                    hit(lit, "bound to an *_AXIS constant outside the "
                             "constants module")
    return out


# ---------------------------------------------------------------------------
# rule: private_mesh_plumbing

#: Sharding-constructor call targets the rule polices. Annotations
#: (``x: NamedSharding``) and isinstance checks are fine — the hazard
#: is CONSTRUCTING one, which births a private mesh/spec universe.
_MESH_CTOR_NAMES = frozenset({"Mesh", "AbstractMesh", "NamedSharding"})

#: File suffixes allowed to construct them: the layout layer itself.
#: ``compat.py`` (version-portable shard_map shims), ``parallel/
#: layout.py`` (SpecLayout — the ONE object that owns mesh+specs),
#: ``runtime/distributed.py`` (``make_mesh``, the device-enumeration
#: factory SpecLayout builds on) and the axis-constants module.
_PRIVATE_MESH_ALLOW = (
    "tpu_syncbn/compat.py",
    "tpu_syncbn/parallel/layout.py",
    "tpu_syncbn/runtime/distributed.py",
    "tpu_syncbn/mesh_axes.py",
)


def check_private_mesh_plumbing(
    tree: ast.AST, path: str, src_lines: Sequence[str]
) -> list[Violation]:
    """``private_mesh_plumbing``: a ``Mesh`` / ``AbstractMesh`` /
    ``NamedSharding`` constructed outside the layout layer.

    ISSUE 20's composition contract: trainers, engines and strategy
    modules CONSUME a :class:`tpu_syncbn.parallel.SpecLayout` (or the
    ``runtime.distributed.make_mesh`` factory it builds on) instead of
    assembling their own mesh and shardings. A private ``Mesh(...)`` or
    ``NamedSharding(...)`` is exactly the siloing that made DP, ZeRO,
    TP and pipeline four incompatible programs: each module's axes and
    specs live in its own universe, so nothing composes on one mesh.
    Route through ``layout.sharding(spec)`` / the SpecLayout presets;
    the layout carries the mesh, the batch spec, the param rules and
    the derived reduce axes as one object."""
    norm = path.replace(os.sep, "/")
    if any(norm.endswith(suffix) for suffix in _PRIVATE_MESH_ALLOW):
        return []
    out: list[Violation] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        fname = func.id if isinstance(func, ast.Name) else (
            func.attr if isinstance(func, ast.Attribute) else None
        )
        if fname in _MESH_CTOR_NAMES:
            out.append(Violation(
                rule="private_mesh_plumbing", path=path,
                line=node.lineno, col=node.col_offset,
                message=f"{fname}(...) constructed outside the layout "
                        "layer — consume a parallel.SpecLayout "
                        "(layout.sharding(spec), the presets, or "
                        "runtime.distributed.make_mesh); a private "
                        "mesh is the siloing that keeps DP/FSDP/TP/"
                        "pipe from composing into one program",
            ))
    return out


# ---------------------------------------------------------------------------
# rule: lossy_default_mode

#: Parameter names that carry a wire-compression mode anywhere in the
#: stack (``collectives.compressed_*``, the trainers' ``compress=``,
#: SyncBN's ``stats_compress=``).
_LOSSY_MODE_PARAMS = frozenset({
    "mode", "compress", "stats_compress", "compress_stats",
    "grad_compression",
})
#: The lossy wire dtypes. ``"none"``/``None``/``"fp32"`` defaults are
#: clean; these as a DEFAULT are the hazard.
_LOSSY_MODE_LITERALS = frozenset({"bf16", "int8"})


def check_lossy_default_mode(
    tree: ast.AST, path: str, src_lines: Sequence[str]
) -> list[Violation]:
    """``lossy_default_mode``: a compression-mode parameter whose
    *default* value is a lossy wire dtype (``"bf16"``/``"int8"``).

    ISSUE 12's safety contract: lossy collectives are opt-in at every
    call site — the divergence guard's pmin/finiteness consensus and
    SyncBN's moment/count reductions must never ride a quantized wire
    because a caller forgot to pass a flag. A lossy default IS that
    silent routing: every existing caller changes numerics without a
    diff at the call site. Defaults must stay ``"none"`` (or ``None``);
    lossy modes are passed explicitly. The companion contract invariant
    (``contract.guard_stays_fp32``) pins the same property in the traced
    programs."""
    out: list[Violation] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        pos = list(node.args.posonlyargs) + list(node.args.args)
        pairs = list(zip(
            pos[len(pos) - len(node.args.defaults):], node.args.defaults,
        )) + list(zip(node.args.kwonlyargs, node.args.kw_defaults))
        for arg, default in pairs:
            if (
                arg.arg in _LOSSY_MODE_PARAMS
                and isinstance(default, ast.Constant)
                and default.value in _LOSSY_MODE_LITERALS
            ):
                out.append(Violation(
                    rule="lossy_default_mode", path=path,
                    line=default.lineno, col=default.col_offset,
                    message=f"parameter {arg.arg!r} of {node.name!r} "
                            f"defaults to lossy mode "
                            f"{default.value!r} — wire compression must "
                            "be explicit opt-in (default 'none'); a "
                            "lossy default silently re-routes every "
                            "caller, including guard/stat collectives",
                ))
    return out


# ---------------------------------------------------------------------------
# driver

RULES: dict[str, Callable] = {
    "raw_api_bypass": check_raw_api_bypass,
    "host_sync_in_step": check_host_sync_in_step,
    "donate_after_use": check_donate_after_use,
    "unlocked_shared_state": check_unlocked_shared_state,
    "telemetry_name_schema": check_telemetry_name_schema,
    "unbounded_label_value": check_unbounded_label_value,
    "unpaired_trace_span": check_unpaired_trace_span,
    "wallclock_duration": check_wallclock_duration,
    "unbounded_blocking": check_unbounded_blocking,
    "hardcoded_mesh_axis": check_hardcoded_mesh_axis,
    "private_mesh_plumbing": check_private_mesh_plumbing,
    "lossy_default_mode": check_lossy_default_mode,
}


def _suppressed(src_lines: Sequence[str], v: Violation) -> bool:
    if not v.line or v.line > len(src_lines):
        return False
    m = _SUPPRESS_RE.search(src_lines[v.line - 1])
    if not m:
        return False
    rules = m.group(1)
    if rules is None:
        return True
    return v.rule in {r.strip() for r in rules.split(",")}


def lint_file(path: str, *, rules: Sequence[str] | None = None) -> list[Violation]:
    with open(path, encoding="utf-8") as f:
        src = f.read()
    return lint_source(src, path, rules=rules)


def lint_source(
    src: str, path: str, *, rules: Sequence[str] | None = None
) -> list[Violation]:
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [Violation(rule="parse_error", path=path,
                          line=e.lineno or 0,
                          message=f"file does not parse: {e.msg}")]
    _attach_parents(tree)
    src_lines = src.splitlines()
    out: list[Violation] = []
    for rule_id in (rules if rules is not None else RULES):
        for v in RULES[rule_id](tree, path, src_lines):
            if not _suppressed(src_lines, v):
                out.append(v)
    out.sort(key=lambda v: (v.path, v.line, v.col, v.rule))
    return out


def package_files(pkg_root: str | None = None) -> list[str]:
    """Every ``.py`` file of the installed ``tpu_syncbn`` package (or an
    explicit root), sorted for deterministic output."""
    if pkg_root is None:
        import tpu_syncbn

        pkg_root = os.path.dirname(os.path.abspath(tpu_syncbn.__file__))
    files: list[str] = []
    for dirpath, dirnames, filenames in os.walk(pkg_root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                files.append(os.path.join(dirpath, fn))
    return sorted(files)


def lint_package(
    pkg_root: str | None = None, *, rules: Sequence[str] | None = None
) -> list[Violation]:
    out: list[Violation] = []
    for path in package_files(pkg_root):
        out.extend(lint_file(path, rules=rules))
    return out
