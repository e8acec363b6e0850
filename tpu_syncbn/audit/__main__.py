"""CLI driver: ``python -m tpu_syncbn.audit [--strict] [--json]
[--shardings] [--mem-budget N] [--changed-only REF]``, plus the
``plan`` subcommand (``python -m tpu_syncbn.audit plan``): the
contract-driven parallelism planner's ranked layout table — predicted
step time per candidate, decomposed into compute/collective/bubble/
host shares, with nothing compiled (docs/PLANNER.md).

Exit codes: 0 — clean; 1 — violations (or, under ``--strict``, traced
programs with no pinned golden; or ``--write-goldens`` refusing to
overwrite a mismatching golden without ``--force``); 2 — usage error.

The contract layer traces programs over the same virtual 8-device CPU
mesh the test suite uses (goldens record the world they were pinned on),
so the CPU is forced here — tracing on an attached TPU would otherwise
silently change every byte estimate. ``XLA_FLAGS`` is read when the
backend starts, so the env is enough for it; ``JAX_PLATFORMS`` was read
when ``python -m tpu_syncbn.audit`` imported the package (and jax)
before this module ran, so the platform also goes through
``jax.config``. The forced
variables are snapshotted at import and restored when :func:`main`
returns — the ``jax.config`` platform override included — so the
module is callable in-process (tests) without leaking
``XLA_FLAGS``/``JAX_PLATFORMS`` into the caller; restoration only
rolls back values *we* set, never a caller's own later changes. (A
backend jax already initialized during the run stays initialized —
restoring the config returns the *selector* to the caller, which is
all an in-process caller that has not yet touched devices needs.)
"""

from __future__ import annotations

import os

_DEVCOUNT_FLAG = "--xla_force_host_platform_device_count=8"

#: var -> (original value or None, the value we forced). Populated at
#: import so the mutation lands before jax does; consumed by
#: ``_restore_env`` when main() exits.
_FORCED_ENV: dict[str, tuple[str | None, str]] = {}

#: jax_platforms config values captured before ``_run`` forced "cpu"
#: (the in-process no-leak contract must roll this back too, not just
#: the env vars).
_PRIOR_JAX_PLATFORMS: list = []


def _force_env() -> None:
    if _DEVCOUNT_FLAG not in os.environ.get("XLA_FLAGS", ""):
        forced = (os.environ.get("XLA_FLAGS", "") + " "
                  + _DEVCOUNT_FLAG).strip()
        _FORCED_ENV["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS"), forced)
        os.environ["XLA_FLAGS"] = forced
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        _FORCED_ENV["JAX_PLATFORMS"] = (
            os.environ.get("JAX_PLATFORMS"), "cpu"
        )
        os.environ["JAX_PLATFORMS"] = "cpu"


def _restore_env() -> None:
    """Roll back exactly the variables we forced — and only if they
    still hold our value (a caller who changed them since keeps their
    change)."""
    for var, (original, forced) in list(_FORCED_ENV.items()):
        if os.environ.get(var) == forced:
            if original is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = original
        _FORCED_ENV.pop(var)
    while _PRIOR_JAX_PLATFORMS:
        prior = _PRIOR_JAX_PLATFORMS.pop()
        import jax

        if jax.config.jax_platforms == "cpu":  # still our value
            jax.config.update("jax_platforms", prior)


_force_env()

import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402


def _parse_bytes(text: str) -> int:
    """``1048576`` / ``512k`` / ``64m`` / ``2g`` → bytes."""
    text = text.strip().lower()
    mult = {"k": 1 << 10, "m": 1 << 20, "g": 1 << 30}.get(text[-1:], 1)
    digits = text[:-1] if mult != 1 else text
    return int(digits) * mult


def _changed_files(ref: str, pkg_root: str) -> list[str] | None:
    """Package ``.py`` files changed vs ``ref``. ``--relative`` makes
    git print paths relative to the cwd (the package's parent), so the
    join below is correct even when that directory is not the repo
    toplevel (monorepo layouts). None when git is unusable — the caller
    falls back to the full sweep rather than silently auditing
    nothing."""
    base = os.path.dirname(os.path.abspath(pkg_root))
    rels: list[str] = []
    # diffed AND untracked: a brand-new module is exactly the file most
    # likely to carry a fresh violation — `git diff` alone misses it
    for cmd in (
        ["git", "diff", "--name-only", "--relative", ref, "--", "*.py"],
        ["git", "ls-files", "--others", "--exclude-standard",
         "--", "*.py"],
    ):
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=30,
                cwd=base,
            )
        except (OSError, subprocess.TimeoutExpired):
            return None
        if proc.returncode != 0:
            return None
        rels.extend(proc.stdout.splitlines())
    out = []
    for rel in dict.fromkeys(r.strip() for r in rels):
        path = os.path.join(base, rel)
        if path.endswith(".py") and os.path.exists(path) \
                and os.path.abspath(path).startswith(
                    os.path.abspath(pkg_root) + os.sep):
            out.append(path)
    return out

#: Changed paths touching these package subtrees invalidate the traced
#: program set, so --changed-only keeps the contract layer on for them
#: (and skips it — the slow part — otherwise).
_CONTRACT_SOURCES = ("parallel", "serve", "nn", "ops", "audit",
                    "runtime", "compat.py", "mesh_axes.py")


def main(argv=None) -> int:
    # re-force at entry: a prior in-process call restored the env on
    # exit, so import-time forcing alone would leave a second call's
    # contract layer on whatever platform the caller selected
    _force_env()
    argv = list(sys.argv[1:] if argv is None else argv)
    try:
        if argv and argv[0] == "plan":
            return _run_plan(_parse_plan(argv[1:]))
        return _run(_parse(argv))
    finally:
        _restore_env()


def _parse(argv):
    parser = argparse.ArgumentParser(
        prog="python -m tpu_syncbn.audit",
        description="Static program-contract audit: jaxpr-level "
        "collective/donation verification, sharding-flow analysis, and "
        "repo-hazard source lint (docs/STATIC_ANALYSIS.md).",
    )
    parser.add_argument(
        "--strict", action="store_true",
        help="traced programs with no pinned golden are failures, "
        "not warnings",
    )
    parser.add_argument(
        "--json", action="store_true", dest="as_json",
        help="emit one machine-readable JSON report on stdout",
    )
    parser.add_argument(
        "--shardings", action="store_true",
        help="layer 3 deep mode: compile each traced program once so "
        "the sharding block carries the XLA memory_analysis "
        "cross-check (the propagation pass itself always runs with "
        "the contract layer)",
    )
    parser.add_argument(
        "--mem-budget", default=None, metavar="BYTES",
        help="per-device peak-memory contract (accepts k/m/g suffixes); "
        "any traced program whose estimated peak exceeds it is a "
        "sharding.mem_budget violation",
    )
    parser.add_argument(
        "--write-goldens", action="store_true",
        help="re-pin every program contract under the contracts dir. "
        "Prints the per-contract old->new field diff; refuses to "
        "overwrite mismatching goldens without --force",
    )
    parser.add_argument(
        "--force", action="store_true",
        help="with --write-goldens: overwrite goldens even when they "
        "mismatch (you have reviewed the printed diff)",
    )
    parser.add_argument(
        "--changed-only", default=None, metavar="GIT_REF",
        help="fast local mode: lint only package files changed vs the "
        "git ref, and run the contract layer only when a "
        "program-defining subtree changed",
    )
    parser.add_argument(
        "--contracts-dir", default=None, metavar="DIR",
        help="golden-contract directory (default: tests/contracts/ "
        "next to the package)",
    )
    parser.add_argument(
        "--no-contracts", action="store_true",
        help="source lint only — skips program tracing entirely "
        "(fast; no mesh, no trainer construction)",
    )
    parser.add_argument(
        "--no-lint", action="store_true",
        help="contract layer only",
    )
    parser.add_argument(
        "--rules", default=None, metavar="R1,R2",
        help="comma-separated srclint rule subset (default: all)",
    )
    parser.add_argument(
        "--root", default=None, metavar="PATH",
        help="lint this source tree instead of the installed package",
    )
    return parser.parse_args(argv)


def _run(args) -> int:
    mem_budget = None
    if args.mem_budget is not None:
        try:
            mem_budget = _parse_bytes(args.mem_budget)
        except ValueError:
            print(f"--mem-budget: cannot parse {args.mem_budget!r} "
                  "(want bytes, or k/m/g-suffixed)", file=sys.stderr)
            return 2
        if mem_budget < 1:
            print("--mem-budget must be positive", file=sys.stderr)
            return 2
    if args.force and not args.write_goldens:
        print("--force only applies to --write-goldens", file=sys.stderr)
        return 2

    if not args.no_contracts:
        # jax was imported with the package, before the env vars above
        # were forced — select the pinned CPU mesh the goldens were
        # traced on through the config; the prior value is restored
        # with the env when main() returns
        import jax

        if jax.config.jax_platforms != "cpu":
            _PRIOR_JAX_PLATFORMS.append(jax.config.jax_platforms)
            jax.config.update("jax_platforms", "cpu")

    from tpu_syncbn import audit
    from tpu_syncbn.audit.srclint import RULES, package_files

    rules = None
    if args.rules:
        rules = [r.strip() for r in args.rules.split(",") if r.strip()]
        unknown = [r for r in rules if r not in RULES]
        if unknown:
            print(f"unknown rule(s): {', '.join(unknown)} "
                  f"(have: {', '.join(RULES)})", file=sys.stderr)
            return 2

    lint_paths = None
    contracts = not args.no_contracts
    if args.changed_only is not None:
        import tpu_syncbn

        pkg_root = args.root or os.path.dirname(
            os.path.abspath(tpu_syncbn.__file__)
        )
        changed = _changed_files(args.changed_only, pkg_root)
        if changed is None:
            print(f"--changed-only: git diff vs {args.changed_only!r} "
                  "failed; falling back to the full sweep",
                  file=sys.stderr)
        else:
            lint_paths = changed
            if contracts:
                rel = [os.path.relpath(p, pkg_root) for p in changed]
                touches_programs = any(
                    r == src or r.startswith(src + os.sep)
                    or r.replace(os.sep, "/").split("/")[0] == src
                    for r in rel for src in _CONTRACT_SOURCES
                )
                contracts = touches_programs
                if not contracts:
                    print("--changed-only: no program-defining sources "
                          "changed; skipping the contract layer",
                          file=sys.stderr)

    if args.write_goldens:
        from tpu_syncbn.audit import jaxpr_audit

        gdir = args.contracts_dir or jaxpr_audit.default_golden_dir()
        live = jaxpr_audit.build_contracts(memory=args.shardings)
        diffs = jaxpr_audit.golden_diffs(live, gdir)
        for name in sorted(diffs):
            print(f"re-pin {name}:")
            for line in diffs[name]:
                print(f"  {line}")
        mismatching = {
            n for n, lines in diffs.items()
            if lines != ["<new golden — no previous pin>"]
        }
        if mismatching and not args.force:
            print(
                f"refusing to overwrite {len(mismatching)} mismatching "
                "golden(s) without --force — review the old->new diff "
                "above first (docs/STATIC_ANALYSIS.md)"
            )
            return 1
        if not diffs:
            print("goldens already match the live contracts — "
                  "nothing re-pinned")
            return 0
        written = jaxpr_audit.write_goldens(live, gdir)
        for path in written:
            print(f"pinned {os.path.relpath(path)}")
        return 0

    result = audit.run_audit(
        strict=args.strict,
        lint=not args.no_lint,
        contracts=contracts,
        golden_dir=args.contracts_dir,
        pkg_root=args.root,
        rules=rules,
        shardings=args.shardings,
        mem_budget=mem_budget,
        lint_paths=lint_paths,
    )

    if args.as_json:
        print(json.dumps(result.to_json(), indent=1, sort_keys=False))
    else:
        for v in result.violations:
            print(v.format())
        for name in result.unpinned:
            tag = "FAIL" if args.strict else "warn"
            print(f"{tag}: program {name!r} has no pinned golden "
                  "(--write-goldens to pin)")
        print(
            f"audit: {result.files_linted} files linted, "
            f"{result.programs_checked} programs checked, "
            f"{len(result.violations)} violation(s)"
            + (f", {len(result.unpinned)} unpinned" if result.unpinned
               else "")
        )
    return 0 if result.ok else 1


def _parse_plan(argv):
    parser = argparse.ArgumentParser(
        prog="python -m tpu_syncbn.audit plan",
        description="Contract-driven parallelism planner: enumerate "
        "DP / DP+ZeRO / DP×FSDP / DP×TP / pipeline / tensor layout "
        "candidates over the "
        "virtual 8-device mesh, cost each statically from its traced "
        "contract (nothing compiles), and print the ranked "
        "predicted-step-time table (docs/PLANNER.md).",
    )
    parser.add_argument(
        "--layers", type=int, default=None, metavar="N",
        help="LayerStack depth (default: the bench proxy stack)",
    )
    parser.add_argument(
        "--d-model", type=int, default=None, metavar="D",
        help="LayerStack model width",
    )
    parser.add_argument(
        "--d-hidden", type=int, default=None, metavar="H",
        help="LayerStack hidden width",
    )
    parser.add_argument(
        "--batch", type=int, default=32, metavar="B",
        help="global batch rows (default 32)",
    )
    parser.add_argument(
        "--objective", default="step_time",
        choices=("step_time", "wire_bytes", "peak_memory"),
        help="ranking objective (default step_time)",
    )
    parser.add_argument(
        "--mem-budget", default=None, metavar="BYTES",
        help="per-device peak-memory contract (k/m/g suffixes ok); "
        "candidates whose predicted peak exceeds it are rejected with "
        "a named reason",
    )
    parser.add_argument(
        "--top", type=int, default=None, metavar="K",
        help="print only the K best plans (default: all)",
    )
    parser.add_argument(
        "--json", action="store_true", dest="as_json",
        help="emit the full RankedPlans JSON on stdout",
    )
    return parser.parse_args(argv)


def _run_plan(args) -> int:
    mem_budget = None
    if args.mem_budget is not None:
        try:
            mem_budget = _parse_bytes(args.mem_budget)
        except ValueError:
            print(f"--mem-budget: cannot parse {args.mem_budget!r} "
                  "(want bytes, or k/m/g-suffixed)", file=sys.stderr)
            return 2
        if mem_budget < 1:
            print("--mem-budget must be positive", file=sys.stderr)
            return 2
    # same pinned-CPU-mesh discipline as the contract layer: candidates
    # are built with the real trainers, so the virtual 8-device mesh
    # must win; rolled back with the env
    import jax

    if jax.config.jax_platforms != "cpu":
        _PRIOR_JAX_PLATFORMS.append(jax.config.jax_platforms)
        jax.config.update("jax_platforms", "cpu")

    from tpu_syncbn.parallel import planner

    stack = planner.bench_stack()
    if (args.layers is not None or args.d_model is not None
            or args.d_hidden is not None):
        stack = planner.LayerStack(
            n_layers=args.layers if args.layers is not None
            else stack.n_layers,
            d_model=args.d_model if args.d_model is not None
            else stack.d_model,
            d_hidden=args.d_hidden if args.d_hidden is not None
            else stack.d_hidden,
            name="custom",
        )
    try:
        ranked = planner.plan(
            stack, args.batch, len(jax.devices()),
            objective=args.objective, mem_budget=mem_budget,
        )
    except ValueError as e:
        print(f"plan: {e}", file=sys.stderr)
        return 2
    if args.top is not None:
        ranked.plans = ranked.plans[:max(0, args.top)]
    if args.as_json:
        print(json.dumps(ranked.to_json(), indent=1, sort_keys=False))
    else:
        print(ranked.table())
    return 0 if ranked.plans else 1


if __name__ == "__main__":
    sys.exit(main())
