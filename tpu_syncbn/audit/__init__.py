"""Program-contract auditor: static verification that the stack's
compiled programs and source text honor the invariants the paper (and
PRs 1–5) promised.

Three layers, one driver:

* :mod:`tpu_syncbn.audit.jaxpr_audit` — abstractly traces every
  compiled program the stack builds (DataParallel plain/zero, GANTrainer,
  fused scan at K=1/4, serve eval buckets, and the
  tensor/pipeline/expert/sequence strategy programs) and extracts a
  :class:`~tpu_syncbn.audit.contracts.ProgramContract` (collectives +
  bytes-on-wire, effective donation, host callbacks, BN-stat upcasts),
  checked against cross-program invariants and goldens pinned under
  ``tests/contracts/``.
* :mod:`tpu_syncbn.audit.sharding_audit` — layer 3: per-value
  named-sharding propagation over the same traces (elementwise /
  reduce / collective / scan / ``shard_map`` boundaries), detecting
  accidental full replication, implicit resharding no declared
  collective explains, and per-device peak memory (cross-checked
  against XLA ``memory_analysis`` under ``--shardings``); pinned as the
  ``sharding`` block of each golden.
* :mod:`tpu_syncbn.audit.srclint` — stdlib-only AST lint enforcing the
  repo's hazard rules (donate-after-use, compat bypass, host sync in
  step builders, lock discipline, telemetry schema, unpaired spans,
  hardcoded mesh axes).

Run all with ``python -m tpu_syncbn.audit [--strict] [--json]
[--shardings] [--mem-budget N]`` or via :func:`run_audit`; the rule
catalog and re-pin workflow live in docs/STATIC_ANALYSIS.md. Results
feed the ``audit.*`` telemetry counters (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import dataclasses

from tpu_syncbn.audit.contracts import (  # noqa: F401
    CONTRACT_SCHEMA,
    SHARDING_SCHEMA,
    ProgramContract,
    ShardingContract,
    compare_contracts,
    compare_sharding,
    extract_contract,
    load_contract,
    save_contract,
)
from tpu_syncbn.audit.srclint import (  # noqa: F401
    RULES,
    Violation,
    lint_file,
    lint_package,
    lint_source,
)

#: Bump when the CLI/JSON report shape changes incompatibly.
REPORT_SCHEMA = 1


@dataclasses.dataclass
class AuditResult:
    """Aggregate outcome of one audit run — both layers' violations plus
    the accounting the CLI, the tier-1 test, and the ``audit.*``
    telemetry counters all key on."""

    violations: list[Violation]
    unpinned: list[str]
    files_linted: int
    programs_checked: int
    strict: bool

    @property
    def rule_counts(self) -> dict[str, int]:
        counts: dict[str, int] = {}
        for v in self.violations:
            counts[v.rule] = counts.get(v.rule, 0) + 1
        return counts

    @property
    def ok(self) -> bool:
        if self.violations:
            return False
        return not (self.strict and self.unpinned)

    def to_json(self) -> dict:
        return {
            "schema": REPORT_SCHEMA,
            "ok": self.ok,
            "strict": self.strict,
            "files_linted": self.files_linted,
            "programs_checked": self.programs_checked,
            "violations": [v.to_json() for v in self.violations],
            "unpinned": list(self.unpinned),
            "rule_counts": dict(sorted(self.rule_counts.items())),
        }


def run_audit(
    *,
    strict: bool = False,
    lint: bool = True,
    contracts: bool = True,
    golden_dir: str | None = None,
    pkg_root: str | None = None,
    rules=None,
    shardings: bool = False,
    mem_budget: int | None = None,
    lint_paths=None,
) -> AuditResult:
    """Run the audit layers and fold the outcome into the ``audit.*``
    telemetry counters. ``contracts=False`` skips program tracing
    entirely — no mesh, no trainer construction; the lint rules
    themselves are pure ``ast``. This function touches no environment
    variables — the CLI (``__main__``) forces and *restores* the pinned
    CPU mesh around it, so calling in-process (tests) leaks no
    config into the caller.

    ``lint_paths`` restricts the source lint to an explicit file list
    (the ``--changed-only`` fast mode). ``shardings=True`` compiles each
    traced program once so the layer-3 block carries the XLA
    ``memory_analysis`` cross-check; the sharding *propagation* itself
    always runs with the contract layer. ``mem_budget`` (bytes) arms the
    per-device peak-memory contract (``sharding.mem_budget``)."""
    from tpu_syncbn.obs import telemetry

    violations: list[Violation] = []
    unpinned: list[str] = []
    files_linted = 0
    programs_checked = 0
    sharding_programs = 0
    sharding_violations = 0

    if lint:
        from tpu_syncbn.audit import srclint

        files = (list(lint_paths) if lint_paths is not None
                 else srclint.package_files(pkg_root))
        files_linted = len(files)
        for path in files:
            violations.extend(srclint.lint_file(path, rules=rules))

    if contracts:
        from tpu_syncbn.audit import jaxpr_audit

        live = jaxpr_audit.build_contracts(memory=shardings)
        programs_checked = len(live)
        sharding_programs = sum(
            1 for c in live.values() if c.sharding is not None
        )
        violations.extend(jaxpr_audit.check_invariants(live))
        sharding_found = jaxpr_audit.check_sharding(
            live, mem_budget=mem_budget
        )
        sharding_violations = len(sharding_found)
        violations.extend(sharding_found)
        gdir = golden_dir or jaxpr_audit.default_golden_dir()
        golden_violations, unpinned = jaxpr_audit.check_goldens(live, gdir)
        violations.extend(golden_violations)

    result = AuditResult(
        violations=violations,
        unpinned=unpinned,
        files_linted=files_linted,
        programs_checked=programs_checked,
        strict=strict,
    )
    telemetry.count("audit.runs")
    if files_linted:
        telemetry.count("audit.files_linted", files_linted)
    if programs_checked:
        telemetry.count("audit.programs_checked", programs_checked)
    if sharding_programs:
        telemetry.count("audit.sharding.programs", sharding_programs)
    if contracts:
        # counted even at 0 — but only when the layer actually ran,
        # so a lint-only run never minted a "sharding ran clean" signal
        telemetry.count("audit.sharding.violations", sharding_violations)
    telemetry.count("audit.violations", len(violations))
    for rule, n in result.rule_counts.items():
        telemetry.count(f"audit.rule.{rule}", n)
    return result


__all__ = [
    "REPORT_SCHEMA",
    "CONTRACT_SCHEMA",
    "SHARDING_SCHEMA",
    "AuditResult",
    "ProgramContract",
    "ShardingContract",
    "Violation",
    "RULES",
    "run_audit",
    "lint_file",
    "lint_package",
    "lint_source",
    "compare_contracts",
    "compare_sharding",
    "extract_contract",
    "load_contract",
    "save_contract",
]
