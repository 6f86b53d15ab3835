"""graftlint — project-native static analysis for the jumbo-mae-tpu tree.

Three checker families, each conservative by construction (a finding is a
claim the AST supports outright, so the shipped tree lints clean without
suppression comments):

* ``check_tracing``  (TRC001-TRC004) — JAX tracing hazards inside jitted
  functions: Python control flow on traced values, host syncs, wall-clock
  and host RNG, config-shaped parameters without ``static_argnames``.
* ``check_locks``    (LCK001-LCK004) — lock discipline in the threaded
  serving/observability code: blocking while holding a known lock, the
  round-10 self-deadlock shape, global lock-order cycles, ``yield`` under
  a lock.
* ``check_contracts`` (CON001-CON004) — drift between code and the
  project's frozen contracts: metric names ↔ README glossary, journal
  events ↔ ``obs.journal.JOURNAL_EVENTS``, fault sites ↔
  ``faults.inject.KNOWN_SITES``, config keys ↔ the config dataclasses.

Run ``python -m tools.graftlint`` from the repo root. Exit 0 means clean
(every finding either fixed or baselined with a reason), exit 2 means
unbaselined findings — CI gates on it.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path

from tools.graftlint.astutil import iter_py_files, parse_file
from tools.graftlint.check_contracts import (
    ContractScan,
    Registries,
    check_contracts_py,
    full_repo_contracts,
)
from tools.graftlint.check_locks import check_locks, order_graph_findings
from tools.graftlint.check_tracing import check_tracing
from tools.graftlint.findings import (
    EXIT_CLEAN,
    EXIT_FINDINGS,
    Baseline,
    Finding,
    render_report,
    split_by_baseline,
)

__all__ = [
    "Baseline",
    "Finding",
    "LintResult",
    "run_lint",
    "render_report",
    "split_by_baseline",
    "EXIT_CLEAN",
    "EXIT_FINDINGS",
    "DEFAULT_PATHS",
]

# What a bare ``python -m tools.graftlint`` scans, relative to the root.
DEFAULT_PATHS = ("jumbo_mae_tpu_tpu", "tools")


@dataclass
class LintResult:
    findings: list[Finding] = field(default_factory=list)
    files_scanned: int = 0


def run_lint(
    root: Path,
    paths: list[Path] | None = None,
    *,
    full: bool | None = None,
) -> LintResult:
    """Lint ``paths`` (default: the project tree under ``root``).

    ``full`` additionally runs the repo-wide two-sided contract checks
    (stale README glossary rows, README journal table, CI workflow and
    README text carriers). It defaults to on exactly when no explicit
    paths were given — explicit paths mean "lint these files", and
    repo-wide documentation drift is not those files' fault.
    """
    if full is None:
        full = paths is None
    if paths is None:
        paths = [root / p for p in DEFAULT_PATHS]
    result = LintResult()
    regs = Registries.load(root)
    scan = ContractScan()
    order_edges: list[tuple[str, str, str, int]] = []
    for path in iter_py_files([p for p in paths if p.exists()]):
        sf = parse_file(path, root)
        if sf is None:
            continue
        result.files_scanned += 1
        result.findings.extend(check_tracing(sf))
        facts = check_locks(sf)
        result.findings.extend(facts.findings)
        order_edges.extend(facts.order_edges)
        check_contracts_py(sf, regs, scan)
    result.findings.extend(scan.findings)
    result.findings.extend(order_graph_findings(order_edges))
    if full:
        result.findings.extend(full_repo_contracts(root, regs, scan))
    result.findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return result
