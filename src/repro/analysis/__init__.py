"""Static analysis: the AST lint engine + the compile-time plan verifier
(DESIGN.md §14).

The paper's architecture only works because hard invariants hold —
channel counts divide the ICP/OCP mesh (Eq. 6/7), every stage's shapes
follow from its inputs (Eq. 1–2), int8 requant stays exact per-channel.
This package checks those invariants *statically*, once, with a named
rule and a fix hint, instead of letting a mistyped stage compile and die
at dispatch:

  * ``repro.analysis.rules`` / ``engine`` — an AST lint engine over the
    source tree. Every regex grep-gate that used to live in
    ``scripts/check_dispatch.py`` is now an AST rule (plus rules the
    regexes could not express: aliased clock imports, unthreaded RNG
    keys, bare ``except:``, mutable default args). Findings carry
    path:line, rule id, severity, message and a suggested fix; per-line
    ``# lint: disable=<rule>`` suppresses; ``--json`` emits machine-
    readable output.

  * ``repro.analysis.verifier`` — ``verify_plan(plan_or_bound)``
    statically re-derives and checks every stage of a compiled
    ``ExecutionPlan`` / ``BoundPlan`` before any dispatch: shape/dtype
    flow, quantization invariants, sharding legality, artifact-schema
    coherence. Wired into
    ``compile_model``/``bind`` under ``verify=True`` and into the
    artifact loader, so a corrupt plan is rejected with a named
    violation instead of a downstream crash.

``python -m repro.analysis`` runs both over the tree; ``scripts/check.sh``
gates the build on it.
"""
from repro.analysis.engine import (DEFAULT_SCAN_DIRS, LintEngine,
                                   findings_to_json, format_findings,
                                   lint_tree)
from repro.analysis.findings import Finding, Severity
from repro.analysis.rules import Rule, all_rules, rule_by_id
from repro.analysis.verifier import (PlanVerificationError, Violation,
                                     verify_plan)

__all__ = ["Finding", "Severity", "Rule", "all_rules", "rule_by_id",
           "LintEngine", "lint_tree", "format_findings", "findings_to_json",
           "DEFAULT_SCAN_DIRS", "Violation", "PlanVerificationError",
           "verify_plan"]
