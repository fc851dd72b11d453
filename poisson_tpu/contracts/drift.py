"""Registry drift detection: the cross-file halves of the contract.

Two contracts span file boundaries, which is exactly where ad-hoc
discipline drifts:

1. **bench detail ↔ regress cohort key.** Every ``detail.*`` field a
   ``bench.py`` mode emits is either *experiment identity* (it must be
   picked up by ``benchmarks/regress.py``'s ``record_from_result`` and
   join :func:`cohort_key`, so runs are only ever compared like-for-
   like) or *attribution payload* (it must be explicitly listed in
   ``contracts.manifest.ATTRIBUTION_ONLY_DETAIL`` with a reason). A
   detail key in neither set is the PR 9/11/12 drift class: a new
   dispatch dimension whose records silently judge the wrong baseline.

2. **policy fields ↔ chaos coverage.** Every ``ServicePolicy``/
   ``FleetPolicy`` field must be exercised by at least one scenario in
   ``testing/chaos.py`` (as a constructor kwarg or attribute access) or
   carry an explicit exemption in ``POLICY_COVERAGE_EXEMPT``. A policy
   knob no chaos scenario ever sets is a failure-handling path with no
   deterministic regression test.

Both checks are pure stdlib-``ast`` over source text (the unit-test
seam takes strings), reported as :class:`~poisson_tpu.contracts.lint.
Finding` rows so the CLI/JSON report renders one finding stream.
"""

from __future__ import annotations

import ast
import os
from dataclasses import asdict
from typing import Optional

from poisson_tpu.contracts.lint import Finding, repo_root
from poisson_tpu.contracts.manifest import (
    ATTRIBUTION_ONLY_DETAIL,
    POLICY_COVERAGE_EXEMPT,
)

def bench_detail_keys(bench_source: str) -> dict:
    """Every literal key of every ``"detail": {...}`` dict in bench.py,
    mapped to the first line it appears on."""
    keys: dict = {}
    tree = ast.parse(bench_source)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Dict):
            continue
        for k, v in zip(node.keys, node.values):
            if (isinstance(k, ast.Constant) and k.value == "detail"
                    and isinstance(v, ast.Dict)):
                for dk in v.keys:
                    if (isinstance(dk, ast.Constant)
                            and isinstance(dk.value, str)):
                        keys.setdefault(dk.value, dk.lineno)
    return keys


def cohort_detail_fields(regress_source: str) -> set:
    """The detail fields ``record_from_result`` lifts into the sentinel
    record (the fields eligible for ``cohort_key``), read off the
    ``det.get("...")`` calls in its body."""
    tree = ast.parse(regress_source)
    fields: set = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.FunctionDef)
                and node.name == "record_from_result"):
            for call in ast.walk(node):
                if (isinstance(call, ast.Call)
                        and isinstance(call.func, ast.Attribute)
                        and call.func.attr == "get"
                        and isinstance(call.func.value, ast.Name)
                        and call.func.value.id == "det"
                        and call.args
                        and isinstance(call.args[0], ast.Constant)):
                    fields.add(call.args[0].value)
    return fields


def check_bench_cohort(bench_source: str, regress_source: str,
                       attribution_only: Optional[dict] = None) -> list:
    """Findings for bench detail keys that neither join the cohort key
    nor carry an attribution-only exemption."""
    allow = (ATTRIBUTION_ONLY_DETAIL if attribution_only is None
             else attribution_only)
    cohort = cohort_detail_fields(regress_source)
    detail_keys = bench_detail_keys(bench_source)
    findings = []
    for key, line in sorted(detail_keys.items()):
        if key in cohort or key in allow:
            continue
        findings.append(Finding(
            rule="bench-detail-cohort", file="bench.py", line=line,
            col=0,
            message=(
                f"detail key '{key}' is neither lifted into the "
                f"regress.py cohort key (record_from_result) nor "
                f"listed attribution-only in contracts.manifest."
                f"ATTRIBUTION_ONLY_DETAIL — a new dispatch dimension "
                f"must split cohorts, payload must be declared payload"),
        ))
    # Staleness, the same asymmetry the ledger closes with
    # ledger-stale: an allowlist entry for a key bench.py no longer
    # emits is rot — and a future different key colliding with a
    # rotted name would be silently waved through.
    for key in sorted(set(allow) - set(detail_keys)):
        findings.append(Finding(
            rule="attribution-stale", file="bench.py", line=1, col=0,
            message=(
                f"ATTRIBUTION_ONLY_DETAIL entry '{key}' matches no "
                f"detail key any bench.py mode emits — remove the "
                f"stale exemption from contracts.manifest"),
        ))
    return findings


def policy_fields(types_source: str) -> dict:
    """{'ServicePolicy.capacity': lineno, ...} for the dataclass fields
    of ServicePolicy and FleetPolicy in serve/types.py."""
    out: dict = {}
    for node in ast.parse(types_source).body:
        if not (isinstance(node, ast.ClassDef)
                and node.name in ("ServicePolicy", "FleetPolicy")):
            continue
        for stmt in node.body:
            if (isinstance(stmt, ast.AnnAssign)
                    and isinstance(stmt.target, ast.Name)):
                out[f"{node.name}.{stmt.target.id}"] = stmt.lineno
    return out


def chaos_exercised_names(chaos_source: str) -> set:
    """Every keyword-argument name and attribute name appearing in
    testing/chaos.py — the (deliberately generous) evidence that a
    policy field is exercised by at least one scenario."""
    names: set = set()
    for node in ast.walk(ast.parse(chaos_source)):
        if isinstance(node, ast.Call):
            for kw in node.keywords:
                if kw.arg:
                    names.add(kw.arg)
        elif isinstance(node, ast.Attribute):
            names.add(node.attr)
    return names


def check_policy_coverage(types_source: str, chaos_source: str,
                          exempt: Optional[dict] = None) -> list:
    """Findings for policy fields no chaos scenario exercises and no
    exemption explains."""
    exempt = POLICY_COVERAGE_EXEMPT if exempt is None else exempt
    exercised = chaos_exercised_names(chaos_source)
    fields = policy_fields(types_source)
    findings = []
    for qualified, line in sorted(fields.items()):
        field = qualified.split(".", 1)[1]
        if field in exercised or qualified in exempt:
            continue
        findings.append(Finding(
            rule="policy-chaos-coverage", file="poisson_tpu/serve/types.py",
            line=line, col=0,
            message=(
                f"{qualified} is never exercised by any chaos scenario "
                f"(no kwarg/attribute use in testing/chaos.py) and has "
                f"no exemption in contracts.manifest."
                f"POLICY_COVERAGE_EXEMPT — a failure-handling knob "
                f"needs a deterministic drill or a written reason"),
        ))
    for qualified in sorted(set(exempt) - set(fields)):
        findings.append(Finding(
            rule="exemption-stale", file="poisson_tpu/serve/types.py",
            line=1, col=0,
            message=(
                f"POLICY_COVERAGE_EXEMPT entry '{qualified}' matches "
                f"no ServicePolicy/FleetPolicy field — remove the "
                f"stale exemption from contracts.manifest"),
        ))
    return findings


def run_drift(root: Optional[str] = None) -> dict:
    """Both cross-file checks over the tree; report dict mirroring
    :func:`poisson_tpu.contracts.lint.run_lint`."""
    root = os.path.abspath(root or repo_root())
    findings = []

    def read(rel):
        """Source text, or None with a loud finding — a drift check
        whose inputs vanished must fail with a diagnostic, not crash
        (and never silently pass)."""
        try:
            with open(os.path.join(root, rel)) as f:
                return f.read()
        except OSError as e:
            findings.append(Finding(
                rule="drift-source-missing", file=rel, line=1, col=0,
                message=(f"cross-file drift check cannot read its "
                         f"source ({e}) — wrong --root, or a checked "
                         f"file moved without updating contracts.drift"),
            ))
            return None

    bench_src = read("bench.py")
    regress_src = read("benchmarks/regress.py")
    if bench_src is not None and regress_src is not None:
        findings.extend(check_bench_cohort(bench_src, regress_src))
    types_src = read("poisson_tpu/serve/types.py")
    chaos_src = read("poisson_tpu/testing/chaos.py")
    if types_src is not None and chaos_src is not None:
        findings.extend(check_policy_coverage(types_src, chaos_src))
    return {
        "schema": "poisson_tpu.contracts.drift/1",
        "root": root,
        "checks": ["bench-detail-cohort", "attribution-stale",
                   "policy-chaos-coverage", "exemption-stale"],
        "findings": [asdict(f) for f in findings],
        "counts": {"findings": len(findings)},
    }
