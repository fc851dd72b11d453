"""Registry drift detection: the cross-file half of the contract.

**policy fields ↔ chaos coverage.** Every ``ServicePolicy``/
``FleetPolicy`` field must be exercised by at least one scenario in
``testing/chaos.py`` (as a constructor kwarg or attribute access) or
carry an explicit exemption in ``POLICY_COVERAGE_EXEMPT``. A policy
knob no chaos scenario ever sets is a failure-handling path with no
deterministic regression test.

The check is pure stdlib-``ast`` over source text (the unit-test seam
takes strings), reported as :class:`~poisson_tpu.contracts.lint.
Finding` rows so the CLI/JSON report renders one finding stream. It
reads only files inside the package.
"""

from __future__ import annotations

import ast
import os
from dataclasses import asdict
from typing import Optional

from poisson_tpu.contracts.lint import Finding, repo_root
from poisson_tpu.contracts.manifest import POLICY_COVERAGE_EXEMPT


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
    """The cross-file check over the tree; report dict mirroring
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

    types_src = read("poisson_tpu/serve/types.py")
    chaos_src = read("poisson_tpu/testing/chaos.py")
    if types_src is not None and chaos_src is not None:
        findings.extend(check_policy_coverage(types_src, chaos_src))
    return {
        "schema": "poisson_tpu.contracts.drift/1",
        "root": root,
        "checks": ["policy-chaos-coverage", "exemption-stale"],
        "findings": [asdict(f) for f in findings],
        "counts": {"findings": len(findings)},
    }
