"""Program-contract checker: static enforcement of the repo's
correctness discipline.

Three layers, one gate (``python -m poisson_tpu.contracts``):

- :mod:`~poisson_tpu.contracts.lint` — trace-safety AST lint (stdlib
  ``ast``, no jax): ungated host callbacks, Python control flow on
  traced values, unhashable jit static defaults, wall-clock/RNG in
  solver code, undocumented counter names, undeclared flight span
  kinds, unregistered chaos scenarios, fingerprints in cache/cohort
  keys. Inline suppression requires a reason string.
- :mod:`~poisson_tpu.contracts.hlo` +
  :mod:`~poisson_tpu.contracts.manifest` — the HLO identity ledger: a
  declarative registry of every flag-off program, lowered through the
  real entry points, canonicalized, fingerprinted, and checked
  (structure + fingerprint) against the committed ``ledger.json``.
- :mod:`~poisson_tpu.contracts.drift` — registry drift detection:
  every ``ServicePolicy``/``FleetPolicy`` field needs a chaos drill or
  a written exemption.

The gate reads and lints only ``poisson_tpu/``; nothing outside the
package is an input to it.

README "Program contracts" documents the rule table, the suppression
syntax, and the ledger-update workflow.
"""

from poisson_tpu.contracts.hlo import (
    CALLBACK_MARKERS,
    COLLECTIVE_MARKERS,
    MG_MARKERS,
    assert_no_forbidden,
    find_forbidden,
    hlo_fingerprint,
    strip_hlo_metadata,
)
from poisson_tpu.contracts.lint import Finding, lint_source, run_lint

__all__ = [
    "CALLBACK_MARKERS",
    "COLLECTIVE_MARKERS",
    "MG_MARKERS",
    "Finding",
    "assert_no_forbidden",
    "find_forbidden",
    "hlo_fingerprint",
    "lint_source",
    "run_lint",
    "strip_hlo_metadata",
]
