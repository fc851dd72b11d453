"""HLO canonicalization, fingerprints, and structural assertions.

The "flag-off means byte-identical" contract used to be enforced by
hand-rolled pins scattered across the test suite, each with its own
``re.sub`` metadata strip and its own marker greps. This module is the
one shared code path: canonicalize a lowered (StableHLO) or compiled
(HLO) program text, fingerprint it, and grep it for structurally
forbidden ops — the ledger (``contracts.manifest``) and the remaining
test pins both go through here.

Canonical form = the program text with location/debug metadata removed:
``metadata={...}`` operand annotations (compiled HLO), ``loc(...)``
attributes and ``#loc`` definition lines (StableHLO). Instruction
content, ordering, shapes, and constants are untouched — two programs
with equal canonical text compute the same thing the same way.

No jax import at module level: callers hand in program *text* (the
``.lower(...).as_text()`` / ``.compile().as_text()`` they already
have), so the stdlib-only consumers (tests, the ledger diff tool) stay
import-light.
"""

from __future__ import annotations

import hashlib
import re
from typing import Iterable, Sequence

# Substring markers for structural assertions over canonical text.
# Host-boundary ops: any of these in a flag-off program means a callback
# or custom kernel was traced in (the stream/verify/debug contract).
CALLBACK_MARKERS = ("custom_call", "custom-call", "callback",
                    "infeed", "outfeed")
# Collective/SPMD ops: any of these under ``mesh=None`` means the
# sharded machinery leaked into the single-device executable family.
COLLECTIVE_MARKERS = ("shard_map", "psum", "all_reduce", "all-reduce",
                      "all_gather", "all-gather", "collective_permute",
                      "collective-permute", "reduce_scatter",
                      "reduce-scatter")
# Dense-algebra ops: the Jacobi path's preconditioner is elementwise, so
# a ``dot_general`` in a jacobi program means the MG machinery (whose
# coarse solve is a dense matmul) leaked into the default executable.
MG_MARKERS = ("dot_general", "dot-general")

_METADATA_RE = re.compile(r", metadata=\{[^}]*\}")
_LOC_INLINE_RE = re.compile(r"\s*loc\([^()]*(?:\([^()]*\)[^()]*)*\)")
_LOC_LINE_RE = re.compile(r"^#loc.*$", re.MULTILINE)
# Compiled HLO text carries a source-location table after the module
# header (FileNames / FunctionNames / FileLocations / StackFrames, one
# block each up to a blank line): debug info, like ``metadata``.
_DEBUG_TABLE_RE = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*",
    re.MULTILINE)
# A host callback's backend_config is the host-side callable's ADDRESS
# (``xla_python_cpu_callback`` carries the pointer as a decimal string)
# — process-lifetime identity, not program structure. Left in place it
# makes every callback-bearing program's fingerprint unstable across
# processes, which would turn the ledger gate into noise for exactly
# the opt-in programs (stream/verify/history ON) it should also cover.
# Only all-digit configs are normalized: real kernel configs (proto or
# JSON blobs) never look like a bare pointer. The same pointer value
# also rides into the program as an i64 ``stablehlo.constant`` operand
# of the custom_call — exactly those constants (value-matched against
# the collected backend_config pointers) are normalized with it.
_CALLBACK_PTR_RE = re.compile(r'backend_config = "(\d+)"')


def strip_hlo_metadata(text: str) -> str:
    """Canonicalize program text: drop ``metadata={...}`` annotations and
    the source-location tables (compiled HLO), inline ``loc(...)``
    attributes and ``#loc`` lines (StableHLO), and normalize host-callback
    pointer identities. The historical test-pin strip, now in one
    place."""
    text = _METADATA_RE.sub("", text)
    text = _DEBUG_TABLE_RE.sub("", text)
    text = _LOC_INLINE_RE.sub("", text)
    text = _LOC_LINE_RE.sub("", text)
    ptrs = set(_CALLBACK_PTR_RE.findall(text))
    text = _CALLBACK_PTR_RE.sub('backend_config = "<host-callback>"',
                                text)
    for ptr in ptrs:
        text = text.replace(f"dense<{ptr}>", "dense<HOST_CALLBACK_PTR>")
    return text


def hlo_fingerprint(text: str) -> str:
    """sha256 of the canonical program text."""
    return hashlib.sha256(
        strip_hlo_metadata(text).encode("utf-8")).hexdigest()


def find_forbidden(text: str, markers: Sequence[str]) -> list:
    """The subset of ``markers`` present in the canonical text (order
    preserved, each reported once)."""
    canon = strip_hlo_metadata(text)
    return [m for m in markers if m in canon]


def assert_no_forbidden(text: str, markers: Sequence[str],
                        context: str = "program") -> None:
    """Raise AssertionError naming every forbidden marker found — the
    shared structural pin the tests and the ledger both call."""
    found = find_forbidden(text, markers)
    assert not found, (
        f"{context}: forbidden op marker(s) {found} present in the "
        f"lowering — a flag-off program must not contain them")


def markers_for(names: Iterable[str]) -> tuple:
    """Resolve symbolic marker-set names ('callbacks', 'collectives',
    'mg') to the concrete marker tuples — the ledger file stores the
    symbolic names so the marker vocabulary can evolve in one place."""
    table = {"callbacks": CALLBACK_MARKERS,
             "collectives": COLLECTIVE_MARKERS,
             "mg": MG_MARKERS}
    out: list = []
    for name in names:
        out.extend(table[name])
    return tuple(out)
