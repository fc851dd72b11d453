"""The work a cell must do, computed from shapes alone.

Kept with the benchmark so that no change to the program can move it. The
unit is the compulsory HBM traffic of the CG state: each iteration has to
read and write w, r and p once, on the whole (M+1) x (N+1) grid, in the
state's precision. Coefficients are not counted, because a matrix-free
kernel may compute them from the ellipse; scalars and halos are not
counted either. So no correct implementation can move fewer bytes, and no
share of the roofline built on this count can pass 100%.
"""

from __future__ import annotations

# w, r and p, each read once and written once.
STATE_PASSES = 6


def cg_state_bytes_per_iteration(M: int, N: int, itemsize: int = 4) -> int:
    """Compulsory bytes one CG iteration moves for one right-hand side."""
    if M < 2 or N < 2 or itemsize < 1:
        raise ValueError(f"no grid {M}x{N} with itemsize {itemsize}")
    return STATE_PASSES * (M + 1) * (N + 1) * itemsize


def least_seconds(total_bytes: float, bytes_per_s: float) -> float:
    """The least time a chip with bandwidth ``bytes_per_s`` needs."""
    return total_bytes / bytes_per_s


class UnknownDevice(LookupError):
    """A device kind with no published peak in ``perf/peaks.json``."""


def peak(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    import json
    import pathlib

    table = json.loads((pathlib.Path(__file__).with_name("peaks.json"))
                       .read_text())
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"perf/peaks.json (known: {sorted(table)})")
    return table[device_kind]
