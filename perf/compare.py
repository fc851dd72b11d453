"""The comparison that decides ``correct``: each number the check computes
against the plain reference, beside the limit its configuration file
states for it. A run is correct when every number is at or under its
limit."""

from __future__ import annotations

import numpy as np


def field_gap(w, w_ref) -> float:
    """Widest gap of a solution field from the reference's, as a share of
    the reference's largest value."""
    w = np.asarray(w, np.float64)
    w_ref = np.asarray(w_ref, np.float64)
    if w.shape != w_ref.shape:
        return float("inf")
    scale = float(np.abs(w_ref).max())
    gap = float(np.abs(w - w_ref).max())
    if not np.isfinite(gap):
        return float("inf")
    return gap / scale if scale > 0 else gap


class Checks:
    """Numbers compared, each kept at its worst over the answers checked."""

    def __init__(self, limits: dict):
        self.limits = dict(limits)
        self.values: dict = {}

    def add(self, name: str, value: float) -> None:
        if name not in self.limits:
            raise KeyError(f"no limit for {name!r} in the configuration "
                           f"(limits: {sorted(self.limits)})")
        value = float(value)
        if np.isnan(value):
            value = float("inf")
        self.values[name] = max(self.values.get(name, value), value)

    @property
    def ok(self) -> bool:
        return bool(self.values) and all(
            v <= self.limits[k] for k, v in self.values.items())

    def report(self) -> dict:
        """``{name: {"value": v, "limit": l}}``; an infinite value (an
        answer that says nothing sound) is written as a large finite
        number, so that the line stays JSON."""
        return {k: {"value": (v if np.isfinite(v) else 1e300),
                    "limit": self.limits[k]}
                for k, v in self.values.items()}
