"""The one traffic generator: reads a traffic file's parameters and draws
every input from ``--seed``.

Right-hand-side gates (the entry's ``rhs_gate``, a scale of the
configuration's f) come from a pool, taken in cycles, each cycle a fresh
seeded permutation of the whole pool, so that a seed changes the order and
never the amount of work. The pool is a fixed geometric ladder, or, with
``"draw": "uniform"``, seeded uniform draws in the range that always hold
both of its ends: the slowest gate, and with it the work of a batch that
holds the whole pool, is then the same for every seed, while the inputs
differ from seed to seed.
"""

from __future__ import annotations

from typing import Iterator, List

import numpy as np

# Independent streams drawn from one seed.
STREAM_GATES, STREAM_POOL, STREAM_SAMPLE = 1, 2, 3


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**64 - 1), stream])


def pool(spec: dict, seed: int) -> List[float]:
    """``{"lo": a, "hi": b, "n": k}``: k gates spaced geometrically; with
    ``"draw": "uniform"``: a, b and k - 2 seeded uniform draws between."""
    lo, hi, n = float(spec["lo"]), float(spec["hi"]), int(spec["n"])
    draw = spec.get("draw", "ladder")
    if draw == "ladder":
        return [float(g) for g in np.geomspace(lo, hi, n)]
    if draw == "uniform" and n >= 2:
        inner = rng(seed, STREAM_POOL).uniform(lo, hi, n - 2)
        return [lo, hi] + [float(g) for g in inner]
    raise ValueError(f"no pool of {spec!r}")


def gates(spec: dict, seed: int) -> Iterator[float]:
    """The pool in seeded cycles, each a permutation of all of it."""
    values = pool(spec, seed)
    r = rng(seed, STREAM_GATES)
    while True:
        for i in r.permutation(len(values)):
            yield values[i]


class Reservoir:
    """A seeded uniform sample of ``k`` items from a stream of unknown
    length, held in O(k): which answers the check compares."""

    def __init__(self, k: int, seed: int):
        self.k = int(k)
        self.items: list = []
        self.seen = 0
        self._rng = rng(seed, STREAM_SAMPLE)

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
            return
        j = int(self._rng.integers(0, self.seen))
        if j < self.k:
            self.items[j] = item
