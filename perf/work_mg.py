"""The work of one multigrid-preconditioned CG iteration, from shapes alone.

Kept with the benchmark, beside ``perf/work.py``, so that no change to the
program can move it. Each iteration has to move, in the state's precision:

- the CG state: w, r and p read and written once on the whole
  (M+1) x (N+1) grid (``work.cg_state_bytes_per_iteration``);
- on every level of the V-cycle, the level's residual read once and its
  correction written once, on that level's (M_l+1) x (N_l+1) grid;
- the coarsest level's dense inverse, read once.

Coefficients are not counted (a matrix-free kernel may compute them from
the ellipse), nor scalars and halos. So no correct implementation of the
same cycle moves fewer bytes, and no share of the roofline built on this
count can pass 100%.
"""

from __future__ import annotations

from perf import work

# The cycle's coarsening rule (the configuration's "mg" block states it):
# halve while both sides are even and the halved smaller side stays at or
# above MIN_SIZE, to at most MAX_LEVELS levels.
MIN_SIZE = 10
MAX_LEVELS = 16


def levels(M: int, N: int, min_size: int = MIN_SIZE,
           max_levels: int = MAX_LEVELS) -> list:
    """The (M_l, N_l) of every level, finest first."""
    dims = [(int(M), int(N))]
    while len(dims) < max_levels:
        m, n = dims[-1]
        if m % 2 or n % 2 or min(m, n) // 2 < min_size:
            break
        dims.append((m // 2, n // 2))
    return dims


def coarsest_unknowns(M: int, N: int) -> int:
    """Interior unknowns of the coarsest level: the dense inverse's side."""
    mc, nc = levels(M, N)[-1]
    return (mc - 1) * (nc - 1)


def mg_bytes_per_iteration(M: int, N: int, itemsize: int = 4) -> int:
    """Compulsory bytes one MG-preconditioned CG iteration moves."""
    transfers = sum(2 * (m + 1) * (n + 1) * itemsize
                    for m, n in levels(M, N))
    n = coarsest_unknowns(M, N)
    return (work.cg_state_bytes_per_iteration(M, N, itemsize) + transfers
            + n * n * itemsize)
