"""The work of a batch of multigrid-preconditioned CG solves on one
operator, from shapes alone.

Kept with the benchmark, beside ``perf/work_mg.py``, so that no change to
the program can move it. ``work_mg.mg_bytes_per_iteration`` counts one
solve's iteration: the CG state, each level's residual and correction,
and the coarsest level's dense inverse. In a batch the first two are each
member's own, while the inverse is one matrix that a correct batched
cycle reads once for all its members. So a batch moves, in the state's
precision:

- for each member-iteration, ``mg_bytes_per_iteration`` less the
  inverse;
- for each iteration of the batched loop, the inverse once.

No correct implementation of the same cycle moves fewer bytes, and no
share of the roofline built on this count can pass 100%.
"""

from __future__ import annotations

from perf import work_mg


def inverse_bytes(M: int, N: int, itemsize: int = 4) -> int:
    """Bytes of the coarsest level's dense inverse."""
    n = work_mg.coarsest_unknowns(M, N)
    return n * n * itemsize


def member_bytes_per_iteration(M: int, N: int, itemsize: int = 4) -> int:
    """Compulsory bytes of one member's iteration, the inverse left out."""
    return (work_mg.mg_bytes_per_iteration(M, N, itemsize)
            - inverse_bytes(M, N, itemsize))


def batch_bytes(M: int, N: int, member_iterations: int,
                loop_iterations: int, itemsize: int = 4) -> int:
    """Compulsory bytes of ``member_iterations`` member-iterations run in
    ``loop_iterations`` iterations of the batched loop."""
    return (member_iterations * member_bytes_per_iteration(M, N, itemsize)
            + loop_iterations * inverse_bytes(M, N, itemsize))
