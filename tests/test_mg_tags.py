"""The V-cycle's level names in the compiled program.

``mg.cycle.v_cycle`` tags every op of level l with the frontend attribute
``mg_level="<l>"``; the compiled instructions keep it, and a TPU op event
carries it in its name, which is how the chip benchmark splits device time
by level (``perf/mg_trace.py``). The Jacobi programs carry no tag.
"""

from __future__ import annotations

import re

import pytest

from poisson_tpu.config import Problem
from poisson_tpu.mg import DEFAULT_MG, plan_levels

pytestmark = pytest.mark.mg

PROBLEM = Problem(M=200, N=300)
_TAG = re.compile(r'mg_level="(\d+)"')


def _compiled_text(preconditioner: str) -> str:
    from poisson_tpu.solvers.pcg import _solve, solve_setup

    if preconditioner == "mg":
        from poisson_tpu.mg.preconditioner import _solve_mg, mg_solve_setup

        args = mg_solve_setup(PROBLEM, "float32", True)
        lowered = _solve_mg.lower(PROBLEM, True, DEFAULT_MG, 0, 0, 0.0,
                                  *args)
    else:
        args = solve_setup(PROBLEM, "float32", True)
        lowered = _solve.lower(PROBLEM, True, 0, 0, 0.0, False, 0, *args)
    return lowered.compile().as_text()


@pytest.mark.parametrize("preconditioner", ["mg", "jacobi"])
def test_every_level_is_named_in_the_compiled_program(preconditioner):
    levels = {int(m) for m in _TAG.findall(_compiled_text(preconditioner))}
    if preconditioner == "jacobi":
        assert levels == set()
    else:
        # Every level, the coarsest (its dense solve) included.
        assert levels == set(range(len(plan_levels(PROBLEM.M, PROBLEM.N))))
