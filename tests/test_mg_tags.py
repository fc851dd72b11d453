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


def _sharded_text(R: int) -> str:
    import jax

    from poisson_tpu.parallel import make_solver_mesh, mg_sharded

    mesh = make_solver_mesh(jax.devices()[:4], grid=(2, 2))
    plan, (hier, rhs, aux) = mg_sharded.mesh_setup(
        SHARDED, "float32", True, mesh, DEFAULT_MG, replicated_from=R)
    return mg_sharded._solve_mg_sharded.lower(
        SHARDED, mesh, plan, DEFAULT_MG, True, hier, rhs,
        aux).compile().as_text()


# Blocks of 200x300 on a 2x2 mesh: two levels can stay sharded.
SHARDED = Problem(M=400, N=600)
_COLLECTIVE = re.compile(r" (collective-permute|all-gather|all-reduce)"
                         r"(?:-start)?\(")


@pytest.mark.parametrize("R", [1, 2])
def test_sharded_levels_and_their_halos_are_named(R):
    """In the MG solve over a 2x2 mesh every level carries its tag, and so
    does every halo permute of a sharded level (levels 0 … R−1) and the
    gather at the replication level R; the CG recurrence's own exchange
    and psums carry none."""
    text = _sharded_text(R)
    assert ({int(m) for m in _TAG.findall(text)}
            == set(range(len(plan_levels(SHARDED.M, SHARDED.N)))))
    found = {}
    for line in text.splitlines():
        op = _COLLECTIVE.search(line)
        if op and line.lstrip().startswith("%"):
            tag = _TAG.search(line)
            found.setdefault(op.group(1), []).append(
                None if tag is None else int(tag.group(1)))
    permutes = found["collective-permute"]
    # The CG body's exchange of p: one permute a direction.
    assert permutes.count(None) == 4
    assert {lvl for lvl in permutes if lvl is not None} == set(range(R))
    assert R in found["all-gather"]
    assert set(found["all-reduce"]) == {None}
