"""The multigrid-preconditioned solve against the benchmark's plain
reference (``perf/reference/ellipse_mgpcg.py``), at grids that coarsen to
the same 50x75 coarsest level as the chip configuration
``ellipse-6400x9600-mg``, on seeded right-hand-side gates from that cell's
traffic pool.

- ``pcg_solve(preconditioner="mg", dtype="float32")`` agrees with the fp32
  reference: iteration counts within 1 and ``field_gap`` under the
  configuration's limit;
- the MG reference agrees with the Jacobi reference (the same discrete
  problem) within the error the stopping rule leaves (``_stop_error``);
- the MG reference computed in bfloat16 fails the configuration's limits.
"""

from __future__ import annotations

import functools
import json
import pathlib

import numpy as np
import pytest

from perf import compare, generate
from perf.entry import load_module

pytestmark = pytest.mark.mg

ROOT = pathlib.Path(__file__).resolve().parents[1]
CONFIG = json.loads(
    (ROOT / "perf" / "configs" / "ellipse-6400x9600-mg.json").read_text())
TRAFFIC = json.loads(
    (ROOT / "perf" / "traffic" / "closed-solo-mg.json").read_text())
LIMITS = CONFIG["limits"]
GRIDS = [(200, 300), (400, 600)]
SEEDS = [2**31 + 11, 5 * 2**32 + 3]


def _problem(M, N):
    return dict(CONFIG["problem"], M=M, N=N)


@functools.lru_cache(maxsize=None)
def _reference(name, M, N, dtype, max_iter):
    module = load_module(ROOT / "perf" / "reference" / f"{name}.py")
    return module.Reference(_problem(M, N), max_iter, dtype)


def _gate(seed):
    """The seeded cell's first gate."""
    return next(generate.gates(TRAFFIC["gates"], seed))


def _stop_error(k_jacobi, w_ref):
    """The widest error the Jacobi stopping rule leaves, as a share of
    max|w|. It stops once the last update is under delta in the weighted
    norm sqrt(h1 h2 sum(.^2)), i.e. once its root-mean-square over the
    box (area |Omega| = 2 x 1.2) is under delta / sqrt(|Omega|). Updates
    shrink step by step, so the ones it leaves out add up to no more than
    that bound times the steps it took, k_jacobi. The MG solve stops
    within a few of its own (much smaller) steps of the same solution."""
    p = CONFIG["problem"]
    area = (p["x_max"] - p["x_min"]) * (p["y_max"] - p["y_min"])
    return k_jacobi * p["delta"] / np.sqrt(area) / np.abs(w_ref).max()


@pytest.mark.parametrize("M,N", GRIDS, ids=[f"{m}x{n}" for m, n in GRIDS])
@pytest.mark.parametrize("seed", SEEDS)
def test_mg_solve_against_the_reference(M, N, seed):
    from poisson_tpu.config import Problem
    from poisson_tpu.solvers.pcg import pcg_solve

    gate = _gate(seed)
    max_iter = CONFIG["reference_max_iter"]
    w_ref, k_ref, diff_ref = _reference("ellipse_mgpcg", M, N, "float32",
                                        max_iter).solve(gate)
    assert diff_ref < CONFIG["problem"]["delta"] and k_ref < max_iter

    result = pcg_solve(Problem(**_problem(M, N)), dtype="float32",
                       rhs_gate=gate, preconditioner="mg")
    w = np.asarray(result.w, np.float64)
    assert abs(int(result.iterations) - k_ref) <= 1
    assert compare.field_gap(w, w_ref) <= LIMITS["field_gap"]

    # The same discrete problem, solved by Jacobi-PCG.
    w_jac, k_jac, _ = _reference("ellipse_pcg", M, N, "float32",
                                 20 * M).solve(gate)
    assert compare.field_gap(w_ref, w_jac) <= _stop_error(k_jac, w_jac)

    # A precision below the configuration's fails one of its limits.
    w_bf, k_bf, _ = _reference("ellipse_mgpcg", M, N, "bfloat16",
                               max_iter).solve(gate)
    assert (abs(k_bf - k_ref) > LIMITS["iters_gap"]
            or compare.field_gap(w_bf, w_ref) > LIMITS["field_gap"])
