"""The MG solve split over a device mesh (``parallel.mg_sharded``).

- every legal replication level R on a 2x2 and a 4x1 mesh, at 400x600 and
  800x1200, two right-hand-side gates each: the iteration count of the
  one-device MG program within 1 and its field within ``SOLO_GAP``;
- against the benchmark's plain fp32 reference
  (``perf/reference/ellipse_mgpcg.py``) under the mesh configuration's
  limits;
- the rule that picks R, and its bounds;
- each shard's host blocks equal slices of the one-device
  ``build_hierarchy64``, and the replicated tail its levels;
- the gauges, the refusals, and the CLI's ``--preconditioner mg --mesh``.
"""

from __future__ import annotations

import functools
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from poisson_tpu import obs
from poisson_tpu.config import Problem
from poisson_tpu.mg import DEFAULT_MG, build_hierarchy64, plan_levels
from poisson_tpu.mg.hierarchy import mesh_hierarchy64, reset_hierarchy_cache
from poisson_tpu.obs import metrics
from poisson_tpu.parallel import make_solver_mesh
from poisson_tpu.parallel import mg_sharded
from poisson_tpu.solvers.pcg import host_fields64, pcg_solve

pytestmark = pytest.mark.mg

ROOT = pathlib.Path(__file__).resolve().parents[1]
CONFIG = json.loads((ROOT / "perf" / "configs" /
                     "ellipse-12800x19200-mg-mesh2x2.json").read_text())
GATES = (0.95, 1.05)
# The sharded and the one-device program do the same arithmetic on every
# node; only the order in which the psums add the shards' dots differs.
# The scaled fp32 MG field carries a rounding error of that order: 3.3e-4
# of max|w| at 800x1200 against the fp64 MG solve (PERF.md, MG's scaled
# fp32 field), and two summation orders may each land anywhere in it.
SOLO_GAP = 1e-3
CASES = [
    ((400, 600), (2, 2), 1), ((400, 600), (2, 2), 2),
    ((400, 600), (4, 1), 1), ((400, 600), (4, 1), 2),
    ((800, 1200), (2, 2), 1), ((800, 1200), (2, 2), 2),
    ((800, 1200), (2, 2), 3),
    ((800, 1200), (4, 1), 1), ((800, 1200), (4, 1), 2),
    ((800, 1200), (4, 1), 3),
]


def _mesh(grid):
    return make_solver_mesh(jax.devices()[:grid[0] * grid[1]], grid=grid)


def _deepest(M, N, grid):
    R = 1
    while True:
        try:
            mg_sharded.plan_mesh(Problem(M=M, N=N), *grid,
                                 replicated_from=R + 1)
        except ValueError:
            return R
        R += 1


def _gap(w, w_ref):
    w, w_ref = np.asarray(w, np.float64), np.asarray(w_ref, np.float64)
    return float(np.abs(w - w_ref).max() / np.abs(w_ref).max())


@functools.lru_cache(maxsize=None)
def _solo(M, N, gate):
    r = pcg_solve(Problem(M=M, N=N), dtype="float32", preconditioner="mg",
                  rhs_gate=gate)
    return int(r.iterations), np.asarray(r.w)


def test_cases_cover_every_legal_level():
    for shape, grid in {(c[0], c[1]) for c in CASES}:
        levels = {R for s, g, R in CASES if (s, g) == (shape, grid)}
        assert levels == set(range(1, _deepest(*shape, grid) + 1))


@pytest.mark.parametrize("shape,grid,R", CASES,
                         ids=[f"{s[0]}x{s[1]}-{g[0]}x{g[1]}-R{R}"
                              for s, g, R in CASES])
def test_sharded_matches_the_one_device_program(shape, grid, R):
    problem, mesh = Problem(M=shape[0], N=shape[1]), _mesh(grid)
    plan, (hier, rhs, aux) = mg_sharded.mesh_setup(
        problem, "float32", True, mesh, DEFAULT_MG, replicated_from=R)
    assert plan.replicated_from == R
    for gate in GATES:
        r = mg_sharded._solve_mg_sharded(problem, mesh, plan, DEFAULT_MG,
                                         True, hier, rhs * np.float32(gate),
                                         aux)
        k_solo, w_solo = _solo(*shape, gate)
        assert abs(int(r.iterations) - k_solo) <= 1
        assert _gap(r.w, w_solo) <= SOLO_GAP


@pytest.mark.parametrize("shape", [(400, 600), (800, 1200)])
def test_sharded_agrees_with_the_plain_reference(shape):
    from perf import compare, generate
    from perf.entry import load_module

    module = load_module(ROOT / "perf" / "reference" / "ellipse_mgpcg.py")
    problem = dict(CONFIG["problem"], M=shape[0], N=shape[1])
    ref = module.Reference(problem, CONFIG["reference_max_iter"], "float32")
    traffic = json.loads((ROOT / "perf" / "traffic" /
                          "closed-solo-mg-trace1s.json").read_text())
    mesh = _mesh((2, 2))
    for seed in (2**31 + 11, 5 * 2**32 + 3):
        gate = next(generate.gates(traffic["gates"], seed))
        r = pcg_solve(Problem(**problem), dtype="float32",
                      preconditioner="mg", rhs_gate=gate, mesh=mesh)
        w_ref, k_ref, _ = ref.solve(gate)
        assert (abs(int(r.iterations) - k_ref)
                <= CONFIG["limits"]["iters_gap"])
        assert (compare.field_gap(np.asarray(r.w), w_ref)
                <= CONFIG["limits"]["field_gap"])


def test_replication_rule():
    big = Problem(M=12800, N=19200)
    plan = mg_sharded.plan_mesh(big, 2, 2)
    mg = CONFIG["mg"]
    assert [list(d) for d in plan.dims] == mg["levels"]
    # Levels 0-2 are at least 32 MiB a grid (bandwidth-bound), level 3 is
    # not: the cycle leaves the shards there.
    assert plan.replicated_from == mg["replicated_from"] == 3
    assert list(plan.dims[3]) == mg["coarse_below"]
    assert (plan.m_blk, plan.n_blk) == (6400, 9600)
    # The level-7 blocks are 50x75: 75 does not halve.
    assert _deepest(12800, 19200, (2, 2)) == 7
    assert mg_sharded.plan_mesh(Problem(M=6400, N=9600), 2, 2
                                ).replicated_from == 2
    # Grids whose level 0 is not bandwidth-bound still shard it.
    assert mg_sharded.plan_mesh(Problem(M=400, N=600), 2, 2
                                ).replicated_from == 1
    for M, N, grid in ((402, 600, (4, 1)), (400, 602, (2, 4)),
                       (42, 40, (2, 4))):
        with pytest.raises(ValueError):
            mg_sharded.plan_mesh(Problem(M=M, N=N), *grid)
    with pytest.raises(ValueError):
        mg_sharded.plan_mesh(Problem(M=400, N=600), 2, 2, replicated_from=3)


@pytest.mark.parametrize("shape,grid,R", [((400, 600), (2, 2), 2),
                                          ((400, 600), (4, 1), 1),
                                          ((800, 1200), (2, 2), 3)])
def test_shard_blocks_are_slices_of_the_whole_hierarchy(shape, grid, R):
    problem = Problem(M=shape[0], N=shape[1])
    plan = mg_sharded.plan_mesh(problem, *grid, replicated_from=R)
    a64, b64, rhs64, aux64 = host_fields64(problem, True)
    whole = build_hierarchy64(problem, a64, b64, DEFAULT_MG)
    built = mesh_hierarchy64(problem, plan, True, DEFAULT_MG)

    def cut(u, lvl, px, py):
        m, n = plan.m_blk >> lvl, plan.n_blk >> lvl
        padded = np.pad(u, ((0, 1), (0, 1)))
        return padded[px * m:px * m + m + 2, py * n:py * n + n + 2]

    def owned(lvl, px, py):
        m, n = plan.m_blk >> lvl, plan.n_blk >> lvl
        M, N = plan.dims[lvl]
        i, j = np.arange(m + 2), np.arange(n + 2)
        rows = (i >= 1) & (i <= m) & (px * m + i <= M - 1)
        cols = (j >= 1) & (j <= n) & (py * n + j <= N - 1)
        return rows[:, None] & cols[None, :]

    for (px, py), fields in built["shards"].items():
        for lvl in range(R):
            a, b, dinv = fields["levels"][lvl]
            wa, wb, wdinv = whole["levels"][lvl]
            assert np.array_equal(a, cut(wa, lvl, px, py))
            assert np.array_equal(b, cut(wb, lvl, px, py))
            assert np.array_equal(dinv, cut(wdinv, lvl, px, py)
                                  * owned(lvl, px, py))
        mask = owned(0, px, py)
        assert np.array_equal(fields["rhs"], cut(rhs64, 0, px, py) * mask)
        assert np.array_equal(fields["aux"], cut(aux64, 0, px, py))
        assert np.array_equal(fields["scinv"],
                              cut(whole["scinv"], 0, px, py) * mask)
    for mine, theirs in zip(built["tail"], whole["levels"][R:],
                            strict=True):
        for x, y in zip(mine, theirs):
            assert np.array_equal(x, y)
    assert np.array_equal(built["coarse_inv"], whole["coarse_inv"])


def test_gauges_and_counters():
    reset_hierarchy_cache()
    metrics.reset()
    problem = Problem(M=400, N=600)
    r = pcg_solve(problem, dtype="float32", preconditioner="mg",
                  mesh=_mesh((2, 2)))
    snap = metrics.snapshot(rank=0)
    assert abs(int(r.iterations) - _solo(400, 600, 1.0)[0]) <= 1
    assert snap["gauges"]["mg.pallas_levels"] == 0
    assert snap["gauges"]["mg.replicated_from"] == 1
    assert snap["gauges"]["mg.levels"] == len(plan_levels(400, 600))
    assert snap["counters"]["mg.solves"] == 1
    assert snap["counters"]["mg.hierarchy_cache.misses"] == 1
    assert r.w.shape == (401, 601)


def test_mesh_refusals():
    problem, mesh = Problem(M=400, N=600), _mesh((2, 2))
    with pytest.raises(ValueError, match="pcg_solve_sharded"):
        pcg_solve(problem, dtype="float32", mesh=mesh)
    with pytest.raises(ValueError, match="verify_every"):
        pcg_solve(problem, dtype="float32", preconditioner="mg", mesh=mesh,
                  verify_every=5)
    with pytest.raises(ValueError, match="halve"):
        pcg_solve(Problem(M=42, N=40), dtype="float32",
                  preconditioner="mg", mesh=make_solver_mesh(
                      jax.devices()[:8], grid=(2, 4)))


def test_cli_runs_mg_on_a_mesh():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, "-m", "poisson_tpu", "400", "600",
         "--preconditioner", "mg", "--mesh", "2x2", "--dtype", "float32",
         "--json"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr
    record = json.loads(proc.stdout.strip().splitlines()[-1])
    assert record["backend"] == "sharded" and record["mesh"] == [2, 2]
    assert record["devices"] == 4
    assert abs(record["iterations"] - _solo(400, 600, 1.0)[0]) <= 1
    bad = subprocess.run(
        [sys.executable, "-m", "poisson_tpu", "42", "40",
         "--preconditioner", "mg"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert bad.returncode != 0 and "halve" in bad.stderr


def test_one_device_gauge_is_untouched():
    obs.gauge("mg.replicated_from", 0)
    pcg_solve(Problem(M=40, N=60), dtype="float32", preconditioner="mg")
    assert metrics.snapshot(rank=0)["gauges"]["mg.replicated_from"] == 0
