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
- the gauges, the refusals, and the CLI's ``--preconditioner mg --mesh``;
- the shards' levels on the strip kernels (``kernels_on_blocks``: the
  platform reads as a TPU, the size rule takes small blocks, the kernels
  run interpreted in strips of 16 rows): the rule, the 2-line ring
  exchange against slices of the whole grid, one cycle against the
  sharded XLA cycle, and whole solves against the one-device program.
"""

from __future__ import annotations

import functools
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from poisson_tpu import obs
from poisson_tpu.config import Problem
from poisson_tpu.mg import (
    DEFAULT_MG,
    MGConfig,
    build_hierarchy64,
    hierarchy,
    plan_levels,
    v_cycle,
)
from poisson_tpu.mg.hierarchy import (
    mesh_hierarchy64,
    mesh_kernel_levels,
    reset_hierarchy_cache,
)
from poisson_tpu.obs import metrics
from poisson_tpu.ops import pallas_mg
from poisson_tpu.parallel import make_solver_mesh
from poisson_tpu.parallel import mg_sharded
from poisson_tpu.parallel.halo import exchange_halos
from poisson_tpu.parallel.mesh import X_AXIS, Y_AXIS
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
    built = mesh_hierarchy64(problem, plan, True, DEFAULT_MG, strips=R)

    def cut(u, lvl, px, py):
        m, n = plan.m_blk >> lvl, plan.n_blk >> lvl
        padded = np.pad(u, ((0, 1), (0, 1)))
        return padded[px * m:px * m + m + 2, py * n:py * n + n + 2]

    def strip(u, lvl, px, py):
        """The block with a 2-line ring (zeros past the grid), transposed."""
        m, n = plan.m_blk >> lvl, plan.n_blk >> lvl
        padded = np.pad(u, 2)
        return padded[px * m + 1:px * m + m + 5, py * n + 1:py * n + n + 5].T

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
            # The strip-kernel blocks: every ring node as its owner has it,
            # dinv zero only off the grid's interior (as the whole grid's).
            sa, sb, sdinv = fields["strips"][lvl]
            assert np.array_equal(sa, strip(wa, lvl, px, py))
            assert np.array_equal(sb, strip(wb, lvl, px, py))
            assert np.array_equal(sdinv, strip(wdinv, lvl, px, py))
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


def test_mesh_kernel_levels_rule():
    """At 12800x19200 on 2x2 a chip holds 6400x9600 blocks: levels 0 and 1
    (246 and 61 MB) go on the strip kernels, level 2 (15 MB) does not; on
    the CPU, in bfloat16, or with more sweeps than the ring holds, none."""
    plan = mg_sharded.plan_mesh(Problem(M=12800, N=19200), 2, 2)
    assert mesh_kernel_levels("tpu", "float32", plan) == 2
    assert mesh_kernel_levels("cpu", "float32", plan) == 0
    assert mesh_kernel_levels("tpu", "bfloat16", plan) == 0
    assert mesh_kernel_levels("tpu", "float32", plan,
                              MGConfig(post_smooth=3)) == 0
    # Never past the sharded levels.
    small = mg_sharded.plan_mesh(Problem(M=6400, N=9600), 2, 2,
                                 replicated_from=1)
    assert mesh_kernel_levels("tpu", "float32", small) == 1


def _blocks(mesh, u, lvl_blk, ring):
    """The whole grid ``u`` cut into each shard's block with a ``ring``-line
    ring (zeros past the grid), as one (px·(m̂+2·ring), py·(n̂+2·ring))
    array over ``mesh``."""
    (m, n), (px, py) = lvl_blk, mesh.devices.shape
    padded = np.pad(u, ring)
    parts = [[padded[i * m + 1:i * m + 1 + m + 2 * ring,
                     j * n + 1:j * n + 1 + n + 2 * ring]
              for j in range(py)] for i in range(px)]
    return jax.device_put(np.block(parts),
                          NamedSharding(mesh, P(X_AXIS, Y_AXIS)))


def _exchanged(grid, ring, exchange):
    """A random whole grid with a zero Dirichlet ring, cut into each
    shard's block with a ``ring``-line ring that holds garbage, passed
    through ``exchange`` in ``shard_map``; and the blocks it should give."""
    M, N = 40, 60
    mesh = _mesh(grid)
    m, n = M // grid[0], N // grid[1]
    rng = np.random.default_rng(7)
    whole = np.pad(rng.standard_normal((M - 1, N - 1)), 1).astype(np.float32)
    want = _blocks(mesh, whole, (m, n), ring)
    owned = np.zeros((m + 2 * ring, n + 2 * ring), bool)
    owned[ring:-ring, ring:-ring] = True
    garbage = np.where(np.tile(owned, grid), np.asarray(want), 7.0)
    got = shard_map(
        exchange, mesh=mesh,
        in_specs=P(X_AXIS, Y_AXIS), out_specs=P(X_AXIS, Y_AXIS),
        check_vma=False)(jax.device_put(
            garbage.astype(np.float32),
            NamedSharding(mesh, P(X_AXIS, Y_AXIS))))
    return np.asarray(got), np.asarray(want), (m, n)


@pytest.mark.parametrize("grid", [(2, 2), (4, 1)])
@pytest.mark.parametrize("depth,inset", [(2, 0), (1, 1)])
def test_ring_exchange_equals_slices_of_the_whole_grid(grid, depth, inset):
    """Each shard's owned lines cut from a whole grid whose Dirichlet ring
    is zero, its 2-line ring filled with garbage: after the exchange, the
    refreshed lines (both, or the inner one) are the whole grid's, zeros
    past its edges, edge shards included."""
    got, want, (m, n) = _exchanged(grid, 2, lambda u: exchange_halos(
        u, *grid, depth, ((2, u.shape[0] - 2), (2, u.shape[1] - 2))))
    keep = np.zeros((m + 4, n + 4), bool)
    keep[inset:m + 4 - inset, inset:n + 4 - inset] = True
    keep = np.tile(keep, grid)
    np.testing.assert_array_equal(got[keep], want[keep])


@pytest.mark.parametrize("grid", [(2, 2), (4, 1)])
def test_default_exchange_refreshes_the_one_line_ring(grid):
    """The CG body's call, ``exchange_halos(u, px, py)`` on (m̂+2, n̂+2)
    blocks: the whole 1-line ring is the whole grid's, corners included,
    zeros past its edges."""
    got, want, _ = _exchanged(grid, 1, lambda u: exchange_halos(u, *grid))
    np.testing.assert_array_equal(got, want)


@pytest.fixture
def kernels_on_blocks(monkeypatch):
    """The hierarchies see a TPU and take level blocks of at least
    ``min_bytes`` (the test sets it) on the strip kernels, in strips of 16
    rows; the kernels run interpreted."""
    monkeypatch.setattr(hierarchy, "_platform", lambda: "tpu")
    monkeypatch.setattr(pallas_mg, "STRIP_VMEM",
                        16 * pallas_mg.STRIP_BUFFERS * 128 * 4)
    reset_hierarchy_cache()
    yield lambda min_bytes: monkeypatch.setattr(
        pallas_mg, "MIN_STRIP_LEVEL_BYTES", min_bytes)
    reset_hierarchy_cache()


# 80x120 on 2x2: 40x60 blocks (10,004 bytes), 20x30 at level 1 (2,604).
KERNEL_CASES = [(5000, 1), (0, 2)]


def _one_cycle(problem, mesh, plan, hier, r):
    """One V-cycle of the sharded solve's preconditioner on ``r`` (level-0
    blocks), with the kernel levels ``hier.strips`` holds."""
    kernel_levels = len(hier.strips)

    def fn(hier, r):
        grid = mg_sharded.ShardedGrid(problem, plan, kernel_levels)
        if kernel_levels:
            r = jnp.pad(r, 1)   # level 0's r comes padded to the kernels
        return v_cycle(hier, r, problem.h1, problem.h2, DEFAULT_MG,
                       kernel_levels, True, grid)

    return jax.jit(shard_map(
        fn, mesh=mesh,
        in_specs=(mg_sharded._hierarchy_specs(hier, plan),
                  P(X_AXIS, Y_AXIS)),
        out_specs=P(X_AXIS, Y_AXIS), check_vma=False))(hier, r)


@pytest.mark.parametrize("min_bytes,strips", KERNEL_CASES)
def test_sharded_cycle_on_the_kernels_matches_the_xla_cycle(
        kernels_on_blocks, min_bytes, strips):
    problem, mesh = Problem(M=80, N=120), _mesh((2, 2))
    # The XLA cycle's hierarchy first: no block is large enough for the
    # kernels, so it places every level's fields.
    kernels_on_blocks(1 << 62)
    plan, (xla, _, _) = mg_sharded.mesh_setup(
        problem, "float32", True, mesh, DEFAULT_MG, replicated_from=2)
    assert not xla.strips
    reset_hierarchy_cache()
    kernels_on_blocks(min_bytes)
    plan, (hier, _, _) = mg_sharded.mesh_setup(
        problem, "float32", True, mesh, DEFAULT_MG, replicated_from=2)
    assert len(hier.strips) == strips
    # Of the kernel levels' blocks only level 0's a and b are placed.
    assert [[f is None for f in hier.levels[lvl]] for lvl in range(strips)] \
        == [[False, False, True]] + [[True] * 3] * (strips - 1)
    rng = np.random.default_rng(11)
    whole = np.pad(rng.standard_normal((79, 119)), 1).astype(np.float32)
    r = _blocks(mesh, whole, (40, 60), 1)
    owned = np.tile(np.asarray(
        [[1 <= i <= 40 and 1 <= j <= 60 for j in range(62)]
         for i in range(42)]), (2, 2))
    r = r * owned
    got = np.asarray(_one_cycle(problem, mesh, plan, hier, r))
    want = np.asarray(_one_cycle(problem, mesh, plan, xla, r))
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    # Zero off the owned interior: the blocks' rings, and the grid's far
    # edge lines that the last shards hold.
    interior = owned & np.asarray(_blocks(mesh, np.pad(
        np.ones((79, 119)), 1), (40, 60), 1)).astype(bool)
    assert not got[~interior].any()


@pytest.mark.parametrize("min_bytes,strips", KERNEL_CASES)
def test_sharded_solve_on_the_kernels(kernels_on_blocks, min_bytes, strips):
    """The whole solve, as ``pcg_solve`` runs it over a mesh: the gauge
    reads the kernel levels, the count and field are the one-device
    program's."""
    kernels_on_blocks(min_bytes)
    metrics.reset()
    r = pcg_solve(Problem(M=80, N=120), dtype="float32",
                  preconditioner="mg", mesh=_mesh((2, 2)))
    assert metrics.snapshot(rank=0)["gauges"]["mg.pallas_levels"] == strips
    k_solo, w_solo = _solo(80, 120, 1.0)
    assert abs(int(r.iterations) - k_solo) <= 1
    assert _gap(r.w, w_solo) <= SOLO_GAP
