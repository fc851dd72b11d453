"""Multigrid-preconditioned CG over a 2D device mesh.

The MG solve of ``mg.preconditioner._solve_mg`` split over a ``px × py``
mesh as one ``shard_map`` program, the weak-scaling layout of HPCG
(Heroux, Dongarra & Luszczek, SAND2013-8752: CG preconditioned by a
geometric V-cycle on a domain split over processes, with a halo exchange
on every level):

- the CG recurrence is ``parallel.pcg_sharded``'s: the same owned-mask
  and padding layout, halo ``ppermute``s before the operator, ``psum``
  dots, the convergence test on the device (``make_pcg_body`` /
  ``pcg_loop`` with one V-cycle as ``apply_Dinv``);
- levels ``0 … R−1`` of the cycle stay sharded: each shard smooths,
  forms its residual, restricts and prolongs its own block with
  ``mg.cycle``'s level operators, and refreshes the block's halo ring
  (``parallel.halo.exchange_halos``) before every stencil read: each
  smoothing sweep, the residual, the restriction's and the
  prolongation's edge reads (:class:`ShardedGrid`);
- at level R, the replication level, the level's right-hand side is
  gathered (two tiled ``all_gather``s); every device then runs the solo
  cycle's own code on the whole coarse grid down to the dense coarsest
  solve, and cuts its block of the correction back out.

R comes from :func:`plan_mesh`'s rule: the first level whose whole grid
is not bandwidth-bound on one chip (``ops.pallas_mg.bandwidth_bound``:
under 32 MiB in fp32; the levels from 32 MiB up ran faster on the strip
kernels on a v5e), since below it a level's latency-bound halo exchanges
cost more than computing the whole level on every chip; at least 1, and
never past the deepest level whose blocks all halve evenly (the coarse
blocks must tile the coarse grid).
At 12800×19200 on a 2×2 mesh (9 levels, 6400×9600 blocks) that is
R = 3: levels 0–2 (12800×19200 … 3200×4800, 983 MB … 61 MB a grid) stay
sharded and levels 3–8 (1600×2400 down to 50×75) run whole on every
chip; the deepest legal R there is 7 (the level-7 block, 50×75, does not
halve).

The strip kernels (``ops.pallas_mg``) stay on the one-device program:
their two sweeps a pass need a 2-deep halo, so the sharded levels run
XLA's level operators (``mg.pallas_levels`` reads 0 here).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from poisson_tpu.config import Problem
from poisson_tpu.mg.cycle import (
    prolong_bilinear,
    restrict_full_weighting,
    v_cycle,
)
from poisson_tpu.mg.hierarchy import (
    DEFAULT_MG,
    MGConfig,
    MGLevels,
    MeshPlan,
    mesh_hierarchy,
    validate_mg_problem,
)
from poisson_tpu.ops.stencil import pad_interior
from poisson_tpu.parallel.halo import exchange_halos
from poisson_tpu.parallel.mesh import X_AXIS, Y_AXIS
from poisson_tpu.parallel.pcg_sharded import _owned_mask, _sharded_ops
from poisson_tpu.solvers.pcg import PCGResult, pcg_loop


def plan_mesh(problem: Problem, px: int, py: int,
              config: MGConfig = DEFAULT_MG,
              replicated_from=None) -> MeshPlan:
    """The split of ``problem``'s MG solve over a ``px × py`` mesh, with
    R from the module docstring's rule, or ``replicated_from`` where
    given (1 … the deepest legal level). Raises ValueError for a grid
    the mesh does not divide into even blocks."""
    from poisson_tpu.ops.pallas_mg import bandwidth_bound

    dims = validate_mg_problem(problem, config)
    if problem.M % px or problem.N % py:
        raise ValueError(
            f"MG over a {px}x{py} mesh needs M divisible by {px} and N by "
            f"{py}: {problem.M}x{problem.N} is not")
    m_blk, n_blk = problem.M // px, problem.N // py
    deepest = 0
    while (deepest < len(dims) - 1 and not (m_blk >> deepest) % 2
           and not (n_blk >> deepest) % 2):
        deepest += 1
    if deepest < 1:
        raise ValueError(
            f"MG over a {px}x{py} mesh needs blocks that halve at least "
            f"once: {problem.M}x{problem.N} gives {m_blk}x{n_blk}")
    if replicated_from is None:
        rule = next((lvl for lvl, (m, n) in enumerate(dims)
                     if not bandwidth_bound(m, n)), len(dims) - 1)
        replicated_from = min(max(rule, 1), deepest)
    elif not 1 <= replicated_from <= deepest:
        raise ValueError(
            f"replicated_from must lie in 1..{deepest} for "
            f"{problem.M}x{problem.N} on a {px}x{py} mesh, got "
            f"{replicated_from}")
    return MeshPlan(dims=dims, px=px, py=py, m_blk=m_blk, n_blk=n_blk,
                    replicated_from=int(replicated_from))


def mesh_setup(problem: Problem, dtype_name: str, scaled: bool, mesh: Mesh,
               config: MGConfig = DEFAULT_MG, replicated_from=None):
    """(plan, (hierarchy, rhs, aux)) of an MG solve over ``mesh``: the
    plan of :func:`plan_mesh` and the cached blocks of
    ``mg.hierarchy.mesh_hierarchy``."""
    plan = plan_mesh(problem, mesh.shape[X_AXIS], mesh.shape[Y_AXIS],
                     config, replicated_from)
    return plan, mesh_hierarchy(problem, dtype_name, scaled, mesh, plan,
                                config)


class ShardedGrid:
    """The V-cycle's grid inside ``shard_map`` (``mg.cycle.WholeGrid``'s
    hooks): levels below ``plan.replicated_from`` as this shard's
    (m̂_l + 2, n̂_l + 2) blocks, whose halo ring ``exchange`` refreshes.

    - ``restrict``: the fine block's ring refreshed, one zero line added
      past its far edges, and the whole-grid full weighting; coarse
      node I of the block sits on fine node 2I of it, so its nine reads
      are the block's own rows 1 … m̂ + 1. Nodes off the coarse owned
      interior are zeroed.
    - ``prolong``: the coarse block's ring refreshed (a block cut from
      the replicated level already has it), bilinear interpolation, the
      fine block's rows and columns kept, nodes off its owned interior
      zeroed.
    - ``gather``/``scatter``: at the replication level, the owned
      interiors tiled into the whole grid on every device, and this
      device's block, ring included, cut from the whole correction."""

    def __init__(self, problem: Problem, plan: MeshPlan):
        self.problem = problem
        self.plan = plan
        self.replicated_from = plan.replicated_from

    def _block(self, lvl: int):
        return self.plan.m_blk >> lvl, self.plan.n_blk >> lvl

    def mask(self, lvl: int, dtype):
        """1 on this shard's owned interior of level ``lvl``, else 0."""
        M, N = self.plan.dims[lvl]
        return _owned_mask(self.problem.with_(M=M, N=N),
                           *self._block(lvl), dtype)[0]

    def exchange(self, u):
        return exchange_halos(u, self.plan.px, self.plan.py)

    def restrict(self, lvl: int, res):
        fine = jnp.pad(self.exchange(res), ((0, 1), (0, 1)))
        coarse = restrict_full_weighting(fine)
        return coarse * self.mask(lvl + 1, coarse.dtype)

    def prolong(self, lvl: int, ec):
        if lvl + 1 < self.replicated_from:
            ec = self.exchange(ec)
        m, n = self._block(lvl)
        e = prolong_bilinear(ec)[:m + 2, :n + 2]
        return e * self.mask(lvl, e.dtype)

    def gather(self, lvl: int, rl):
        whole = lax.all_gather(rl[1:-1, 1:-1], X_AXIS, axis=0, tiled=True)
        whole = lax.all_gather(whole, Y_AXIS, axis=1, tiled=True)
        # The tiles cover global rows 1 … M_l (M_l is the zeroed edge):
        # add the ring's row 0 and column 0.
        return jnp.pad(whole, ((1, 0), (1, 0)))

    def scatter(self, lvl: int, e):
        m, n = self._block(lvl)
        return lax.dynamic_slice(
            jnp.pad(e, ((0, 1), (0, 1))),
            (lax.axis_index(X_AXIS) * m, lax.axis_index(Y_AXIS) * n),
            (m + 2, n + 2))


def _hierarchy_specs(hier: MGLevels, plan: MeshPlan) -> MGLevels:
    """``shard_map`` specs of a :func:`mesh_hierarchy`: blocks above the
    replication level, whole grids from it down."""
    blocked, whole = P(X_AXIS, Y_AXIS), P()
    return MGLevels(
        levels=tuple((blocked if lvl < plan.replicated_from else whole,) * 3
                     for lvl in range(len(hier.levels))),
        coarse_inv=None if hier.coarse_inv is None else whole,
        scinv=blocked, strips=())


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _solve_mg_sharded(problem: Problem, mesh: Mesh, plan: MeshPlan,
                      config: MGConfig, scaled: bool, hier: MGLevels, rhs,
                      aux) -> PCGResult:
    """The sharded MG solve: ``rhs`` and ``aux`` (D^{-1/2} scaled, D
    unscaled) are level-0 blocks as ``hier``'s; the result is
    ``solvers.pcg._solve``'s, ``w`` on the whole (M+1, N+1) grid."""

    def shard_fn(hier, rhs, aux):
        grid = ShardedGrid(problem, plan)
        a, b, _ = hier.levels[0]
        ops = _sharded_ops(problem, a, b, aux, grid.mask(0, rhs.dtype),
                           plan.px, plan.py, scaled)
        h1, h2 = problem.h1, problem.h2
        if scaled:
            scinv = hier.scinv

            def precond(rt):
                return scinv * v_cycle(hier, scinv * rt, h1, h2, config,
                                       grid=grid)
        else:
            def precond(r):
                return v_cycle(hier, r, h1, h2, config, grid=grid)

        s = pcg_loop(
            ops._replace(apply_Dinv=precond), rhs,
            delta=problem.delta, max_iter=problem.iteration_cap,
            weighted_norm=problem.weighted_norm,
            h1=problem.h1, h2=problem.h2, preconditioner="mg",
        )
        w = s.w * aux if scaled else s.w
        return w[1:-1, 1:-1], s.k, s.diff, s.zr, s.flag

    blocked = P(X_AXIS, Y_AXIS)
    w_int, k, diff, zr, flag = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(_hierarchy_specs(hier, plan), blocked, blocked),
        out_specs=(blocked, P(), P(), P(), P()),
        check_vma=False,
    )(hier, rhs, aux)
    w = pad_interior(w_int[: problem.M - 1, : problem.N - 1])
    return PCGResult(w=w, iterations=k, diff=diff, residual_dot=zr,
                     flag=flag)
