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

On a TPU in fp32, the sharded levels whose blocks are bandwidth-bound
smooth on the strip kernels of ``ops.pallas_mg``, as the one-device
program's large levels do (``mg.hierarchy.mesh_kernel_levels``: the
one-device rule read on a chip's blocks; levels 0–1 at 12800×19200 on
2×2, ``mg.pallas_levels`` reads that count). Their two stencil reads a
pass reach two lines past a block, so these levels hold their
kernel-side fields as blocks with a 2-line ring (``STRIP_RING``),
transposed as the kernels read them, with D⁻¹ zero off the grid's
interior rather than the shard's: a ring node holds what its owner
computes there. r's ring is refreshed once before the pre-smoother and
x + e's once before the post-smoother (``halo.exchange_halos``), where
the XLA level operators refresh a 1-line ring before every stencil
read. Treated as a grid of its own, the 2-ringed block's outermost ring
is what the kernels' operator mask drops: x comes out right on the inner
ring line and on the owned nodes, the residual on the owned nodes.
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
    STRIP_RING,
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
      device's block, ring included, cut from the whole correction.

    The first ``kernel_levels`` levels run on the strip kernels, on
    (m̂_l + 4, n̂_l + 4) blocks with a ``STRIP_RING``-line ring
    (module docstring): the block's own ring is the inner ring line.

    - ``strip_rhs``: r's 2-line ring refreshed (r comes padded to it);
    - ``restrict`` takes the kernel's residual with its inner ring line
      refreshed, and ``prolong`` a level's result likewise; ``restrict``
      into a kernel level gives r padded to the ring;
    - ``strip_correction``: x + e, its 2-line ring refreshed, as the
      post-smoother's x (and no separate e);
    - ``strip_result``: level 0 hands CG its block, zero off the owned
      interior; a coarser level hands ``prolong`` the kernel's block."""

    def __init__(self, problem: Problem, plan: MeshPlan,
                 kernel_levels: int = 0):
        self.problem = problem
        self.plan = plan
        self.replicated_from = plan.replicated_from
        self.kernel_levels = kernel_levels

    def _block(self, lvl: int):
        return self.plan.m_blk >> lvl, self.plan.n_blk >> lvl

    def mask(self, lvl: int, dtype):
        """1 on this shard's owned interior of level ``lvl``, else 0."""
        M, N = self.plan.dims[lvl]
        return _owned_mask(self.problem.with_(M=M, N=N),
                           *self._block(lvl), dtype)[0]

    def exchange(self, u):
        return exchange_halos(u, self.plan.px, self.plan.py)

    def _ring(self, u, depth: int):
        """Refresh the inner ``depth`` lines of a kernel level's 2-line
        ring."""
        return exchange_halos(u, self.plan.px, self.plan.py, depth,
                              tuple((STRIP_RING, n - STRIP_RING)
                                    for n in u.shape))

    def restrict(self, lvl: int, res):
        mask = self.mask(lvl + 1, res.dtype)
        if lvl + 1 < self.kernel_levels:
            # The coarse level's r padded to its kernels' ring: the fine
            # block padded to start two lines before the coarse ring's
            # outer line (a TPU fuses a pad into the op that reads it).
            # Only the coarse ring reads past the block, and the ring
            # exchange overwrites it.
            fine = jnp.pad(self._ring(res, 1), ((1, 2), (1, 2)))
            mask = jnp.pad(mask, 1)
        elif lvl < self.kernel_levels:
            # The block and, past its far edges, the outer ring line,
            # which only the coarse ring reads.
            fine = self._ring(res, 1)[1:, 1:]
        else:
            fine = jnp.pad(self.exchange(res), ((0, 1), (0, 1)))
        return restrict_full_weighting(fine) * mask

    def prolong(self, lvl: int, ec):
        if lvl + 1 < self.kernel_levels:
            ec = self._ring(ec, 1)[1:-1, 1:-1]
        elif lvl + 1 < self.replicated_from:
            ec = self.exchange(ec)
        m, n = self._block(lvl)
        e = prolong_bilinear(ec)[:m + 2, :n + 2]
        return e * self.mask(lvl, e.dtype)

    def strip_rhs(self, lvl: int, rl):
        # rl arrives padded to the ring: level 0's from the
        # preconditioner (_solve_mg_sharded), a coarser level's from
        # ``restrict``.
        return self._ring(rl, STRIP_RING)

    def strip_correction(self, lvl: int, xt, e):
        x = xt.T + jnp.pad(e, 1)
        return self._ring(x, STRIP_RING).T, None

    def strip_result(self, lvl: int, x):
        if lvl:
            return x
        return x[1:-1, 1:-1] * self.mask(0, x.dtype)

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
    replication level, whole grids from it down, None where it places
    no block."""
    blocked, whole = P(X_AXIS, Y_AXIS), P()
    return MGLevels(
        levels=tuple(tuple(None if f is None else
                           blocked if lvl < plan.replicated_from else whole
                           for f in fields)
                     for lvl, fields in enumerate(hier.levels)),
        coarse_inv=None if hier.coarse_inv is None else whole,
        scinv=blocked,
        strips=tuple((P(Y_AXIS, X_AXIS),) * 3 for _ in hier.strips))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4),
                   static_argnames=("interpret",))
def _solve_mg_sharded(problem: Problem, mesh: Mesh, plan: MeshPlan,
                      config: MGConfig, scaled: bool, hier: MGLevels, rhs,
                      aux, interpret: bool = False) -> PCGResult:
    """The sharded MG solve: ``rhs`` and ``aux`` (D^{-1/2} scaled, D
    unscaled) are level-0 blocks as ``hier``'s; the result is
    ``solvers.pcg._solve``'s, ``w`` on the whole (M+1, N+1) grid. The
    levels ``hier.strips`` holds run on the strip kernels, in the
    interpreter when ``interpret``."""

    def shard_fn(hier, rhs, aux):
        kernel_levels = len(hier.strips)
        grid = ShardedGrid(problem, plan, kernel_levels)
        a, b, _ = hier.levels[0]
        ops = _sharded_ops(problem, a, b, aux, grid.mask(0, rhs.dtype),
                           plan.px, plan.py, scaled)
        h1, h2 = problem.h1, problem.h2

        def cycle(r):
            return v_cycle(hier, r, h1, h2, config, kernel_levels,
                           interpret, grid)

        def pad(u):
            # Level 0 on the strip kernels takes r padded to its ring. A
            # TPU fuses a pad into the op that reads it, not into the one
            # that makes its operand: padding the factors of √d·r makes
            # the padded product in one pass over the block.
            return jnp.pad(u, 1) if kernel_levels else u

        if scaled:
            scinv = hier.scinv

            def precond(rt):
                return scinv * cycle(pad(scinv) * pad(rt))
        else:
            def precond(r):
                return cycle(pad(r))

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
