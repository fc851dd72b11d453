"""Distributed PCG: ``shard_map`` over a 2D device mesh.

TPU-native re-design of the reference's MPI solver
(``solve_mpi``, ``stage2-mpi/poisson_mpi_decomp.cpp:356-460``; CUDA variant
``gradient_solver_mpi``, ``stage4-mpi+cuda/poisson_mpi_cuda_f.cu:688-983``):

- one SPMD program over the mesh instead of per-rank processes;
- halo exchange = ``ppermute`` ICI shifts per iteration (parallel.halo);
- the 3 per-iteration ``MPI_Allreduce`` scalars (``stage2:…cpp:412,435,439``)
  become ``lax.psum`` over both mesh axes;
- the δ-convergence test stays *inside* the device-resident while_loop —
  every shard computes the same psum'd scalar, so all break together
  (the reference's synchronized termination, ``stage2:…cpp:437-448``) with
  no host round-trip per iteration, unlike stage4's host-synchronous loop.

Shard layout: the reference's ``decompose_2d`` balances blocks differing by
≤1 (``stage2:…cpp:75-111``); SPMD wants identical block shapes, so the
(M-1)×(N-1) interior is padded up to (Px·m̂)×(Py·n̂), m̂=⌈(M-1)/Px⌉, and padded
cells are masked out of every operator and reduction. Real cells adjacent to
the padding read zeros there — identical to the global Dirichlet condition.

Setup modes:
- ``setup='host'`` (default): fields built once on the host in fp64 (numpy)
  and sharded as halo-inclusive blocks — the reference's CPU-setup pattern
  (``stage4:…cu:717``), keeping setup precision independent of device dtype.
- ``setup='device'``: every shard builds its own coefficient block + halo
  ring locally from closed-form geometry (the vectorised ``fic_reg_local``,
  ``stage2:…cpp:124-170``) — no host memory, no transfer; setup precision
  follows the device dtype (fp64 only with x64).

Precision: like the single-device solver, sub-64-bit dtypes default to the
symmetrically-scaled system (unit-diagonal Ã = D^{-1/2}AD^{-1/2}) — plain CG
on it is iterate-identical to Jacobi-PCG but keeps fp32 viable at fine grids
(see ``solvers.pcg.scaled_single_device_ops``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from poisson_tpu.config import Problem
from poisson_tpu.models.fictitious_domain import coefficient_fields, rhs_field
from poisson_tpu.ops.stencil import apply_A, apply_Dinv, diag_D, pad_interior
from poisson_tpu.parallel.halo import exchange_halos
from poisson_tpu.parallel.mesh import X_AXIS, Y_AXIS, block_size
from poisson_tpu.solvers.pcg import (
    PCGOps,
    PCGResult,
    pcg_loop,
    resolve_dtype,
    resolve_scaled,
)
from jax import shard_map


def _owned_mask(problem: Problem, m_blk: int, n_blk: int, dtype):
    """Owned-interior mask for this shard: local ring excluded, padded
    global range excluded. Local index li ∈ 0..m̂+1 maps to global grid index
    gi = px·m̂ + li — the local↔global mapping of ``fic_reg_local``
    (``stage2:…cpp:124-170``)."""
    px = lax.axis_index(X_AXIS)
    py = lax.axis_index(Y_AXIS)
    gi = px * m_blk + jnp.arange(m_blk + 2)
    gj = py * n_blk + jnp.arange(n_blk + 2)
    own_i = (jnp.arange(m_blk + 2) >= 1) & (jnp.arange(m_blk + 2) <= m_blk)
    own_j = (jnp.arange(n_blk + 2) >= 1) & (jnp.arange(n_blk + 2) <= n_blk)
    in_i = (gi >= 1) & (gi <= problem.M - 1)
    in_j = (gj >= 1) & (gj <= problem.N - 1)
    mask = ((own_i & in_i)[:, None] & (own_j & in_j)[None, :]).astype(dtype)
    return mask, gi, gj


def _device_local_fields(problem: Problem, m_blk: int, n_blk: int, dtype,
                         scaled: bool):
    """On-device per-shard field build (setup='device')."""
    mask, gi, gj = _owned_mask(problem, m_blk, n_blk, dtype)
    a, b = coefficient_fields(problem, gi, gj, dtype)
    rhs = rhs_field(problem, gi, gj, dtype) * mask
    d = diag_D(a, b, problem.h1, problem.h2)
    if not scaled:
        # Padded to the full local grid so both setup modes hand _sharded_ops
        # the same aux layout (it re-slices the interior).
        return a, b, rhs, pad_interior(d), mask
    sc = pad_interior(1.0 / jnp.sqrt(d))
    rhs_scaled = rhs * sc
    return a, b, rhs_scaled, sc, mask


@functools.lru_cache(maxsize=8)
def _host_shard_blocks(problem: Problem, px_size: int, py_size: int,
                       m_blk: int, n_blk: int, dtype_name: str, scaled: bool):
    """Host fp64 field build sharded into stacked halo-inclusive blocks.

    Fields come from ``solvers.pcg.host_fields64`` (the shared setup
    derivation). Returns arrays of shape (Px·Py, m̂+2, n̂+2), leading axis in
    mesh order (x-major), to be consumed with in_specs=P(('x','y')).
    Cached so repeated solves pay for setup and transfer once.
    """
    from poisson_tpu.solvers.pcg import host_fields64

    dtype = jnp.dtype(dtype_name)
    a64, b64, rhs_use, aux64 = host_fields64(problem, scaled)

    gm = px_size * m_blk + 2
    gn = py_size * n_blk + 2

    def blocks(global_grid):
        full = np.zeros((gm, gn), np.float64)
        full[: global_grid.shape[0], : global_grid.shape[1]] = global_grid
        out = np.empty((px_size * py_size, m_blk + 2, n_blk + 2), np.float64)
        for px in range(px_size):
            for py in range(py_size):
                out[px * py_size + py] = full[
                    px * m_blk : px * m_blk + m_blk + 2,
                    py * n_blk : py * n_blk + n_blk + 2,
                ]
        return jnp.asarray(out, dtype)

    return blocks(a64), blocks(b64), blocks(rhs_use), blocks(aux64)


def _sharded_ops(problem: Problem, a, b, aux, mask, px_size: int,
                 py_size: int, scaled: bool) -> PCGOps:
    h1, h2 = problem.h1, problem.h2
    axes = (X_AXIS, Y_AXIS)

    def exchange(p):
        return exchange_halos(p, px_size, py_size)

    if scaled:
        sc = aux

        def op_apply_A(p):
            # Fold the halo refresh around the scaling: neighbours need the
            # *scaled* field sc·p, whose interior values they own.
            return apply_A(exchange(p * sc), a, b, h1, h2) * sc * mask

        op_dinv = lambda r: r  # unit diagonal after symmetric scaling
        op_sqnorm = lambda u: lax.psum(jnp.sum((u * sc) ** 2 * mask), axes)
        loop_exchange = lambda p: p
    else:
        d_int = aux[1:-1, 1:-1]

        def op_apply_A(p):
            return apply_A(p, a, b, h1, h2) * mask

        op_dinv = lambda r: apply_Dinv(r, d_int) * mask
        op_sqnorm = lambda u: lax.psum(jnp.sum(u * u * mask), axes)
        loop_exchange = exchange

    def dot(u, v):
        # At least one operand of every loop dot is masked (Ap, z, r),
        # so the plain local sum is the owned-interior sum.
        return lax.psum(jnp.sum(u * v), axes) * (h1 * h2)

    return PCGOps(
        apply_A=op_apply_A,
        apply_Dinv=op_dinv,
        dot=dot,
        sqnorm=op_sqnorm,
        exchange=loop_exchange,
    )


def _run_shard_batched(problem: Problem, a, b, rhs_stack, aux, mask,
                       px_size, py_size, scaled: bool):
    """The batch×mesh composition, per shard: the SAME masked vmapped
    body ``solvers.batched.pcg_loop_batched`` runs on every shard over
    a (B, m̂+2, n̂+2) stack of local RHS blocks — vmap INSIDE the shard
    is exactly "vmap outside shard_map" spelled SPMD: the mesh splits
    the grid, the batch axis rides whole on every device, and each
    member's psum'd reductions are per-member mesh scalars (the vmapped
    ``lax.psum`` reduces elementwise over the batch axis). Per-member
    convergence masking is untouched, so a member's stop flag and
    iteration count follow the exact batched-driver semantics; halo
    exchange and coefficient traffic are paid once per iteration for
    the whole batch (the amortization this composition exists for)."""
    from poisson_tpu.solvers.batched import pcg_loop_batched

    ops = _sharded_ops(problem, a, b, aux, mask, px_size, py_size, scaled)
    s = pcg_loop_batched(
        ops, rhs_stack,
        delta=problem.delta, max_iter=problem.iteration_cap,
        weighted_norm=problem.weighted_norm,
        h1=problem.h1, h2=problem.h2,
    )
    w = s.w * aux if scaled else s.w
    return w[:, 1:-1, 1:-1], s.k, s.diff, s.zr, s.flag


def shard_rhs_stack(rhs_stack, px_size: int, py_size: int, m_blk: int,
                    n_blk: int):
    """A (B, M+1, N+1) full-grid RHS stack as halo-inclusive per-shard
    blocks (Px·Py, B, m̂+2, n̂+2), leading axis in mesh order — the
    batched mirror of :func:`_host_shard_blocks`' layout, consumed with
    ``in_specs=P(('x','y'))``."""
    arr = np.asarray(rhs_stack)
    nb = arr.shape[0]
    gm = px_size * m_blk + 2
    gn = py_size * n_blk + 2
    full = np.zeros((nb, gm, gn), arr.dtype)
    full[:, : arr.shape[1], : arr.shape[2]] = arr
    out = np.empty((px_size * py_size, nb, m_blk + 2, n_blk + 2),
                   arr.dtype)
    for px in range(px_size):
        for py in range(py_size):
            out[px * py_size + py] = full[
                :,
                px * m_blk : px * m_blk + m_blk + 2,
                py * n_blk : py * n_blk + n_blk + 2,
            ]
    return jnp.asarray(out)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def solve_batched_sharded(problem: Problem, mesh: Mesh, dtype_name: str,
                          scaled: bool, a_blk, b_blk, rhs_blk, aux_blk):
    """One fused dispatch solving B right-hand sides on an N-device
    mesh (the ``solve_batched(mesh=)`` engine): compiled once per
    (bucket, grid, dtype, scaled, mesh shape) — coefficient blocks and
    the per-member RHS blocks are operands, so every padded request set
    of a bucket reuses the executable exactly like the single-device
    driver. Returns a batched :class:`PCGResult` (leading batch axis on
    ``w``/``iterations``/``diff``/``residual_dot``/``flag``)."""
    dtype = jnp.dtype(dtype_name)
    px_size = mesh.shape[X_AXIS]
    py_size = mesh.shape[Y_AXIS]
    m_blk = block_size(problem.M - 1, px_size)
    n_blk = block_size(problem.N - 1, py_size)

    def shard_fn(a, b, rhs, aux):
        a, b, aux = a[0], b[0], aux[0]
        rhs = rhs[0]                      # (B, m̂+2, n̂+2) local stack
        mask, _, _ = _owned_mask(problem, m_blk, n_blk, dtype)
        rhs = rhs * mask                  # broadcasts over the batch
        return _run_shard_batched(
            problem, a, b, rhs, aux, mask, px_size, py_size, scaled
        )

    spec = P((X_AXIS, Y_AXIS))
    w_int, k, diff, zr, flag = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(P(None, X_AXIS, Y_AXIS), P(), P(), P(), P()),
        check_vma=False,
    )(a_blk, b_blk, rhs_blk, aux_blk)
    w = jax.vmap(pad_interior)(
        w_int[:, : problem.M - 1, : problem.N - 1])
    return PCGResult(w=w, iterations=k, diff=diff, residual_dot=zr,
                     flag=flag, max_iterations=jnp.max(k))


def _run_shard(problem: Problem, a, b, rhs, aux, mask, px_size, py_size,
               scaled: bool):
    ops = _sharded_ops(problem, a, b, aux, mask, px_size, py_size, scaled)
    s = pcg_loop(
        ops, rhs,
        delta=problem.delta, max_iter=problem.iteration_cap,
        weighted_norm=problem.weighted_norm,
        h1=problem.h1, h2=problem.h2,
    )
    w = s.w * aux if scaled else s.w
    # Every shard returns its owned interior block; k/diff/zr/flag are
    # mesh-replicated scalars (the ops psum every reduction, so all shards
    # compute the same convergence/divergence verdict in step — the
    # reference's synchronized termination extended to failure modes).
    return w[1:-1, 1:-1], s.k, s.diff, s.zr, s.flag


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _solve_device_setup(problem: Problem, mesh: Mesh, dtype_name: str,
                        scaled: bool) -> PCGResult:
    dtype = jnp.dtype(dtype_name)
    px_size = mesh.shape[X_AXIS]
    py_size = mesh.shape[Y_AXIS]
    m_blk = block_size(problem.M - 1, px_size)
    n_blk = block_size(problem.N - 1, py_size)

    def shard_fn():
        a, b, rhs, aux, mask = _device_local_fields(
            problem, m_blk, n_blk, dtype, scaled
        )
        return _run_shard(
            problem, a, b, rhs, aux, mask, px_size, py_size, scaled
        )

    w_int, k, diff, zr, flag = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(),
        out_specs=(P(X_AXIS, Y_AXIS), P(), P(), P(), P()),
        check_vma=False,
    )()
    w = pad_interior(w_int[: problem.M - 1, : problem.N - 1])
    return PCGResult(w=w, iterations=k, diff=diff, residual_dot=zr, flag=flag)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _solve_host_setup(problem: Problem, mesh: Mesh, dtype_name: str,
                      scaled: bool, a_blk, b_blk, rhs_blk, aux_blk
                      ) -> PCGResult:
    dtype = jnp.dtype(dtype_name)
    px_size = mesh.shape[X_AXIS]
    py_size = mesh.shape[Y_AXIS]
    m_blk = block_size(problem.M - 1, px_size)
    n_blk = block_size(problem.N - 1, py_size)

    def shard_fn(a, b, rhs, aux):
        a, b = a[0], b[0]
        rhs, aux = rhs[0], aux[0]
        mask, _, _ = _owned_mask(problem, m_blk, n_blk, dtype)
        rhs = rhs * mask
        return _run_shard(
            problem, a, b, rhs, aux, mask, px_size, py_size, scaled
        )

    spec = P((X_AXIS, Y_AXIS))
    w_int, k, diff, zr, flag = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(P(X_AXIS, Y_AXIS), P(), P(), P(), P()),
        check_vma=False,
    )(a_blk, b_blk, rhs_blk, aux_blk)
    w = pad_interior(w_int[: problem.M - 1, : problem.N - 1])
    return PCGResult(w=w, iterations=k, diff=diff, residual_dot=zr, flag=flag)


def pcg_solve_sharded(problem: Problem, mesh: Mesh, dtype=None, scaled=None,
                      setup: str = "host") -> PCGResult:
    """Distributed solve over ``mesh`` (the stage2/3/4 workload, SURVEY §3.2-3.3).

    P=1 meshes reproduce the single-device path; any Px×Py works, matching
    the reference's size-agnostic MPI programs. See module docstring for
    ``setup`` and precision policy.
    """
    dtype_name = resolve_dtype(dtype)
    use_scaled = resolve_scaled(scaled, dtype_name)
    if setup == "device":
        return _solve_device_setup(problem, mesh, dtype_name, use_scaled)
    if setup != "host":
        raise ValueError(f"setup must be 'host' or 'device', got {setup!r}")
    px_size = mesh.shape[X_AXIS]
    py_size = mesh.shape[Y_AXIS]
    m_blk = block_size(problem.M - 1, px_size)
    n_blk = block_size(problem.N - 1, py_size)
    a_blk, b_blk, rhs_blk, aux_blk = _host_shard_blocks(
        problem, px_size, py_size, m_blk, n_blk, dtype_name, use_scaled
    )
    return _solve_host_setup(
        problem, mesh, dtype_name, use_scaled, a_blk, b_blk, rhs_blk, aux_blk
    )
