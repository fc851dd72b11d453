"""The geometric V-cycle: transfers, smoothing, and the cycle itself.

Everything here is pure traced jnp over the ``ops.stencil`` array
convention — full grids (…, M+1, N+1) with an identically-zero Dirichlet
ring — and batch-polymorphic the same way the stencil library is:
ellipsis indexing everywhere, so one implementation serves the solo
solve, the leading-batch-axis stacks, and ``vmap``-ed per-member bodies
(the batched/lane drivers) unchanged. The exception is the solo
program's largest levels, which smooth on the Pallas strip kernels of
``ops.pallas_mg`` (:func:`v_cycle`'s ``kernel_levels``).

The cycle reads its grid through a :class:`WholeGrid` (one device: the
whole grid, nothing to exchange). The sharded cycle
(``parallel.mg_sharded``) hands it per-shard blocks with a halo ring
instead, and the same level operators run on them.

The transfer pair is chosen for symmetry, not convenience: bilinear
prolongation P (coincident copy, ½ edges, ¼ centres) and full-weighting
restriction R (the 1/16·[1 2 1; 2 4 2; 1 2 1] stencil) satisfy
R = ¼·Pᵀ exactly, so the coarse-grid correction P·A_c⁻¹·R is symmetric
whenever A_c is — and weighted Jacobi is A-self-adjoint — making the
whole V-cycle an SPD operator that plain CG may precondition with
(Briggs/Henson/McCormick ch. 10, PAPERS.md).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax.experimental.xla_metadata import set_xla_metadata

from poisson_tpu.mg.hierarchy import DEFAULT_MG, MGConfig, MGLevels
from poisson_tpu.ops.pallas_mg import (
    mg_postsmooth,
    mg_presmooth_residual,
    strip_grid,
)
from poisson_tpu.ops.stencil import apply_A, pad_interior


def restrict_full_weighting(r):
    """Fine (…, M+1, N+1) → coarse (…, M/2+1, N/2+1) by the 9-point
    full-weighting stencil over interior coarse nodes (the ring stays
    zero). Coarse node (I, J) sits on fine node (2I, 2J); the stencil
    sums to 1, so the restricted residual keeps function-value
    semantics — the rediscretized coarse operator consumes it directly.

    The stencil is separable, 1/16·[1 2 1; 2 4 2; 1 2 1] = ¼[1 2 1]
    across the rows ⊗ ¼[1 2 1] along them, and is applied one axis at a
    time. Along the rows: the filter from contiguous shifted slices,
    then every other column (fine columns 2, 4, …, N-2) by one strided
    ``lax.slice``. Across them, on that half-width array: a reshape
    splits the rows into even and odd ones, and coarse row I is
    ¼(odd[I-1] + 2·even[I] + odd[I]) from contiguous slices. No gather
    (jnp's step indexing lowers to one, which a v5e runs at 0.24 GB/s)
    and no stride along the rows: XLA on a TPU lays these grids out with
    the row axis on the vector lanes, where a v5e ran a strided slice at
    about 4.5 G output elements a second (a sublane one near bandwidth)."""
    lead, row = r.shape[:-2], r.ndim - 2
    cols = 0.25 * (r[..., :-2] + 2.0 * r[..., 1:-1] + r[..., 2:])
    # cols[..., k] is fine column k + 1: keep k = 1, 3, …, N-3.
    half = lax.slice(cols, (0,) * row + (0, 1),
                     cols.shape[:-1] + (cols.shape[-1] - 1,),
                     (1,) * row + (1, 2))
    mc = (r.shape[-2] - 1) // 2
    pairs = lax.slice_in_dim(half, 0, 2 * mc, axis=row).reshape(
        lead + (mc, 2, half.shape[-1]))
    even = lax.index_in_dim(pairs, 0, axis=row + 1, keepdims=False)
    odd = lax.index_in_dim(pairs, 1, axis=row + 1, keepdims=False)
    return pad_interior(0.25 * (odd[..., :-1, :] + 2.0 * even[..., 1:, :]
                                + odd[..., 1:, :]))


def prolong_bilinear(e):
    """Coarse (…, Mc+1, Nc+1) → fine (…, 2Mc+1, 2Nc+1) by bilinear
    interpolation: coincident fine nodes copy, edge midpoints average
    their 2 coarse neighbours, cell centres their 4 (as the tensor
    product of two 1D linear interpolations — an interleave by
    stack+reshape, which XLA lowers as cheap concatenation where the
    equivalent strided ``.at[].set`` scatter costs ~50× on CPU). The
    coarse ring is zero, so fine near-boundary nodes interpolate
    against the Dirichlet value — the result's ring is zero by
    construction."""
    mid_r = 0.5 * (e[..., :-1, :] + e[..., 1:, :])
    rows = jnp.stack([e[..., :-1, :], mid_r], axis=-2)
    rows = rows.reshape(e.shape[:-2]
                        + (2 * (e.shape[-2] - 1), e.shape[-1]))
    ex = jnp.concatenate([rows, e[..., -1:, :]], axis=-2)
    mid_c = 0.5 * (ex[..., :, :-1] + ex[..., :, 1:])
    cols = jnp.stack([ex[..., :, :-1], mid_c], axis=-1)
    cols = cols.reshape(ex.shape[:-1] + (2 * (ex.shape[-1] - 1),))
    return jnp.concatenate([cols, ex[..., :, -1:]], axis=-1)


def _unchanged(u):
    return u


def smooth_jacobi(x, rhs, a, b, dinv, h1: float, h2: float,
                  sweeps: int, omega: float, from_zero: bool = False,
                  exchange=_unchanged):
    """``sweeps`` damped-Jacobi sweeps x ← x + ω·D⁻¹(rhs − Ax).

    ``dinv`` is the zero-ring-padded inverse diagonal, so the update is
    one fused elementwise expression and the ring stays untouched.
    ``from_zero`` starts from x = 0 and folds the first sweep into the
    cheap closed form ω·D⁻¹·rhs (no stencil application against a zero
    iterate). Unrolled: ``sweeps`` is a small static constant.
    ``exchange`` refreshes x's halo ring before each stencil read (a
    shard's block; on one device x is the whole grid and it is the
    identity)."""
    if from_zero:
        if sweeps <= 0:
            return jnp.zeros_like(rhs)
        x = omega * dinv * rhs
        sweeps -= 1
    for _ in range(sweeps):
        x = x + omega * dinv * (rhs - apply_A(exchange(x), a, b, h1, h2))
    return x


def coarse_solve(rhs, a, b, dinv, coarse_inv, h1: float, h2: float,
                 config: MGConfig):
    """The coarsest-level solve: the dense symmetrised inverse as one
    interior matvec when it was built (``coarse_dense_limit``), else
    ``coarse_sweeps`` smoother sweeps from zero. The matvec is
    deliberately a broadcast-multiply + trailing-axis reduce rather
    than a dot/einsum: XLA fuses it into one per-row accumulation loop
    whose order is the same in the solo program, under ``vmap`` (the
    batched/lane drivers), and inside any fusion context — a dot would
    dispatch to shape-dependent GEMV/GEMM kernels whose accumulation
    orders differ, and the bit-parity contract between the solo and
    batched MG solves (tests/test_mg.py) rests on this reduction."""
    if coarse_inv is None:
        return smooth_jacobi(None, rhs, a, b, dinv, h1, h2,
                             config.coarse_sweeps, config.omega,
                             from_zero=True)
    mc, nc = rhs.shape[-2] - 1, rhs.shape[-1] - 1
    flat = rhs[..., 1:-1, 1:-1].reshape(rhs.shape[:-2]
                                        + ((mc - 1) * (nc - 1),))
    e = jnp.sum(coarse_inv * flat[..., None, :], axis=-1)
    return pad_interior(e.reshape(rhs.shape[:-2] + (mc - 1, nc - 1)))


class WholeGrid:
    """Where the cycle's grids live on one device: each level whole, so
    nothing to exchange, and the transfers are the plain
    :func:`restrict_full_weighting` and :func:`prolong_bilinear`.

    The sharded cycle's grid (``parallel.mg_sharded.ShardedGrid``) keeps
    levels ``0 … replicated_from − 1`` as per-shard blocks with a halo
    ring: ``exchange`` refreshes a block's ring, ``restrict`` and
    ``prolong`` wrap the same transfers for blocks, and at level
    ``replicated_from`` ``gather`` assembles the whole grid on every
    device, where the rest of the cycle runs as here, and ``scatter``
    cuts each device's block of the correction back out.

    On the strip-kernel levels, ``strip_rhs`` gives the level's r as the
    kernels read it, ``strip_correction`` the kernels' x and e (the
    prolongated correction) for the post-smoother, and ``strip_result``
    what the level hands back; here the grids pass through as they are."""

    replicated_from = None
    exchange = staticmethod(_unchanged)

    @staticmethod
    def restrict(lvl: int, res):
        return restrict_full_weighting(res)

    @staticmethod
    def prolong(lvl: int, ec):
        return prolong_bilinear(ec)

    @staticmethod
    def strip_rhs(lvl: int, rl):
        return rl

    @staticmethod
    def strip_correction(lvl: int, xt, e):
        return xt, e.T

    @staticmethod
    def strip_result(lvl: int, x):
        return x


WHOLE = WholeGrid()


def v_cycle(hier: MGLevels, r, h1: float, h2: float,
            config: MGConfig = DEFAULT_MG, kernel_levels: int = 0,
            interpret: bool = False, grid=WHOLE):
    """One V(ν₁, ν₂) cycle applied to the residual ``r``: z ≈ A⁻¹r.

    Python recursion over the static level tuple — the cycle unrolls at
    trace time (≤ ~7 levels for every supported grid). ``h1``/``h2``
    are the finest spacings; each level doubles them. Symmetric by
    construction (module docstring), so the result is an SPD
    preconditioner application for the outer CG.

    The first ``kernel_levels`` levels (a trace-time constant; their
    transposed fields in ``hier.strips``) smooth and form their residual
    on the Pallas strip kernels (``ops.pallas_mg``) instead of
    :func:`smooth_jacobi` and ``apply_A``: the same arithmetic, two
    passes over the level. ``interpret`` runs those kernels in the
    Pallas interpreter (off a TPU).

    ``grid`` says where each level lives (:class:`WholeGrid`): on one
    device, whole; in ``shard_map``, as halo-ringed blocks down to
    ``grid.replicated_from``, where the cycle leaves the shards and every
    device runs the rest of it on the whole coarse grid. A sharded
    level's kernels read its blocks with the wider ring the grid's
    ``strip_*`` hooks give them.

    Every op of level l carries the frontend attribute
    ``mg_level="<l>"`` (smoothing, residual, the restriction out of l and
    the prolongation into l with their halo exchanges, the gather and
    scatter at the replication level, the strip kernels and their ring
    exchanges; the coarsest solve carries the last level's): the
    compiled instructions keep it, so a device trace can split the
    cycle's time by level."""
    levels = hier.levels

    def cycle(lvl: int, rl, grid):
        if lvl == grid.replicated_from:
            with set_xla_metadata(mg_level=str(lvl)):
                whole = grid.gather(lvl, rl)
            e = cycle(lvl, whole, WHOLE)
            with set_xla_metadata(mg_level=str(lvl)):
                return grid.scatter(lvl, e)
        a, b, dinv = levels[lvl]
        h1l, h2l = h1 * (1 << lvl), h2 * (1 << lvl)
        on_strips = lvl < kernel_levels
        exchange = grid.exchange
        with set_xla_metadata(mg_level=str(lvl)):
            if lvl == len(levels) - 1:
                return coarse_solve(rl, a, b, dinv, hier.coarse_inv,
                                    h1l, h2l, config)
            if on_strips:
                # The kernels take the grids transposed (ops.pallas_mg).
                rs = grid.strip_rhs(lvl, rl)
                sg = strip_grid(rs.shape[-2] - 1, rs.shape[-1] - 1)
                sa, sb, sdinv = hier.strips[lvl]
                xt, res = mg_presmooth_residual(
                    sg, rs.T, sa, sb, sdinv, h1l, h2l, config.pre_smooth,
                    config.omega, interpret=interpret)
                res = res.T
            else:
                x = smooth_jacobi(None, rl, a, b, dinv, h1l, h2l,
                                  config.pre_smooth, config.omega,
                                  from_zero=True, exchange=exchange)
                res = rl - apply_A(exchange(x), a, b, h1l, h2l)
            rc = grid.restrict(lvl, res)
        ec = cycle(lvl + 1, rc, grid)
        with set_xla_metadata(mg_level=str(lvl)):
            e = grid.prolong(lvl, ec)
            if on_strips:
                xt, et = grid.strip_correction(lvl, xt, e)
                return grid.strip_result(lvl, mg_postsmooth(
                    sg, xt, et, rs.T, sa, sb, sdinv, h1l, h2l,
                    config.post_smooth, config.omega,
                    interpret=interpret).T)
            return smooth_jacobi(x + e, rl, a, b, dinv, h1l, h2l,
                                 config.post_smooth, config.omega,
                                 exchange=exchange)

    return cycle(0, r, grid)
