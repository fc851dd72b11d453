"""Pallas strip kernels for the V-cycle's bandwidth-bound levels.

On a large grid, the finest levels of ``mg.cycle.v_cycle`` are XLA passes
over the whole grid: a stencil fusion for each application of
``ops.stencil.apply_A``, then each damped-Jacobi update in a pass of its
own. The two kernels here do a level's smoothing and residual in one pass
over row strips each:

  ``mg_presmooth_residual`` — from the level's residual r:
      x ← ω·D⁻¹r                     (the closed-form first sweep from zero)
      ν₁ − 1 times  x ← x + ω·D⁻¹(r − Ax)
      res = r − Ax                    writes x and res;

  ``mg_postsmooth`` — from that x, the prolongated correction e and r:
      x ← x + e
      ν₂ times  x ← x + ω·D⁻¹(r − Ax)  writes x.

A is ``apply_A``'s formula (the same two-sided a/b differences and the
same h₁²/h₂² divisions, zero outside the interior) and the updates are
``mg.cycle.smooth_jacobi``'s, in fp32: only the order of fp32 rounding
may differ from the XLA cycle. Intermediate iterates never leave the
strip: each stencil application consumes one halo row on either side,
so every sweep is recomputed on the halo rows and ν ≤ HALO sweeps fit.

Strip layout
------------
The kernels read and write a level's (m+1, n+1) grids TRANSPOSED, as
(n+1, m+1) arrays: strip rows are grid columns and lanes are grid rows.
On a TPU, XLA lays the V-cycle's grids out with the row axis on the
vector lanes (``{0,1:T(8,128)}``), so the transpose is a relabelling of
the same bytes: the kernels' operands and results pass to and from the
neighbouring XLA ops (the ``√d`` multiply, the restriction, the
prolongation) with no copy. The coefficients are transposed once, with
the hierarchy (``mg.hierarchy.with_strips``).

Strip i writes rows [i·bm, (i+1)·bm) and reads the window of bm + 2·HALO
rows that starts HALO rows above them — strip 0, which has no row above,
the window that starts at row 0 — and lanes [0, C), C the lane count
rounded up to LANE. ``pl.Element`` windows padded past the array's far
edges let the last strip and the lanes run over: what lies outside the
array is undefined, and so is every value computed from it. Every ±1
neighbour is a rotation of the strip (``pltpu.roll``), which keeps each
value tile-aligned; what wraps round lands on the strip's first or last
row (halo, never written back) or on a lane outside the grid. The
operator is masked to the level's interior, so the grid's own values —
the Dirichlet ring included, where D⁻¹ is zero — never read an
undefined one.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from poisson_tpu.ops.pallas_cg import HALO, LANE, SUBLANE, named

# A strip's VMEM: the double-buffered in/out blocks plus the sweeps'
# intermediates, about STRIP_BUFFERS strip-heights of lanes.
STRIP_VMEM = 48 * 2 ** 20
STRIP_BUFFERS = 24
VMEM_LIMIT = 100 * 2 ** 20
# The smallest level grid the kernels take (levels 0 and 1 at 6400×9600,
# which a v5e ran faster on them; the published grids stay on XLA).
MIN_STRIP_LEVEL_BYTES = 32 * 2 ** 20


class StripGrid(NamedTuple):
    """A level's (m, n) grid and its strips: ``nb`` strips of ``bm``
    rows (grid columns), ``cols`` lanes (grid rows, rounded up)."""

    m: int
    n: int
    bm: int
    nb: int
    cols: int


def strip_grid(m: int, n: int, bm: int | None = None) -> StripGrid:
    """The strips of an (m+1, n+1) level grid. ``bm`` is the strip
    height: by default as many rows as the VMEM budget gives at this
    width, at most 128 and at least one sublane granule, never taller
    than the grid."""
    cols = -(-(m + 1) // LANE) * LANE
    if bm is None:
        rows = STRIP_VMEM // (STRIP_BUFFERS * cols * 4)
        rows = min(rows, 128, -(-(n + 1) // SUBLANE) * SUBLANE)
        bm = max(SUBLANE, rows // SUBLANE * SUBLANE)
    if bm <= 0 or bm % SUBLANE:
        raise ValueError(f"bm must be a positive multiple of {SUBLANE}, "
                         f"got {bm}")
    return StripGrid(m, n, bm, -(-(n + 1) // bm), cols)


def bandwidth_bound(m: int, n: int) -> bool:
    """Whether an (m+1, n+1) fp32 level is large enough for the strip
    kernels (``MIN_STRIP_LEVEL_BYTES``)."""
    return (m + 1) * (n + 1) * 4 >= MIN_STRIP_LEVEL_BYTES


def _spec(sg: StripGrid, rows: int, lead: int):
    """Strip i's ``rows``-row window from ``lead`` rows above its block
    (from row 0 for strip 0), padded past the array's far edges (module
    docstring)."""
    granules, lead_granules = sg.bm // SUBLANE, lead // SUBLANE
    end = max((sg.nb - 1) * sg.bm - lead, 0) + rows   # the last window's
    return pl.BlockSpec(
        (pl.Element(rows, (0, end - (sg.n + 1))),
         pl.Element(sg.cols, (0, sg.cols - (sg.m + 1)))),
        # The ×SUBLANE multiply outermost, for Mosaic's divisibility
        # prover (as ops.pallas_cg._strip_in_spec).
        lambda i: (SUBLANE * jnp.maximum(i * granules - lead_granules, 0),
                   0),
    )


def _first_row(sg: StripGrid):
    """The grid row (transposed) at the top of strip ``program_id(0)``'s
    window."""
    return jnp.maximum(pl.program_id(0) * sg.bm - HALO, 0)


def _level_operator(sg: StripGrid, h1: float, h2: float, a, b):
    """x ↦ Ax on one strip, zero outside the level's interior: the
    strip form of ``ops.stencil.apply_A`` (grid rows on the lanes, grid
    columns on the strip rows)."""
    rows = _first_row(sg) + lax.broadcasted_iota(
        jnp.int32, (sg.bm + 2 * HALO, 1), 0)
    lanes = lax.broadcasted_iota(jnp.int32, (1, sg.cols), 1)
    interior = (rows > 0) & (rows < sg.n) & (lanes > 0) & (lanes < sg.m)
    a_next, b_next = _next(a, 1), _next(b, 0)

    def apply(x):
        ax = (a_next * (_next(x, 1) - x)
              - a * (x - _prev(x, 1))) / (h1 * h1)
        ay = (b_next * (_next(x, 0) - x)
              - b * (x - _prev(x, 0))) / (h2 * h2)
        return jnp.where(interior, -(ax + ay), 0.0)

    return apply


def _next(u, axis: int):
    """u[k + 1] along ``axis``, wrapping round."""
    return pltpu.roll(u, u.shape[axis] - 1, axis)


def _prev(u, axis: int):
    """u[k − 1] along ``axis``, wrapping round."""
    return pltpu.roll(u, 1, axis)


def _check_sweeps(sweeps: int):
    if not 0 <= sweeps <= HALO:
        raise ValueError(f"the strip kernels take 0..{HALO} sweeps, "
                         f"got {sweeps}")


def _pallas_call(sg: StripGrid, kernel, name: str, inputs: int,
                 outputs: int, dtype, interpret: bool):
    strip = _spec(sg, sg.bm + 2 * HALO, HALO)
    block = _spec(sg, sg.bm, 0)
    shape = jax.ShapeDtypeStruct((sg.n + 1, sg.m + 1), dtype)
    return pl.pallas_call(
        kernel,
        grid=(sg.nb,),
        in_specs=[strip] * inputs,
        out_specs=[block] * outputs,
        out_shape=[shape] * outputs,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        **named(name),
    )


def _store(sg: StripGrid, ref, u):
    """Write the strip's own rows of the window value ``u``: its first
    bm rows on strip 0, else the bm rows after the HALO above."""
    first = pl.program_id(0) == 0

    @pl.when(first)
    def _():
        ref[:] = u[:sg.bm]

    @pl.when(jnp.logical_not(first))
    def _():
        ref[:] = u[HALO:HALO + sg.bm]


def mg_presmooth_residual(sg: StripGrid, r, a, b, dinv, h1: float,
                          h2: float, sweeps: int, omega: float, *,
                          interpret: bool):
    """(x, res), transposed: ``sweeps`` damped-Jacobi sweeps from zero
    on A x = r, and res = r − A x (module docstring). ``r``, ``a``,
    ``b`` and ``dinv`` are the level's grids, transposed."""
    _check_sweeps(sweeps)

    def kernel(r_ref, a_ref, b_ref, dinv_ref, x_ref, res_ref):
        A = _level_operator(sg, h1, h2, a_ref[:], b_ref[:])
        r = r_ref[:]
        wd = omega * dinv_ref[:]
        x = jnp.zeros_like(r) if sweeps == 0 else wd * r
        for _ in range(sweeps - 1):
            x = x + wd * (r - A(x))
        _store(sg, x_ref, x)
        _store(sg, res_ref, r - A(x))

    return _pallas_call(sg, kernel, "mg_presmooth_residual", 4, 2,
                        r.dtype, interpret)(r, a, b, dinv)


def mg_postsmooth(sg: StripGrid, x, e, r, a, b, dinv, h1: float,
                  h2: float, sweeps: int, omega: float, *,
                  interpret: bool):
    """x + e, then ``sweeps`` damped-Jacobi sweeps on A x = r, all
    transposed: ``x`` is :func:`mg_presmooth_residual`'s, ``e`` the
    prolongated correction, or None where ``x`` already holds it."""
    _check_sweeps(sweeps)
    fields = (x, r, a, b, dinv) if e is None else (x, e, r, a, b, dinv)

    def kernel(*refs):
        x_ref, *e_ref, r_ref, a_ref, b_ref, dinv_ref, out_ref = refs
        A = _level_operator(sg, h1, h2, a_ref[:], b_ref[:])
        r = r_ref[:]
        wd = omega * dinv_ref[:]
        x = x_ref[:] + e_ref[0][:] if e_ref else x_ref[:]
        for _ in range(sweeps):
            x = x + wd * (r - A(x))
        _store(sg, out_ref, x)

    (out,) = _pallas_call(sg, kernel, "mg_postsmooth", len(fields), 1,
                          r.dtype, interpret)(*fields)
    return out
