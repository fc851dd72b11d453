"""VMEM-resident persistent-kernel CG: the whole solve in ONE kernel.

The fused 2-sweep path (``ops.pallas_cg``) already collapses the
reference's ~10 HBM array passes per iteration to ~2, but every
iteration still streams the working set from HBM and launches two
kernels; at the small published grids (40×40, 400×600 —
``stage0/Withoutopenmp1.cpp:176-196``, ``stage1-openmp/Withopenmp2.cpp``)
the working set fits in a TensorCore's ~16 MB VMEM outright. This
module keeps ALL solver state resident in VMEM for the entire solve:

  one ``pallas_call``, no grid: load cs/cw/γ/b̃/sc² once, run the full
  PCG loop as an in-kernel ``lax.while_loop`` (scalar carries k/done/
  ζ/β/diff; array state in VMEM refs), store the solution canvas and
  the iteration count/convergence scalars at the end.

Per-iteration HBM traffic: **zero**. Kernel launches for a 546-iteration
solve: **one** (vs ~1,092 on the 2-sweep path, ~3,800 in the
reference's stage4 with its per-launch ``cudaDeviceSynchronize``,
``stage4-mpi+cuda/poisson_mpi_cuda_f.cu:847-941``). The arithmetic is
identical to the fused path (difference-form stencil on the
symmetrically-scaled system, module doc of ``ops.pallas_cg``), so the
golden iteration counts are reproduced exactly; only the reduction
order differs (whole-array sums instead of per-strip partials).

Capacity: 8 live canvases (5 inputs, solution, r, p) plus compiler
temporaries must fit in VMEM — grids up to roughly 400×600 (the
largest small-tier published grid) qualify; :func:`fits_resident`
gates, and bigger grids belong to the streaming paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from poisson_tpu.config import Problem
from poisson_tpu.ops.pallas_cg import (
    HALO,
    SUBLANE,
    Canvas,
    _shift_col_minus,
    _shift_col_plus,
    build_canvases,
    named,
)
from poisson_tpu.solvers.pcg import PCGResult, _DENOM_TOL

# Live canvases (5 in + w + r + p) plus headroom for the stencil's
# shifted temporaries and ap; measured against the physical ~16 MB/core.
_EQUIV_ARRAYS = 12
_VMEM_BYTES = 15 * 2 ** 20


def _row_minus(u):
    """u[i-1, :] with a zero row shifted in (no wraparound)."""
    return jnp.concatenate([jnp.zeros_like(u[:1, :]), u[:-1, :]], axis=0)


def _row_plus(u):
    """u[i+1, :] with a zero row shifted in."""
    return jnp.concatenate([u[1:, :], jnp.zeros_like(u[:1, :])], axis=0)


def resident_canvas(problem: Problem) -> Canvas:
    """Single-strip canvas covering the whole interior (nb = 1)."""
    bm = max(SUBLANE, -(-(problem.M - 1) // SUBLANE) * SUBLANE)
    from poisson_tpu.ops.pallas_cg import canvas_cols

    cols = canvas_cols(problem)
    return Canvas(bm=bm, nb=1, rows=bm + 2 * HALO, cols=cols)


def fits_resident(problem: Problem) -> bool:
    cv = resident_canvas(problem)
    return _EQUIV_ARRAYS * cv.rows * cv.cols * 4 <= _VMEM_BYTES


def _make_resident_kernel(problem: Problem, cap: int):
    # Plain Python floats: they inline as literals at trace time (jnp
    # scalars made outside the kernel would be captured constants, which
    # pallas_call rejects).
    h1h2 = float(problem.h1 * problem.h2)
    norm_w = h1h2 if problem.weighted_norm else 1.0
    delta = float(problem.delta)

    def kernel(cs_ref, cw_ref, g_ref, rhs_ref, sc2_ref,
               w_ref, k_ref, diff_ref, zr_ref, r_ref, p_ref):
        cs = cs_ref[:]
        cw = cw_ref[:]
        g = g_ref[:]
        sc2 = sc2_ref[:]
        cs_n = _row_plus(cs)       # c̃N at (i, j) = c̃S at (i+1, j)
        cw_e = _shift_col_plus(cw)  # c̃E at (i, j) = c̃W at (i, j+1)

        r0 = rhs_ref[:]
        w_ref[:] = jnp.zeros_like(r0)
        r_ref[:] = r0
        p_ref[:] = jnp.zeros_like(r0)   # β=0 → first direction is r₀
        zr0 = jnp.sum(r0 * r0, dtype=jnp.float32) * h1h2

        def cond(c):
            k, done, zr, beta, diff = c
            return (~done) & (k < cap)

        def body(c):
            k, done, zr, beta, diff = c
            # Direction update fused ahead of the stencil, exactly like
            # kernel A (z = r on the scaled system; β pending).
            p = r_ref[:] + beta * p_ref[:]
            p_ref[:] = p
            ap = (
                cs_n * (p - _row_plus(p))
                + cs * (p - _row_minus(p))
                + cw_e * (p - _shift_col_plus(p))
                + cw * (p - _shift_col_minus(p))
                + g * p
            )
            denom = jnp.sum(ap * p, dtype=jnp.float32) * h1h2
            deg = jnp.abs(denom) < _DENOM_TOL
            alpha = jnp.where(deg, 0.0, zr / jnp.where(deg, 1.0, denom))
            w_ref[:] = w_ref[:] + alpha * p
            diff_new = jnp.abs(alpha) * jnp.sqrt(
                jnp.sum(p * p * sc2, dtype=jnp.float32) * norm_w
            )
            r = r_ref[:] - alpha * ap
            r_ref[:] = r
            zr_new = jnp.sum(r * r, dtype=jnp.float32) * h1h2
            beta_new = zr_new / jnp.where(zr == 0.0, 1.0, zr)
            return (k + 1, deg | (diff_new < delta), zr_new, beta_new,
                    diff_new)

        k, done, zr, beta, diff = lax.while_loop(
            cond, body,
            (jnp.int32(0), jnp.bool_(False), zr0, jnp.float32(0.0),
             jnp.float32(jnp.inf)),
        )
        k_ref[0, 0] = k
        diff_ref[0, 0] = diff
        zr_ref[0, 0] = zr

    return kernel


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _resident_solve(problem: Problem, cv: Canvas, interpret: bool,
                    cs, cw, g, rhs, sc2):
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    canvas = jax.ShapeDtypeStruct((cv.rows, cv.cols), rhs.dtype)
    return pl.pallas_call(
        _make_resident_kernel(problem, problem.iteration_cap),
        in_specs=[vmem] * 5,
        out_specs=[vmem, smem, smem, smem],
        out_shape=[
            canvas,
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((cv.rows, cv.cols), jnp.float32),
            pltpu.VMEM((cv.rows, cv.cols), jnp.float32),
        ],
        interpret=interpret,
        **named("resident_solve"),
    )(cs, cw, g, rhs, sc2)


def resident_cg_solve_rhs(problem: Problem, rhs_grid64,
                          interpret: bool | None = None):
    """Resident solve of ``A w = rhs`` for a caller-supplied RHS grid
    (fp64 host array, full (M+1, N+1) shape) — the mixed-precision
    refinement hook (``solvers.refine``), mirroring
    ``ops.pallas_cg.pallas_cg_solve_rhs`` on the persistent-kernel path
    so each inner correction solve is a single launch.

    Returns ``(w64, iterations)`` with w accumulated on the host in fp64.
    """
    import numpy as np

    from poisson_tpu.ops.pallas_cg import scaled_stencil_fields

    if not fits_resident(problem):
        raise ValueError(
            f"grid {problem.M}x{problem.N} exceeds the VMEM residency "
            "budget; use pallas_cg_solve_rhs"
        )
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    cv = resident_canvas(problem)
    _, cs, cw, g, _, sc2, sc_int = build_canvases(
        problem, cv.bm, "float32", 0
    )
    _, _, _, _, sc64 = scaled_stencil_fields(problem)
    M, N = problem.M, problem.N
    scaled = np.asarray(rhs_grid64, np.float64) * sc64
    rhs_canvas = np.zeros((cv.rows, cv.cols), np.float64)
    rhs_canvas[HALO : HALO + M - 1, : N + 1] = scaled[1:M, :]
    rhs = jnp.asarray(rhs_canvas, jnp.float32)
    w, k, diff, zr = _resident_solve(problem, cv, interpret,
                                     cs, cw, g, rhs, sc2)
    y = w[HALO : HALO + M - 1, 1:N]
    w64 = np.zeros(problem.grid_shape, np.float64)
    w64[1:M, 1:N] = np.asarray(y, np.float64) * np.asarray(
        sc_int, np.float64
    )
    return w64, int(k[0, 0])


def resident_cg_solve(problem: Problem, interpret: bool | None = None,
                      rhs_gate=None) -> PCGResult:
    """Single-device solve with the whole PCG loop resident in VMEM.

    Same system, criterion, and golden iteration counts as the other
    fp32 paths; one kernel launch, zero per-iteration HBM traffic.
    Raises ``ValueError`` for grids whose working set cannot fit —
    use the streaming paths (``pallas_cg_solve`` / ``ca_cg_solve``).
    """
    if not fits_resident(problem):
        cv = resident_canvas(problem)
        need = _EQUIV_ARRAYS * cv.rows * cv.cols * 4
        raise ValueError(
            f"grid {problem.M}x{problem.N} needs ~{need / 2**20:.1f} MB of "
            f"VMEM for residency (budget {_VMEM_BYTES / 2**20:.0f} MB); "
            "use pallas_cg_solve / ca_cg_solve"
        )
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    cv = resident_canvas(problem)
    cv2, cs, cw, g, rhs, sc2, sc_int = build_canvases(
        problem, cv.bm, "float32", 0
    )
    assert cv2 == cv, (cv2, cv)
    if rhs_gate is not None:
        rhs = rhs * jnp.asarray(rhs_gate, rhs.dtype)
    w, k, diff, zr = _resident_solve(problem, cv, interpret,
                                     cs, cw, g, rhs, sc2)
    M, N = problem.M, problem.N
    y = w[HALO : HALO + M - 1, 1:N]
    sol = jnp.pad(y * sc_int, 1)
    return PCGResult(w=sol, iterations=k[0, 0], diff=diff[0, 0],
                     residual_dot=zr[0, 0])
