"""Fused Pallas TPU kernels for the PCG hot loop (SURVEY §7 step 5).

The reference's CUDA stage runs seven separate kernels per iteration with a
``cudaDeviceSynchronize`` after each and three PCIe partial-sum round-trips
(``stage4-mpi+cuda/poisson_mpi_cuda_f.cu:847-941``, SURVEY §3.3). XLA already
collapses the pure-JAX ops (``ops.stencil``) into a handful of fusions; these
kernels go further and restructure the whole iteration into exactly **two
HBM sweeps**:

  kernel A (``_direction_stencil_kernel``), one pass over 4 arrays:
      p ← z + β·p            (the reference's separate ``update_p_kernel``,
                              ``…cu:663-676``, folded into the stencil pass)
      Ap ← Ã p               (``apply_A_kernel``, ``…cu:507-536``)
      partial ⟨Ap, p⟩        (``dot_kernel`` + host finish, ``…cu:574-598``)

  kernel B (``_update_kernel``), one pass over 5 arrays:
      w ← w + α·p;  r ← r − α·Ap     (``update_w_r_kernel``, ``…cu:626-660``)
      partial Σ(p·sc)²                (the convergence sum, same kernel)
      partial ⟨z, r⟩ = Σ r²           (``dot_kernel`` again in the reference)

The preconditioner apply disappears entirely: the solver runs on the
symmetrically-scaled system Ã = D^{-1/2}AD^{-1/2} (see
``solvers.pcg.scaled_single_device_ops``) whose diagonal is exactly 1, so
z = r and the reference's ``apply_Dinv_kernel`` (20% of stage4 runtime,
BASELINE.md Table 2) costs nothing. The scaling is folded into two
precomputed off-diagonal coefficient canvases (``cS``, ``cW``; cN/cE are
shifted views of the same canvases, exploiting the symmetry cNᵢⱼ = cSᵢ₊₁ⱼ
the reference never used) plus a diagonal-residual canvas γ, and the
stencil is evaluated in **difference form**
      (Ãp)ᵢⱼ = Σ_k c̃_k·(pᵢⱼ − p_k) + γᵢⱼ·pᵢⱼ ,
which pairs adjacent grid values in every product — the fp32 rounding
stays at the scale of the (small) differences rather than of |p|. This is
what makes fp32 reproduce the fp64 golden iteration counts *exactly* at
every published grid (989/1858/2449) and reach the discretisation-floor
L2 error; the canonical ``p − Σ c̃p_k`` form drifted 0.1–0.3% in count and
lost 5× in accuracy at 2400×3200 (see :func:`diagonal_residual_canvas`).

Canvas layout
-------------
State lives on a strip-aligned canvas of shape (R, C):

  - interior row ii (global grid row ii+1) at canvas row HALO+ii;
  - R = nb·BM + 2·HALO with nb = ⌈(M−1)/BM⌉: a HALO-row guard band above and
    below the interior strips keeps every halo read in-bounds;
  - global column j at canvas column j, C = N+1 rounded up to the lane width
    (128); Dirichlet ring and pad columns are zero.

Kernel A reads overlapping (BM+2·HALO)-row strips and writes BM-row blocks,
both through ``pl.Element`` indexing (HALO=8 keeps every block height and
offset sublane-aligned, though the stencil only needs ±1 row). All canvases
are **zero outside the interior** (coefficients vanish there because the
scaling vector does), so zeros propagate through both kernels and no
interior masking is needed. w/r outputs alias their inputs (kernel B's in-
and out-blocks coincide, so revisiting is safe) and their guard bands stay
zero; the direction/Ap outputs are fresh buffers with uninitialized guards,
handled by zeroing each strip outside the written band in-kernel — kernel A
must not alias, since its overlapping halo reads would race with the
previous grid step's writes through a unified buffer.

Degenerate-direction corner (⟨Ap,p⟩ ≈ 0, never hit for this SPD system): α is
forced to 0, so w/r keep their values and the loop exits with done=True; the
reported ``diff`` is 0 rather than the pure-JAX path's last real value —
the one (documented) semantic difference from ``solvers.pcg.pcg_loop``.
"""

from __future__ import annotations

import functools
import os
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from poisson_tpu import obs
from poisson_tpu.config import Problem
from poisson_tpu.solvers.pcg import (
    FLAG_BREAKDOWN,
    FLAG_CONVERGED,
    FLAG_NONE,
    FLAG_NONFINITE,
    PCGResult,
    PCGState,
    _DENOM_TOL,
    host_fields64,
)

LANE = 128      # TPU lane width: canvas columns padded to a multiple of this
SUBLANE = 8     # fp32 sublane granule: strip heights in multiples of this
HALO = SUBLANE  # strip halo rows: 1 would do, 8 keeps blocks sublane-aligned
VMEM_BUDGET = 12 * 2 ** 20  # leave headroom under the ~16 MB/core VMEM


# Reduction-partial layout escape hatch, frozen at import so every jit
# cache in the process agrees with the kernels it compiled (flipping the
# env var later would otherwise silently reuse the other layout's
# executable — A/B runs use fresh subprocesses).
#
# Default (off): each grid step writes its partial to its own row of an
# (nb, 1)/(nb, ncb) SMEM output and the caller tree-sums — the
# accuracy-preferred layout. ``POISSON_TPU_SERIAL_REDUCE=1`` switches to a
# single (1, 1) SMEM cell accumulated across grid steps — the layout the
# round-2 TPU measurements compiled — with Kahan compensation in an SMEM
# scratch cell, which removes the serial-rounding L2 loss that motivated
# the per-strip partials in the first place (the compensated sum over
# ≤~10³ strip partials is exact to fp32 for this system). Sequential by
# construction, so it forces the tile grid's ``parallel`` (megacore)
# marking off.
SERIAL_REDUCE = os.environ.get("POISSON_TPU_SERIAL_REDUCE", "0") == "1"


def _resolve_serial(serial: bool | None, parallel: bool) -> bool:
    """Resolve a ``serial`` knob (None = the env default) against the
    ``parallel`` grid marking. The two are contradictory — serial
    accumulation is ordered across grid steps — and silently preferring
    one would fabricate A/B evidence (a 'parallel' row that actually ran
    sequentially), so the combination raises instead."""
    if serial is None:
        serial = SERIAL_REDUCE
    if serial and parallel:
        raise ValueError(
            "serial (Kahan) reduction accumulates across sequential grid "
            "steps; a parallel tile grid cannot honor it — pass one or "
            "the other"
        )
    if parallel and _is_megacore_device():
        # The partial-output layout shares ONE whole-window SMEM output
        # across every grid step (the only Mosaic-lowerable expression —
        # see _partial_out_spec); whether megacore write-back merges
        # distinct cells written by different TensorCores is unverified
        # (the target v5e is single-core, where the question cannot
        # arise — hence the device gate). Surfaced as a warning so a
        # megacore operator validates the golden iteration count before
        # trusting the reductions.
        warnings.warn(
            "parallel tile grid + per-strip SMEM partial outputs: "
            "cross-TensorCore write-back of the shared partial window is "
            "unverified on megacore parts — check the golden iteration "
            "count on this hardware before trusting the reductions",
            RuntimeWarning, stacklevel=3,
        )
    return serial


def _is_megacore(platform: str, device_kind: str) -> bool:
    """Mosaic's ``parallel`` dimension semantics splits the tile grid
    across TensorCores only on megacore chips (two cores fused behind one
    device: v4, v5p). Single-core parts (v5e/v6e "lite") and pre-megacore
    chips (v2/v3 expose each core as its own device) execute the grid on
    one core, where the shared-partial-window question cannot arise.

    libtpu has reported v5p chips with device_kind 'TPU v5' — no 'p'
    suffix at all — while v5e parts carry 'lite' or the explicit 'v5e'
    spelling. Matching 'v5p' alone therefore missed real v5p hardware,
    the one device class this predicate exists for; treat any v5 that is
    not a lite/e part as megacore."""
    if platform != "tpu":
        return False
    kind = device_kind.lower()
    if "v4" in kind:
        return True
    return "v5" in kind and "lite" not in kind and "v5e" not in kind


def _is_megacore_device() -> bool:
    try:
        dev = jax.devices()[0]
    except Exception:
        return False
    return _is_megacore(dev.platform, getattr(dev, "device_kind", ""))


def strip_height(cols: int, owned_rows: int, buffers: int = 12) -> int:
    """Strip height for a canvas of ``cols`` columns covering ``owned_rows``
    interior rows: fills the VMEM budget at ``buffers`` strip-buffers in
    flight (kernel A: 4 in + 2 out, double-buffered → 12; the CA basis
    sweep holds more), capped at 128 rows and at the owned band, floored
    at one sublane granule. Shared by the single-device, sharded, and CA
    canvas geometries."""
    rows = VMEM_BUDGET // (buffers * cols * 4)
    owned_cap = max(SUBLANE, -(-owned_rows // SUBLANE) * SUBLANE)
    rows = min(rows, 128, owned_cap)
    return max(SUBLANE, (rows // SUBLANE) * SUBLANE)


def pick_bm(problem: Problem) -> int:
    """Single-device strip height (see :func:`strip_height`)."""
    return strip_height(canvas_cols(problem), problem.M - 1)


def canvas_cols(problem: Problem) -> int:
    return ((problem.N + 1 + LANE - 1) // LANE) * LANE


class Canvas(NamedTuple):
    """Static geometry of the strip-aligned canvas.

    Full-width (``cg == 0``): one strip per grid step spans every column —
    the hardware-proven default. Column-blocked (``cg == LANE``): a 2D
    kernel grid of (strip, column-block) tiles with LANE-wide column guard
    bands mirroring the row guards; grid column j lives at canvas column
    ``cg + j``. Blocking exists for canvases too wide for a sane strip
    height (the VMEM budget divides by the buffer width, so a 16384-wide
    grid forces 8-row strips whose halo overhead triples the stencil's
    read traffic)."""

    bm: int     # strip height (interior rows per grid step)
    nb: int     # number of interior strips
    rows: int   # nb·bm + 2·HALO
    cols: int   # content cols padded to LANE, plus 2·cg when blocked
    bn: int = 0     # column-block width (0 = full width)
    ncb: int = 1    # number of column blocks
    cg: int = 0     # column guard width (LANE when blocked)


def _width_limited_bm(problem: Problem) -> int:
    """The strip height the VMEM budget alone allows at full width —
    :func:`strip_height` with the owned-rows cap saturated. Distinguishes
    'bm is small because the canvas is huge' (auto-blocking territory)
    from 'bm is small because M is small' (leave the tiny grid alone)."""
    return strip_height(canvas_cols(problem), 128)


def canvas_spec(problem: Problem, bm: int | None = None,
                bn: int | None = None) -> Canvas:
    """``bn``: None = auto (column blocking kicks in only when full-width
    strips degenerate on a huge canvas width); 0 = force full width (the
    portable-checkpoint and refinement layouts); a multiple of LANE =
    explicit blocking."""
    if bn == 0:
        bn = None
    elif bm is None and bn is None and _width_limited_bm(problem) < 4 * SUBLANE:
        # Full-width strips have degenerated (the VMEM budget divided by a
        # huge canvas width leaves almost no rows, and the 2·HALO overfetch
        # then dominates the stencil's reads): auto-select the widest
        # column blocking that restores a sane strip height — wider blocks
        # amortize the column-guard overfetch better. The height target
        # saturates at the owned-rows cap so a short-M grid still gets the
        # widest (least-overfetch) candidate rather than the fallback.
        owned_cap = max(SUBLANE, -(-(problem.M - 1) // SUBLANE) * SUBLANE)
        target = min(8 * SUBLANE, owned_cap)
        for candidate in (4096, 2048, 1024):
            if strip_height(candidate + 2 * LANE, problem.M - 1) >= target:
                bn = candidate
                break
        else:
            bn = 1024
    if bn is not None:
        if bn <= 0 or bn % LANE != 0:
            # Lane-dimension block offsets must stay LANE-aligned.
            raise ValueError(f"bn must be a positive multiple of {LANE}, got {bn}")
        ncb = -(-(problem.N + 1) // bn)
        cols = 2 * LANE + ncb * bn
        if bm is None:
            bm = strip_height(bn + 2 * LANE, problem.M - 1)
    else:
        ncb, cols = 1, canvas_cols(problem)
        if bm is None:
            bm = pick_bm(problem)
    if bm <= 0 or bm % SUBLANE != 0:
        # The strip/block index maps multiply in SUBLANE granules; any other
        # bm would silently address the wrong rows.
        raise ValueError(f"bm must be a positive multiple of {SUBLANE}, got {bm}")
    nb = -(-(problem.M - 1) // bm)
    return Canvas(bm=bm, nb=nb, rows=nb * bm + 2 * HALO, cols=cols,
                  bn=(bn or 0), ncb=ncb, cg=(LANE if bn else 0))


def scaled_stencil_fields(problem: Problem):
    """Grid-indexed folded-scaling stencil fields (host fp64, numpy).

    Returns (gcs, gcw, sc2, rhs, sc) on the full (M+1, N+1) grid:
        gcs[i, j] = a[i,j]·sc[i,j]·sc[i−1,j]/h1²   (south edge, i ≥ 1)
        gcw[i, j] = b[i,j]·sc[i,j]·sc[i,j−1]/h2²   (west edge,  j ≥ 1)
    with row/column 0 zeroed, sc2 = sc², rhs = b̃ = sc·B, sc = D^{-1/2}
    (zero ring). Shared derivation for the single-device and sharded canvas
    builders — the kernels' operator comes from exactly one place.
    """
    a64, b64, rhs64, sc64 = host_fields64(problem, True)
    h1sq, h2sq = problem.h1 ** 2, problem.h2 ** 2
    gcs = np.zeros_like(a64)
    gcs[1:, :] = a64[1:, :] * sc64[1:, :] * sc64[:-1, :] / h1sq
    gcw = np.zeros_like(b64)
    gcw[:, 1:] = b64[:, 1:] * sc64[:, 1:] * sc64[:, :-1] / h2sq
    return gcs, gcw, sc64 * sc64, rhs64, sc64


@functools.lru_cache(maxsize=8)
def build_canvases(problem: Problem, bm: int | None = None,
                   dtype_name: str = "float32", bn: int | None = None):
    """Host fp64 setup → canvas-laid-out device arrays.

    Reuses :func:`solvers.pcg.host_fields64` (the shared precision-policy
    setup) and derives the folded-scaling stencil coefficients:

        cS[i,j] = a[i,j]·sc[i,j]·sc[i−1,j]/h1²   (south edge of point (i,j))
        cW[i,j] = b[i,j]·sc[i,j]·sc[i,j−1]/h2²   (west edge)

    with sc = D^{-1/2} embedded in a zero ring — any edge touching the ring
    (or the guard/pad regions) gets coefficient 0 automatically, which is
    what lets the kernels run maskless.

    Returns (cv, cS, cW, g, rhs, sc2, sc_int): canvases as (R, C) device
    arrays, plus the interior scaling slice (device array) for solution
    extraction. ``g`` is the diagonal residual (see
    :func:`diagonal_residual_canvas`).
    """
    cv = canvas_spec(problem, bm, bn)
    dtype = jnp.dtype(dtype_name)
    M, N = problem.M, problem.N
    gcs, gcw, sc2_64, rhs64, sc64 = scaled_stencil_fields(problem)

    def to_canvas(grid_rows_1_to_M: np.ndarray, col0: int = 0) -> np.ndarray:
        """Embed rows 1..M(−1) of a full (M+1,N+1) grid at canvas row HALO+…
        and canvas column cg+col0 (cg = 0 on the full-width layout)."""
        out = np.zeros((cv.rows, cv.cols), np.float64)
        nr, nc = grid_rows_1_to_M.shape
        out[HALO : HALO + nr, cv.cg + col0 : cv.cg + col0 + nc] = (
            grid_rows_1_to_M
        )
        return out

    # Edge coefficients for i = 1..M (row i=M closes the last interior
    # point's north edge; it is zero anyway since sc[M,:] = 0).
    cs_canvas = to_canvas(gcs[1:, :])
    cw_canvas = to_canvas(gcw[1:, 1:], col0=1)                    # rows 1..M
    rhs_canvas = to_canvas(rhs64[1:M, :])                         # b̃, rows 1..M-1
    sc2_canvas = to_canvas(sc2_64[1:M, :])
    g_canvas = diagonal_residual_canvas(cs_canvas, cw_canvas)

    as_dev = lambda x: jnp.asarray(x, dtype)
    return (
        cv,
        as_dev(cs_canvas),
        as_dev(cw_canvas),
        as_dev(g_canvas),
        as_dev(rhs_canvas),
        as_dev(sc2_canvas),
        as_dev(sc64[1:M, 1:N]),
    )


def diagonal_residual_canvas(cs_canvas: np.ndarray,
                             cw_canvas: np.ndarray) -> np.ndarray:
    """γ = 1 − (c̃N + c̃S + c̃E + c̃W), computed in fp64 from the coefficient
    canvases.

    The scaled operator in *difference form* is
        (Ãp)_c = Σ_k c̃_k·(p_c − p_k) + γ_c·p_c ,
    exactly equivalent to the canonical ``p_c − Σ c̃_k p_k`` but numerically
    far better in fp32: each difference term pairs adjacent grid values
    (benign cancellation), while the canonical form subtracts two O(|p|)
    quantities to produce the small result — amplifying rounding by the
    smooth-mode factor |p|/|Ãp|. γ is O(h·∂sc) near the embedded boundary,
    exactly 0 where the scaling is locally constant, and 1 on padding
    (where all coefficients vanish and p is identically zero).
    """
    cs_next = np.zeros_like(cs_canvas)
    cs_next[:-1] = cs_canvas[1:]
    cw_east = np.zeros_like(cw_canvas)
    cw_east[:, :-1] = cw_canvas[:, 1:]
    return 1.0 - (cs_canvas + cs_next + cw_canvas + cw_east)


def _shift_col_minus(u):
    """u[:, j-1] with a zero column shifted in (no wraparound)."""
    return jnp.concatenate([jnp.zeros_like(u[:, :1]), u[:, :-1]], axis=1)


def _shift_col_plus(u):
    """u[:, j+1] with a zero column shifted in."""
    return jnp.concatenate([u[:, 1:], jnp.zeros_like(u[:, :1])], axis=1)


def _direction_stencil(cv: Canvas, band: tuple[int, int], beta,
                       z_ref, p_ref, cs_ref, cw_ref, g_ref):
    """Kernel A's arithmetic on strip ``program_id(0)``: the new
    direction's center rows and their Ãp (see the kernel factory)."""
    h = HALO
    band_lo, band_hi = band
    off = pl.program_id(0) * cv.bm
    rows = off + lax.broadcasted_iota(jnp.int32, (cv.bm + 2 * h, 1), 0)
    in_band = (rows >= band_lo) & (rows < band_hi)
    pn = jnp.where(in_band, z_ref[:] + beta * p_ref[:], 0.0)
    c = pn[h:-h, :]                            # center rows
    cs_c = cs_ref[h:-h, :]                     # south-edge coeff at center
    cs_n = cs_ref[h + 1 : -h + 1, :]           # north edge = cS shifted down
    cw_c = cw_ref[:]                           # block-spec'd: center rows only
    # Difference form: adjacent-value differences keep fp32 cancellation
    # benign on smooth modes (see diagonal_residual_canvas).
    ap = (
        cs_n * (c - pn[h + 1 : -h + 1, :])
        + cs_c * (c - pn[h - 1 : -h - 1, :])
        + _shift_col_plus(cw_c) * (c - _shift_col_plus(c))
        + cw_c * (c - _shift_col_minus(c))
        + g_ref[:] * c
    )
    return c, ap


def _make_direction_stencil_kernel(cv: Canvas, band: tuple[int, int],
                                   masked: bool, serial: bool = False):
    """Kernel A: p ← z + β·p, Ap ← Ãp, accumulate ⟨Ap, p⟩.

    Strip refs are (BM+2·HALO, C) halo-inclusive; outputs are the BM center
    rows. The halo rows of the new direction are recomputed locally (they
    are the neighbouring strips' center rows), trading 2·C flops per strip
    for not re-reading p after the update — the fused-CG restructuring.

    ``band`` is the canvas-row range [lo, hi) on which the direction update
    is live. Single-device: the interior strips (the Dirichlet ring stays
    zero). Sharded (``parallel.pallas_sharded``): widened by one row per
    side, so the shard's halo rows — whose z/p values neighbours own —
    are recomputed in-register for the stencil, the same values the
    neighbour computes for its own edge (no p exchange).

    ``masked`` adds a (1, C) column-mask operand multiplying the ⟨Ap, p⟩
    partial: sharded canvases carry real (neighbour) values in their halo
    columns, which must not enter the owned-interior reduction. The
    single-device canvas is zero there by construction and needs no mask.

    p's guard blocks are uninitialized garbage (the output is a fresh buffer
    whose guards are never written — it must NOT alias the p input: with the
    buffers unified, a strip's halo read would see the rows the *previous*
    grid step already overwrote). Zero coefficients would absorb finite
    garbage, but not NaN/Inf, so the strip is explicitly zeroed outside the
    live band right where it is computed.
    """
    def kernel(beta_ref, z_ref, p_ref, cs_ref, cw_ref, g_ref, *rest):
        comp_ref = None
        if serial:
            *rest, comp_ref = rest
        if masked:
            colmask_ref, pn_ref, ap_ref, denom_ref = rest
        else:
            pn_ref, ap_ref, denom_ref = rest
        i = pl.program_id(0)
        c, ap = _direction_stencil(cv, band, beta_ref[0, 0], z_ref, p_ref,
                                   cs_ref, cw_ref, g_ref)
        pn_ref[:] = c
        ap_ref[:] = ap

        apc = ap * c
        if masked:
            apc = apc * colmask_ref[:]
        # Per-strip partial only: strip i owns row i of the (nb, 1) output
        # (whole-array SMEM window; see _partial_out_spec) and XLA
        # tree-sums the partials outside the kernel. A single SMEM scalar
        # accumulated across strips rounds serially (nb-long dependence
        # chain), which cost 6× in L2 accuracy at 2400×3200 — the serial
        # variant compensates with a Kahan scratch cell instead.
        part = jnp.sum(apc, dtype=jnp.float32)
        if serial:
            _kahan_add(i == 0, denom_ref, comp_ref, 0, part)
        else:
            denom_ref[i, 0] = part

    return kernel


def _is_first_step(ndims: int):
    """True on the first step of an ``ndims``-dimensional sequential grid."""
    first = pl.program_id(0) == 0
    for d in range(1, ndims):
        first &= pl.program_id(d) == 0
    return first


def _kahan_add(first, out_ref, comp_ref, slot: int, part):
    """Compensated accumulation of ``part`` into the (1, 1) ``out_ref``
    with the running compensation in ``comp_ref[slot]`` (SMEM scratch,
    which persists across the sequential grid steps). ``first`` zeroes
    both."""

    @pl.when(first)
    def _():
        out_ref[0, 0] = 0.0
        comp_ref[slot] = 0.0

    y = part - comp_ref[slot]
    t = out_ref[0, 0] + y
    comp_ref[slot] = (t - out_ref[0, 0]) - y
    out_ref[0, 0] = t


def _make_blocked_stencil_kernel(cv: Canvas, band: tuple[int, int],
                                 serial: bool = False):
    """Column-blocked kernel A (single-device layouts only): the full-width
    kernel's math on a (strip, column-block) 2D grid. Column guards play
    the role row guards play in the full-width layout — every ±1-column
    stencil read comes from the widened block instead of an in-register
    zero shift — and the fresh direction buffer's unwritten guard regions
    are zeroed through the same in-band mask, extended to columns."""
    h = HALO
    cg = cv.cg
    band_lo, band_hi = band

    def kernel(beta_ref, z_ref, p_ref, cs_ref, cw_ref, g_ref,
               pn_ref, ap_ref, denom_ref, *scratch):
        i = pl.program_id(0)
        j = pl.program_id(1)
        beta = beta_ref[0, 0]
        rows = i * cv.bm + lax.broadcasted_iota(
            jnp.int32, (cv.bm + 2 * h, 1), 0
        )
        cols = j * cv.bn + lax.broadcasted_iota(
            jnp.int32, (1, cv.bn + 2 * cg), 1
        )
        live = (
            (rows >= band_lo) & (rows < band_hi)
            & (cols >= cg) & (cols < cg + cv.ncb * cv.bn)
        )
        pn = jnp.where(live, z_ref[:] + beta * p_ref[:], 0.0)
        c = pn[h:-h, cg:-cg]                       # center rows & cols
        cs_c = cs_ref[h:-h, :]
        cs_n = cs_ref[h + 1 : -h + 1, :]
        cw_c = cw_ref[:, cg:-cg]
        cw_e = cw_ref[:, cg + 1 : -cg + 1]
        ap = (
            cs_n * (c - pn[h + 1 : -h + 1, cg:-cg])
            + cs_c * (c - pn[h - 1 : -h - 1, cg:-cg])
            + cw_e * (c - pn[h:-h, cg + 1 : -cg + 1])
            + cw_c * (c - pn[h:-h, cg - 1 : -cg - 1])
            + g_ref[:] * c
        )
        pn_ref[:] = c
        ap_ref[:] = ap
        # Per-tile partial (cell (i, j) of the whole-window (nb, ncb)
        # output; see _partial_out_spec); the caller tree-sums, same
        # accuracy rationale as the strip partials.
        part = jnp.sum(ap * c, dtype=jnp.float32)
        if serial:
            _kahan_add(_is_first_step(2), denom_ref, scratch[0], 0, part)
        else:
            denom_ref[i, j] = part

    return kernel


def _update(alpha, p_ref, ap_ref, w_ref, r_ref, w_out_ref, r_out_ref):
    """Kernel B's update of one block: w ← w + α·p, r ← r − α·Ap.
    Returns (p, r_new) for the partials."""
    p = p_ref[:]
    r_new = r_ref[:] - alpha * ap_ref[:]
    w_out_ref[:] = w_ref[:] + alpha * p
    r_out_ref[:] = r_new
    return p, r_new


def _make_update_kernel(masked: bool, serial: bool = False, ndims: int = 1):
    """Kernel B: w ← w + α·p, r ← r − α·Ap, accumulate Σp²·sc² and Σr².

    ``masked`` adds a (1, C) column mask multiplying the Σr² partial (the
    sharded canvases hold real neighbour values in halo columns); the
    Σp²·sc² partial needs no mask because the sharded sc2 canvas is
    pre-zeroed outside the owned interior."""

    def kernel(alpha_ref, p_ref, ap_ref, sc2_ref, *rest):
        comp_ref = None
        if serial:
            *rest, comp_ref = rest
        if masked:
            colmask_ref, w_ref, r_ref, w_out_ref, r_out_ref, diff_ref, zr_ref = rest
        else:
            w_ref, r_ref, w_out_ref, r_out_ref, diff_ref, zr_ref = rest
        p, r_new = _update(alpha_ref[0, 0], p_ref, ap_ref, w_ref, r_ref,
                           w_out_ref, r_out_ref)
        rr = r_new * r_new
        if masked:
            rr = rr * colmask_ref[:]
        # Per-strip partials (see kernel A): cell (i[, j]) of the
        # whole-window (nb[, ncb]) outputs.
        d_part = jnp.sum(p * p * sc2_ref[:], dtype=jnp.float32)
        z_part = jnp.sum(rr, dtype=jnp.float32)
        if serial:
            first = _is_first_step(ndims)
            _kahan_add(first, diff_ref, comp_ref, 0, d_part)
            _kahan_add(first, zr_ref, comp_ref, 1, z_part)
        else:
            i = pl.program_id(0)
            j = pl.program_id(1) if ndims == 2 else 0
            diff_ref[i, j] = d_part
            zr_ref[i, j] = z_part

    return kernel


def _strip_in_spec(cv: Canvas):
    # Offsets written so the ×SUBLANE multiply is outermost — Mosaic's
    # divisibility prover needs the literal multiply to accept the layout.
    granules = cv.bm // SUBLANE
    return pl.BlockSpec(
        (pl.Element(cv.bm + 2 * HALO), pl.Element(cv.cols)),
        lambda i, *_: (SUBLANE * (i * granules), 0),
    )


def _block_spec(cv: Canvas):
    """BM-row block at canvas offset i·bm + HALO (the strip's center rows) —
    element-indexed, since the offset is sublane- but not block-aligned."""
    granules = cv.bm // SUBLANE
    return pl.BlockSpec(
        (pl.Element(cv.bm), pl.Element(cv.cols)),
        lambda i, *_: (SUBLANE * (i * granules + 1), 0),
    )


def _scalar_spec():
    """(1,1) scalar operand in SMEM — scalar loads/stores are not legal on
    VMEM tiles, and α/β are consumed by the scalar unit."""
    return pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)


def _partial_out_spec():
    """The whole (nb, 1) SMEM output as one trivial window: each strip
    writes its reduction partial to row ``program_id(0)`` in-kernel, and
    XLA tree-sums the partials after the kernel — a serial SMEM
    accumulator across strips loses ~6× L2 accuracy at the largest
    published grid.

    Why trivial-window: Mosaic requires blocked specs' last two dims be
    multiples of (8, 128) or equal to the array dims, so the per-cell
    ``(1, 1) @ (i, 0)`` mapping this replaces lowered ONLY when nb == 1 —
    tiny grids passed while every real geometry crashed at lowering on
    the chip (the round-3 on-hardware failure; reproduced off-chip by
    tests/test_mosaic_lowering.py). SMEM blocks with a trivial window are
    exempt from the tiling rules, and SMEM supports dynamic scalar
    stores, so the whole-array window with in-kernel indexing expresses
    the identical layout legally."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def named(kernel: str) -> dict:
    """``pallas_call`` keywords that give a kernel its stable name:
    ``name`` for the Mosaic kernel, and ``metadata``, which the custom
    call carries as ``frontend_attributes={kernel_metadata={"kernel":
    "<kernel>"}}`` into the compiled program and into the name of each
    of its op events in a TPU profile. A refactor renames HLO
    instructions (``body.6``); it leaves this name alone."""
    return {"name": kernel, "metadata": {"kernel": kernel}}


def _canvas_shape(cv: Canvas, dtype):
    return jax.ShapeDtypeStruct((cv.rows, cv.cols), dtype)


# --- column-blocked (2D-grid) spec family; offsets written as literal
# SUBLANE/LANE multiplies for Mosaic's divisibility prover ------------------


def _blk_specs(cv: Canvas):
    granules = cv.bm // SUBLANE
    lanes = cv.bn // LANE
    strip = pl.BlockSpec(        # z, p: halo rows AND guard cols
        (pl.Element(cv.bm + 2 * HALO), pl.Element(cv.bn + 2 * cv.cg)),
        lambda i, j: (SUBLANE * (i * granules), LANE * (j * lanes)),
    )
    cs = pl.BlockSpec(           # halo rows, center cols
        (pl.Element(cv.bm + 2 * HALO), pl.Element(cv.bn)),
        lambda i, j: (SUBLANE * (i * granules), LANE * (j * lanes + 1)),
    )
    cw = pl.BlockSpec(           # center rows, guard cols (east shift)
        (pl.Element(cv.bm), pl.Element(cv.bn + 2 * cv.cg)),
        lambda i, j: (SUBLANE * (i * granules + 1), LANE * (j * lanes)),
    )
    block = pl.BlockSpec(        # center tile
        (pl.Element(cv.bm), pl.Element(cv.bn)),
        lambda i, j: (SUBLANE * (i * granules + 1), LANE * (j * lanes + 1)),
    )
    scalar = pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                          memory_space=pltpu.SMEM)
    # Whole (nb, ncb) SMEM window; tile (i, j) writes its own cell
    # in-kernel (see _partial_out_spec for why not a per-cell block map).
    partial = pl.BlockSpec(memory_space=pltpu.SMEM)
    return strip, cs, cw, block, scalar, partial


def _colmask_spec(cv: Canvas):
    """(1, C) row broadcast to every strip."""
    return pl.BlockSpec((1, cv.cols), lambda i: (0, 0))


def _grid_params(parallel: bool, ndims: int = 1):
    """Grid-dimension semantics. ``parallel`` lets Mosaic distribute the
    tile loop across TensorCores (megacore): every tile writes disjoint
    center blocks and a distinct cell of the shared whole-window partial
    output. CAVEAT: the partial outputs are one SMEM window shared by all
    grid steps (the only Mosaic-lowerable expression of the layout — see
    _partial_out_spec), and whether megacore write-back merges distinct
    cells written by different cores is UNVERIFIED — the target v5e has a
    single TensorCore, where the question cannot arise. Off by default —
    it must earn its place on hardware (BENCH.md) before becoming the
    default, and on a megacore chip the reduction values need explicit
    validation first (the golden iteration counts catch corruption)."""
    if not parallel:
        return {}
    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel",) * ndims
        )
    }


def direction_and_stencil(cv: Canvas, beta, z, p, cs, cw, g, *,
                          interpret: bool,
                          band: tuple[int, int] | None = None, colmask=None,
                          parallel: bool = False,
                          serial: bool | None = None):
    """p_new, Ap, per-strip ⟨Ap, p_new⟩ partials ((nb, 1), unweighted; caller
    tree-sums) — one HBM sweep.

    ``band``/``colmask`` select the sharded variant (see the kernel factory);
    defaults are the single-device interior band with no mask. A
    column-blocked canvas (``cv.cg > 0``) routes to the 2D-grid kernel —
    single-device only (the sharded layouts stay full-width)."""
    if band is None:
        band = (HALO, cv.rows - HALO)
    serial = _resolve_serial(serial, parallel)
    if cv.cg:
        assert colmask is None, "column blocking is single-device only"
        strip, cs_spec, cw_spec, block, scalar, partial = _blk_specs(cv)
        if serial:
            partial = scalar      # one (1, 1) cell instead of (nb, ncb)
        return pl.pallas_call(
            _make_blocked_stencil_kernel(cv, band, serial),
            grid=(cv.nb, cv.ncb),
            in_specs=[scalar, strip, strip, cs_spec, cw_spec, block],
            out_specs=[block, block, partial],
            out_shape=[
                _canvas_shape(cv, p.dtype),
                _canvas_shape(cv, p.dtype),
                jax.ShapeDtypeStruct(
                    (1, 1) if serial else (cv.nb, cv.ncb), jnp.float32
                ),
            ],
            scratch_shapes=(
                [pltpu.SMEM((1,), jnp.float32)] if serial else []
            ),
            interpret=interpret,
            **named("direction_and_stencil"),
            **_grid_params(parallel, 2),
        )(beta, z, p, cs, cw, g)
    masked = colmask is not None
    in_specs = [
        _scalar_spec(),
        _strip_in_spec(cv),   # z: halo rows feed the stencil
        _strip_in_spec(cv),   # p: ditto
        _strip_in_spec(cv),   # cs: needs rows up to center+1
        _block_spec(cv),      # cw: only center rows are read
        _block_spec(cv),      # g (diagonal residual): center rows
    ]
    operands = [beta, z, p, cs, cw, g]
    if masked:
        in_specs.append(_colmask_spec(cv))
        operands.append(colmask)
    return pl.pallas_call(
        _make_direction_stencil_kernel(cv, band, masked, serial),
        grid=(cv.nb,),
        in_specs=in_specs,
        out_specs=[
            _block_spec(cv),
            _block_spec(cv),
            _scalar_spec() if serial else _partial_out_spec(),
        ],
        out_shape=[
            _canvas_shape(cv, p.dtype),
            _canvas_shape(cv, p.dtype),
            jax.ShapeDtypeStruct((1, 1) if serial else (cv.nb, 1),
                                 jnp.float32),
        ],
        scratch_shapes=([pltpu.SMEM((1,), jnp.float32)] if serial else []),
        interpret=interpret,
        **named("direction_and_stencil"),
        **_grid_params(parallel),
    )(*operands)


def fused_update(cv: Canvas, alpha, p, ap, sc2, w, r, *, interpret: bool,
                 colmask=None, parallel: bool = False,
                 serial: bool | None = None):
    """w', r', per-strip Σ p²·sc² and Σ r'² partials ((nb, 1) each; caller
    tree-sums) — one HBM sweep. Column-blocked canvases run the same
    kernel body on the (strip, column-block) 2D grid with (nb, ncb)
    partials."""
    serial = _resolve_serial(serial, parallel)
    if cv.cg:
        assert colmask is None, "column blocking is single-device only"
        _, _, _, block, scalar, partial = _blk_specs(cv)
        if serial:
            partial = scalar
        pshape = jax.ShapeDtypeStruct(
            (1, 1) if serial else (cv.nb, cv.ncb), jnp.float32
        )
        return pl.pallas_call(
            _make_update_kernel(masked=False, serial=serial, ndims=2),
            grid=(cv.nb, cv.ncb),
            in_specs=[scalar, block, block, block, block, block],
            out_specs=[block, block, partial, partial],
            out_shape=[
                _canvas_shape(cv, w.dtype),
                _canvas_shape(cv, w.dtype),
                pshape,
                pshape,
            ],
            input_output_aliases={4: 0, 5: 1},  # w → w', r → r'
            scratch_shapes=(
                [pltpu.SMEM((2,), jnp.float32)] if serial else []
            ),
            interpret=interpret,
            **named("fused_update"),
            **_grid_params(parallel, 2),
        )(alpha, p, ap, sc2, w, r)
    masked = colmask is not None
    in_specs = [
        _scalar_spec(),
        _block_spec(cv),
        _block_spec(cv),
        _block_spec(cv),
    ]
    operands = [alpha, p, ap, sc2]
    if masked:
        in_specs.append(_colmask_spec(cv))
        operands.append(colmask)
    w_idx = len(operands)
    in_specs += [_block_spec(cv), _block_spec(cv)]
    operands += [w, r]
    pspec = _scalar_spec() if serial else _partial_out_spec()
    pshape = jax.ShapeDtypeStruct((1, 1) if serial else (cv.nb, 1),
                                  jnp.float32)
    return pl.pallas_call(
        _make_update_kernel(masked, serial),
        grid=(cv.nb,),
        in_specs=in_specs,
        out_specs=[
            _block_spec(cv),
            _block_spec(cv),
            pspec,
            pspec,
        ],
        out_shape=[
            _canvas_shape(cv, w.dtype),
            _canvas_shape(cv, w.dtype),
            pshape,
            pshape,
        ],
        input_output_aliases={w_idx: 0, w_idx + 1: 1},  # w → w', r → r'
        scratch_shapes=([pltpu.SMEM((2,), jnp.float32)] if serial else []),
        interpret=interpret,
        **named("fused_update"),
        **_grid_params(parallel),
    )(*operands)


# --- the member axis: B right-hand sides on one operator --------------------
#
# State is a (B, R, C) stack of canvases and the grid is (nb, B): strip
# outer, member inner, so each coefficient operand (cs, cw, g, sc2) keeps
# one block index across a strip's B consecutive steps and the pipeline
# fetches it once per strip, not once per member. α and β arrive as (B,)
# SMEM windows read at program_id(1); member m's strip partials land at
# m·nb + i of a flat (B·nb,) SMEM output. Not ``jax.vmap`` of the one-RHS
# calls: its batching rule puts the batch axis outermost, which re-reads
# the coefficients for every member and shifts the program_id(0) the
# partial layout indexes.


def _member_spec(cv: Canvas, rows: int, granule0: int):
    """Member m's ``rows``-row window at canvas row SUBLANE·(i·bm/SUBLANE
    + granule0): the halo-inclusive strip (granule0 0) or the center
    block (granule0 1)."""
    granules = cv.bm // SUBLANE
    return pl.BlockSpec(
        (pl.squeezed, pl.Element(rows), pl.Element(cv.cols)),
        lambda i, m: (m, SUBLANE * (i * granules + granule0), 0),
    )


def _stack_shape(cv: Canvas, members: int, dtype):
    return jax.ShapeDtypeStruct((members, cv.rows, cv.cols), dtype)


def _make_batched_direction_stencil_kernel(cv: Canvas):
    band = (HALO, cv.rows - HALO)

    def kernel(beta_ref, z_ref, p_ref, cs_ref, cw_ref, g_ref,
               pn_ref, ap_ref, denom_ref):
        i, m = pl.program_id(0), pl.program_id(1)
        c, ap = _direction_stencil(cv, band, beta_ref[m], z_ref, p_ref,
                                   cs_ref, cw_ref, g_ref)
        pn_ref[:] = c
        ap_ref[:] = ap
        denom_ref[m * cv.nb + i] = jnp.sum(ap * c, dtype=jnp.float32)

    return kernel


def _make_batched_update_kernel(cv: Canvas):
    def kernel(alpha_ref, p_ref, ap_ref, sc2_ref, w_ref, r_ref,
               w_out_ref, r_out_ref, diff_ref, zr_ref):
        i, m = pl.program_id(0), pl.program_id(1)
        p, r_new = _update(alpha_ref[m], p_ref, ap_ref, w_ref, r_ref,
                           w_out_ref, r_out_ref)
        rr = r_new * r_new
        diff_ref[m * cv.nb + i] = jnp.sum(p * p * sc2_ref[:],
                                          dtype=jnp.float32)
        zr_ref[m * cv.nb + i] = jnp.sum(rr, dtype=jnp.float32)

    return kernel


def batched_direction_and_stencil(cv: Canvas, beta, z, p, cs, cw, g, *,
                                  interpret: bool):
    """Kernel A over a (B, R, C) stack with per-member β of shape (B,):
    p_new, Ap and the flat (B·nb,) ⟨Ap, p_new⟩ strip partials."""
    members = z.shape[0]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    strip = _member_spec(cv, cv.bm + 2 * HALO, 0)
    block = _member_spec(cv, cv.bm, 1)
    return pl.pallas_call(
        _make_batched_direction_stencil_kernel(cv),
        grid=(cv.nb, members),
        in_specs=[smem, strip, strip, _strip_in_spec(cv), _block_spec(cv),
                  _block_spec(cv)],
        out_specs=[block, block, smem],
        out_shape=[
            _stack_shape(cv, members, p.dtype),
            _stack_shape(cv, members, p.dtype),
            jax.ShapeDtypeStruct((members * cv.nb,), jnp.float32),
        ],
        interpret=interpret,
        **named("batched_direction_and_stencil"),
    )(beta, z, p, cs, cw, g)


def batched_fused_update(cv: Canvas, alpha, p, ap, sc2, w, r, *,
                         interpret: bool):
    """Kernel B over a (B, R, C) stack with per-member α of shape (B,):
    w', r' (aliasing w, r) and the flat (B·nb,) Σ p²·sc² and Σ r'²
    strip partials."""
    members = w.shape[0]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    block = _member_spec(cv, cv.bm, 1)
    partial = jax.ShapeDtypeStruct((members * cv.nb,), jnp.float32)
    return pl.pallas_call(
        _make_batched_update_kernel(cv),
        grid=(cv.nb, members),
        in_specs=[smem, block, block, _block_spec(cv), block, block],
        out_specs=[block, block, smem, smem],
        out_shape=[
            _stack_shape(cv, members, w.dtype),
            _stack_shape(cv, members, w.dtype),
            partial,
            partial,
        ],
        input_output_aliases={4: 0, 5: 1},  # w → w', r → r'
        interpret=interpret,
        **named("batched_fused_update"),
    )(alpha, p, ap, sc2, w, r)


class _FusedState(NamedTuple):
    k: jnp.ndarray
    done: jnp.ndarray
    w: jnp.ndarray
    r: jnp.ndarray
    p: jnp.ndarray
    zr: jnp.ndarray    # ζ = Σ r² · h1h2 (z = r on the scaled system)
    beta: jnp.ndarray
    diff: jnp.ndarray


def _make_fused_body(problem: Problem, cv: Canvas, interpret: bool,
                     cs, cw, g, sc2, dtype, parallel: bool = False,
                     serial: bool = False):
    """One fused iteration (kernels A + B) as a pure state→state function —
    shared by the convergence while_loop and the chunked checkpointed
    solve."""
    h1h2 = jnp.float32(problem.h1 * problem.h2)
    norm_w = h1h2 if problem.weighted_norm else jnp.float32(1.0)

    def body(s: _FusedState) -> _FusedState:
        beta = jnp.reshape(s.beta, (1, 1)).astype(dtype)
        pn, ap, denom_part = direction_and_stencil(
            cv, beta, s.r, s.p, cs, cw, g, interpret=interpret,
            parallel=parallel, serial=serial,
        )
        denom = jnp.sum(denom_part) * h1h2
        degenerate = jnp.abs(denom) < _DENOM_TOL
        alpha32 = jnp.where(degenerate, 0.0, s.zr / jnp.where(degenerate, 1.0, denom))
        alpha = jnp.reshape(alpha32, (1, 1)).astype(dtype)
        w, r, diff_part, zr_part = fused_update(
            cv, alpha, pn, ap, sc2, s.w, s.r, interpret=interpret,
            parallel=parallel, serial=serial,
        )
        diff = jnp.abs(alpha32) * jnp.sqrt(jnp.sum(diff_part) * norm_w)
        zr_new = jnp.sum(zr_part) * h1h2
        converged = diff < problem.delta
        return _FusedState(
            k=s.k + 1,
            done=degenerate | converged,
            w=w, r=r, p=pn,
            zr=zr_new,
            beta=zr_new / jnp.where(s.zr == 0.0, 1.0, s.zr),
            diff=diff,
        )

    return body


def _fused_init(cv: Canvas, rhs) -> _FusedState:
    """w=0, r=b̃, p=0 with β=0 (the first sweep then forms p ← z + 0·p = z₀),
    ζ₀ = Σ b̃² (z = r on the scaled system)."""
    w0 = jnp.zeros((cv.rows, cv.cols), rhs.dtype)
    return _FusedState(
        k=jnp.zeros((), jnp.int32),
        done=jnp.asarray(False),
        w=w0, r=rhs, p=w0,
        zr=jnp.sum(rhs.astype(jnp.float32) ** 2),   # caller scales by h1h2
        beta=jnp.float32(0.0),
        diff=jnp.float32(jnp.inf),
    )


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _fused_solve(problem: Problem, cv: Canvas, interpret: bool,
                 parallel: bool, serial: bool, cs, cw, g, rhs, sc2):
    dtype = rhs.dtype
    body = _make_fused_body(problem, cv, interpret, cs, cw, g, sc2, dtype,
                            parallel, serial)

    def cond(s: _FusedState):
        return (~s.done) & (s.k < problem.iteration_cap)

    init = _fused_init(cv, rhs)
    init = init._replace(zr=init.zr * jnp.float32(problem.h1 * problem.h2))
    return lax.while_loop(cond, body, init)


def pallas_cg_solve_rhs(problem: Problem, rhs_grid64, bm: int | None = None,
                        interpret: bool | None = None,
                        dtype_name: str = "float32",
                        parallel: bool = False,
                        bn: int | None = None,
                        serial: bool | None = None):
    """Fused solve of ``A w = rhs`` for a caller-supplied RHS grid
    (fp64 host array, full (M+1, N+1) shape) — the hook mixed-precision
    refinement (``solvers.refine``) drives. Coefficient canvases come from
    the cache; only the RHS canvas is built per call.

    Returns ``(w64, iterations)`` with w accumulated on the host in fp64.
    """
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    cv, cs, cw, g, _, sc2, sc_int = build_canvases(problem, bm, dtype_name, bn)
    _, _, _, _, sc64 = scaled_stencil_fields(problem)
    M, N = problem.M, problem.N
    scaled = np.asarray(rhs_grid64, np.float64) * sc64
    rhs_canvas = np.zeros((cv.rows, cv.cols), np.float64)
    rhs_canvas[HALO : HALO + M - 1, cv.cg : cv.cg + N + 1] = scaled[1:M, :]
    rhs = jnp.asarray(rhs_canvas, jnp.dtype(dtype_name))
    s = _fused_solve(problem, cv, interpret, parallel,
                     _resolve_serial(serial, parallel), cs, cw, g, rhs, sc2)
    y = s.w[HALO : HALO + M - 1, cv.cg + 1 : cv.cg + N]
    w64 = np.zeros(problem.grid_shape, np.float64)
    w64[1:M, 1:N] = np.asarray(y, np.float64) * np.asarray(
        sc_int, np.float64
    )
    return w64, int(s.k)


def pallas_cg_solve(problem: Problem, bm: int | None = None,
                    interpret: bool | None = None,
                    dtype_name: str = "float32",
                    rhs_gate=None, parallel: bool = False,
                    bn: int | None = None,
                    serial: bool | None = None) -> PCGResult:
    """Single-device solve on the fused Pallas path (fp32, scaled system).

    A/B counterpart of ``solvers.pcg.pcg_solve(dtype=float32)`` — same
    mathematical iteration, two Pallas sweeps per step instead of XLA's
    fusion choices. ``interpret`` defaults to True off-TPU so the kernels
    run (and are tested) on CPU. ``rhs_gate``, if given, is a traced scalar
    the RHS is multiplied by — pass exactly 1.0 to chain benchmark solves
    with a data dependency (serialized, bit-identical result).
    ``parallel`` marks the tile grid parallel so Mosaic may split it
    across TensorCores (megacore chips) — see :func:`_grid_params`.
    ``bn`` selects the column-blocked canvas (see :class:`Canvas`), for
    grids too wide for a sane full-width strip height. ``serial`` selects
    the reduction-partial layout (None = the ``POISSON_TPU_SERIAL_REDUCE``
    env default; see the module constant).

    Runs under the span ``pallas_cg_solve`` with the children
    ``.prepare`` (canvases, gate), ``.launch`` and ``.finish`` (slice,
    unscale, pad): see :func:`poisson_tpu.obs.span`.
    """
    with obs.span("pallas_cg_solve"):
        with obs.span("pallas_cg_solve.prepare"):
            if interpret is None:
                interpret = jax.devices()[0].platform != "tpu"
            cv, cs, cw, g, rhs, sc2, sc_int = build_canvases(
                problem, bm, dtype_name, bn
            )
            if rhs_gate is not None:
                rhs = rhs * jnp.asarray(rhs_gate, rhs.dtype)
            serial = _resolve_serial(serial, parallel)
        with obs.span("pallas_cg_solve.launch"):
            s = _fused_solve(problem, cv, interpret, parallel, serial,
                             cs, cw, g, rhs, sc2)
        with obs.span("pallas_cg_solve.finish"):
            # Canvas → full-grid solution, unscaled: w = sc · y.
            M, N = problem.M, problem.N
            y = s.w[HALO : HALO + M - 1, cv.cg + 1 : cv.cg + N]
            w = jnp.pad(y * sc_int, 1)
    return PCGResult(w=w, iterations=s.k, diff=s.diff, residual_dot=s.zr)


def batched_bm(problem: Problem) -> int:
    """Strip height of the member stacks: :func:`pick_bm`'s strip count
    with the strips cut to the interior, ⌈(M−1)/nb⌉ rounded up to the
    sublane granule. At 400×600 that is 104 rows (416 canvas rows for 399
    interior ones) where ``pick_bm``'s 128 lays out 512; a stack pays
    that slack on every member."""
    interior = problem.M - 1
    nb = -(-interior // pick_bm(problem))
    per_strip = -(-interior // nb)
    return -(-per_strip // SUBLANE) * SUBLANE


def grid_to_canvas(problem: Problem, cv: Canvas, stack):
    """A (B, M+1, N+1) stack of full grids with a zero Dirichlet ring → the
    (B, R, C) canvas stack (full-width layout)."""
    M, N = problem.M, problem.N
    return jnp.pad(stack[:, 1:M, :], (
        (0, 0), (HALO, cv.rows - HALO - (M - 1)), (0, cv.cols - (N + 1))))


class _BatchedState(NamedTuple):
    """Per-member (B,) scalars and (B, R, C) canvas stacks."""

    k: jnp.ndarray
    done: jnp.ndarray
    w: jnp.ndarray
    r: jnp.ndarray
    p: jnp.ndarray
    zr: jnp.ndarray
    beta: jnp.ndarray
    diff: jnp.ndarray
    flag: jnp.ndarray


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _fused_solve_batched(problem: Problem, cv: Canvas, interpret: bool,
                         cs, cw, g, rhs, sc2, sc_int) -> PCGResult:
    """B right-hand sides (a (B, R, C) canvas stack) on one operator in one
    ``while_loop`` over the member-axis kernels.

    Each member runs :func:`_make_fused_body`'s arithmetic on its own
    scalars. A member that has stopped (done, or at the cap) is frozen
    without any canvas select: it gets α = β = 0, so kernel A forms p ← r
    and kernel B leaves its w and r unchanged, and its scalars keep their
    values. The loop runs while any member can advance. Flags follow the
    XLA batched loop: breakdown (⟨Ap, p⟩ under the guard: zero right-hand
    sides and padding stop at k = 1), nonfinite (‖Δw‖ or ζ not finite),
    converged, and FLAG_NONE at the cap."""
    members = rhs.shape[0]
    dtype = rhs.dtype
    h1h2 = jnp.float32(problem.h1 * problem.h2)
    norm_w = h1h2 if problem.weighted_norm else jnp.float32(1.0)
    cap = problem.iteration_cap

    def member_sums(parts):
        # Each member's strip partials, summed as the one-RHS loop sums
        # its (nb, 1) partials.
        return jax.vmap(jnp.sum)(parts.reshape(members, cv.nb, 1))

    def body(s: _BatchedState) -> _BatchedState:
        frozen = s.done | (s.k >= cap)
        pn, ap, denom_part = batched_direction_and_stencil(
            cv, jnp.where(frozen, 0.0, s.beta).astype(dtype), s.r, s.p,
            cs, cw, g, interpret=interpret,
        )
        denom = member_sums(denom_part) * h1h2
        degenerate = jnp.abs(denom) < _DENOM_TOL
        alpha32 = jnp.where(degenerate, 0.0,
                            s.zr / jnp.where(degenerate, 1.0, denom))
        w, r, diff_part, zr_part = batched_fused_update(
            cv, jnp.where(frozen, 0.0, alpha32).astype(dtype), pn, ap, sc2,
            s.w, s.r, interpret=interpret,
        )
        diff = jnp.abs(alpha32) * jnp.sqrt(member_sums(diff_part) * norm_w)
        zr = member_sums(zr_part) * h1h2
        converged = diff < problem.delta
        nonfinite = ~(jnp.isfinite(diff) & jnp.isfinite(zr))
        flag = jnp.where(
            degenerate, FLAG_BREAKDOWN,
            jnp.where(nonfinite, FLAG_NONFINITE,
                      jnp.where(converged, FLAG_CONVERGED, FLAG_NONE)),
        ).astype(jnp.int32)

        def keep(old, new):
            return jnp.where(frozen, old, new)

        return _BatchedState(
            k=keep(s.k, s.k + 1),
            done=keep(s.done, degenerate | converged | nonfinite),
            w=w, r=r, p=pn,
            zr=keep(s.zr, zr),
            beta=zr / jnp.where(s.zr == 0.0, 1.0, s.zr),
            diff=keep(s.diff, diff),
            flag=keep(s.flag, flag),
        )

    def cond(s: _BatchedState):
        return jnp.any((~s.done) & (s.k < cap))

    zeros = jnp.zeros_like(rhs)
    init = _BatchedState(
        k=jnp.zeros((members,), jnp.int32),
        done=jnp.zeros((members,), bool),
        w=zeros, r=rhs, p=zeros,
        zr=jax.vmap(lambda x: jnp.sum(x.astype(jnp.float32) ** 2))(rhs)
        * h1h2,
        beta=jnp.zeros((members,), jnp.float32),
        diff=jnp.full((members,), jnp.inf, jnp.float32),
        flag=jnp.full((members,), FLAG_NONE, jnp.int32),
    )
    s = lax.while_loop(cond, body, init)
    M, N = problem.M, problem.N
    y = s.w[:, HALO : HALO + M - 1, 1:N]
    return PCGResult(
        w=jnp.pad(y * sc_int, ((0, 0), (1, 1), (1, 1))),
        iterations=s.k, diff=s.diff, residual_dot=s.zr, flag=s.flag,
        max_iterations=jnp.max(s.k),
    )


# ---------------------------------------------------------------------------
# Checkpoint/resume on the fused path (see solvers.checkpoint for the format).
#
# The .npz layout is the portable full-grid PCGState the XLA checkpointed
# solvers write, under the (dtype="float32", scaled=True) fingerprint — so a
# fused-path checkpoint resumes on the XLA fp32-scaled path (single-device or
# sharded) and vice versa. State mapping: the fused loop carries the
# *previous* direction plus the pending β (applied at the top of kernel A),
# while PCGState carries the fully-updated direction d = z + β·p. Saving
# forms d = r + β·p (z = r on the scaled system); resuming inverts it with
# p := d − r, β := 1 (then r + 1·(d − r) = d, exact to one ulp per element).
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _fused_chunk(problem: Problem, cv: Canvas, interpret: bool, chunk: int,
                 parallel: bool, serial: bool,
                 cs, cw, g, sc2, s: _FusedState) -> _FusedState:
    """Advance the fused solve by at most ``chunk`` iterations."""
    body = _make_fused_body(problem, cv, interpret, cs, cw, g, sc2,
                            s.r.dtype, parallel, serial)
    stop_at = jnp.minimum(s.k + chunk, problem.iteration_cap)

    def cond(st: _FusedState):
        return (~st.done) & (st.k < stop_at)

    return lax.while_loop(cond, body, s)


def _canvas_to_full(problem: Problem, cv: Canvas, c) -> np.ndarray:
    """Canvas interior rows → the full (M+1, N+1) grid (zero ring; canvas
    ring columns are zero by the maskless invariant). cg-aware: content
    starts at canvas column cv.cg, so the portable full-grid state is
    identical whichever canvas geometry produced it."""
    M, N = problem.M, problem.N
    c = np.asarray(c)
    full = np.zeros((M + 1, N + 1), c.dtype)
    full[1:M, :] = c[HALO : HALO + M - 1, cv.cg : cv.cg + N + 1]
    return full


def _full_to_canvas(problem: Problem, cv: Canvas, full) -> jnp.ndarray:
    M, N = problem.M, problem.N
    full = np.asarray(full)
    c = np.zeros((cv.rows, cv.cols), full.dtype)
    c[HALO : HALO + M - 1, cv.cg : cv.cg + N + 1] = full[1:M, :]
    return jnp.asarray(c)


def pending_to_pcg_state(problem: Problem, cv: Canvas, *, k, done, sol, r,
                         pend, beta, zr, diff) -> PCGState:
    """Any pending-β solver state → the portable full-grid PCGState.

    Both the fused 2-sweep loop and the CA pair loop carry the PREVIOUS
    direction material plus a pending β (applied at the top of their
    first kernel), while PCGState stores the fully-updated direction
    d = z + β·p. This one converter owns that mapping (and the z = r
    convention of the scaled system) for every such solver."""
    r_host = np.asarray(r)
    d = r_host + float(beta) * np.asarray(pend)
    r_full = _canvas_to_full(problem, cv, r_host)
    return PCGState(
        k=np.asarray(k), done=np.asarray(done),
        w=_canvas_to_full(problem, cv, sol), r=r_full, z=r_full,
        p=_canvas_to_full(problem, cv, d),
        zr=np.asarray(zr), diff=np.asarray(diff),
    )


def pcg_state_to_pending(problem: Problem, cv: Canvas,
                         state: PCGState) -> dict:
    """Portable PCGState → pending-β canvases: pend := d − r with β := 1
    (then r + 1·(d − r) = d, exact to one ulp per element). Returned as a
    dict so each solver builds its own state type from it."""
    d = np.asarray(state.p, np.float32)
    r = np.asarray(state.r, np.float32)
    return dict(
        k=jnp.asarray(state.k, jnp.int32),
        done=jnp.asarray(np.asarray(state.done), bool),
        sol=_full_to_canvas(problem, cv, np.asarray(state.w, np.float32)),
        r=_full_to_canvas(problem, cv, r),
        pend=_full_to_canvas(problem, cv, d - r),
        zr=jnp.asarray(np.asarray(state.zr), jnp.float32),
        beta=jnp.float32(1.0),
        diff=jnp.asarray(np.asarray(state.diff), jnp.float32),
    )


def _fused_to_pcg_state(problem: Problem, cv: Canvas,
                        s: _FusedState) -> PCGState:
    """Fused state → the portable full-grid PCGState (y-space, z = r)."""
    return pending_to_pcg_state(
        problem, cv, k=s.k, done=s.done, sol=s.w, r=s.r, pend=s.p,
        beta=s.beta, zr=s.zr, diff=s.diff,
    )


def _pcg_state_to_fused(problem: Problem, cv: Canvas,
                        state: PCGState) -> _FusedState:
    """Portable PCGState → fused state: p := d − r with β := 1."""
    f = pcg_state_to_pending(problem, cv, state)
    return _FusedState(
        k=f["k"], done=f["done"], w=f["sol"], r=f["r"], p=f["pend"],
        zr=f["zr"], beta=f["beta"], diff=f["diff"],
    )


def pallas_cg_solve_checkpointed(problem: Problem, checkpoint_path: str,
                                 chunk: int = 200, bm: int | None = None,
                                 interpret: bool | None = None,
                                 keep_checkpoint: bool = False,
                                 parallel: bool = False,
                                 bn: int | None = None,
                                 serial: bool | None = None,
                                 keep_last: int = 2) -> PCGResult:
    """Fused-path solve with periodic state persistence and automatic
    resume — interoperable with the XLA fp32-scaled checkpoints (module
    comment above). fp32 only, like the fused path itself. The portable
    format is the full-grid PCGState, so any canvas geometry (full-width,
    auto- or explicitly column-blocked) saves and resumes the same file."""
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    serial = _resolve_serial(serial, parallel)
    from poisson_tpu.solvers.checkpoint import (
        _fingerprint,
        load_state,
        run_chunked,
    )

    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    cv, cs, cw, g, rhs, sc2, sc_int = build_canvases(
        problem, bm, "float32", bn
    )
    fp = _fingerprint(problem, "float32", True)

    saved = load_state(checkpoint_path, fp, keep_last=keep_last)
    if saved is None:
        s = _fused_init(cv, rhs)
        s = s._replace(zr=s.zr * jnp.float32(problem.h1 * problem.h2))
    else:
        s = _pcg_state_to_fused(problem, cv, saved)

    s = run_chunked(
        s,
        advance=lambda st: _fused_chunk(problem, cv, interpret, chunk,
                                        parallel, serial, cs, cw, g, sc2, st),
        to_portable=lambda st: _fused_to_pcg_state(problem, cv, st),
        path=checkpoint_path, fingerprint=fp, cap=problem.iteration_cap,
        keep_checkpoint=keep_checkpoint, keep_last=keep_last,
    )

    M, N = problem.M, problem.N
    y = s.w[HALO : HALO + M - 1, cv.cg + 1 : cv.cg + N]
    w = jnp.pad(y * sc_int, 1)
    return PCGResult(w=w, iterations=s.k, diff=s.diff, residual_dot=s.zr)
