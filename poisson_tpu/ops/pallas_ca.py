"""Communication-avoiding (s=2) CG on the fused Pallas canvases.

The fused 2-sweep iteration (``ops.pallas_cg``) moves ~14.7 canvas passes
of HBM traffic per CG iteration, and the measured 2400×3200 plateau sits
at the memory roofline (BENCH.md) — further speedup at that working-set
size must come from *algorithmic traffic reduction*, the same reasoning
that drives s-step/communication-avoiding Krylov methods (the reference's
per-iteration structure, one stencil + three reductions,
``stage4-mpi+cuda/poisson_mpi_cuda_f.cu:847-941``, has no such headroom
either). This module restructures TWO CG iterations into TWO sweeps:

  kernel C (basis sweep), one pass over 5 strip-read arrays (p_prev, r,
  cs, cw, g) plus the center-only sc² block:
      pn  ← r + β·p_prev          (the pending direction update, exactly
                                   kernel A's fused form)
      t1  ← Ã pn                  (computed on center±1 rows in-register)
      t2  ← Ã t1                  (second application — the s-step move)
      t3  ← Ã r
      12 Gram partials            (6 plain + 6 sc²-weighted, SURVEY §2.2's
                                   dot layer batched into one sweep)

  kernel D (update sweep), one pass over 6 center-read arrays:
      x ← x + (α₁+α₂β₁)·pn + α₂·r − α₂α₁·t1
      r ← r − (α₁+α₂β₁)·t1 + α₂α₁·t2 − α₂·t3
      p₁ ← r − α₁·t1 + β₁·pn      (β₂ is applied at the top of the NEXT
                                   kernel C — the same pending-β trick)
      partial Σr²

Both inner steps' α/β/convergence scalars come from the Gram matrix by
the standard CG recurrences (module tests pin them against the 2-sweep
path): with rr = ⟨r,r⟩,

    α₁ = rr/⟨pn,t1⟩               rr₁ = rr − 2α₁⟨r,t1⟩ + α₁²⟨t1,t1⟩
    β₁ = rr₁/rr                   ⟨p₁,Ãp₁⟩ = ⟨r₁,Ãr₁⟩ + 2β₁⟨pn,Ãr₁⟩ + β₁²⟨pn,t1⟩
    ⟨r₁,Ãr₁⟩ = ⟨r,t3⟩ − 2α₁⟨t1,t3⟩ + α₁²⟨t1,t2⟩
    ⟨pn,Ãr₁⟩ = ⟨r,t1⟩ − α₁⟨t1,t1⟩
    α₂ = rr₁/⟨p₁,Ãp₁⟩             (uses ⟨r,t2⟩ = ⟨t1,t3⟩, Ã symmetric)

and the reference's per-iteration convergence test ‖Δw‖ < δ is preserved
for BOTH inner steps (diff₁ = |α₁|·√⟨pn,sc²pn⟩; diff₂ = |α₂|·√⟨p₁,sc²p₁⟩
expanded in the sc²-weighted Gram), including stopping after an odd inner
step — golden iteration counts are odd (989, 2449).

Traffic: ≈ (5·(bm+2H)/bm + 1 + 4) + (6 + 3) ≈ 20.1 passes per TWO
iterations ≈ 10.1/iteration — a ~1.46× reduction over the 2-sweep path,
plus half the kernel launches and half the reduction rounds. fp32
numerics: the monomial 2-step basis is mildly worse conditioned than
plain CG; measured in fp32 it reproduces the golden counts exactly at
every published grid (tests + /tmp-validated 546/989/1858/2449).
It compiles for a v5e (tests/test_chip_compile.py); its speed on the
chip is not measured yet.

Full-width canvases only (the published grids' geometry). The kernels
serve two callers: the single-device drivers below, and the distributed
variant (``parallel.pallas_ca_sharded``), which runs the same sweeps per
shard with ``band`` widened ±2 rows and a ``colmask`` on the unweighted
Gram partials — the double stencil application reaches two cells past a
shard edge, so the sharded driver maintains width-2 halo rings (the
fused path's width-1 ``r``-ring induction does not extend to s=2:
reconstructing p₁'s halo locally would need t1 there, which needs pn on
a ring that grows by one per pair).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from poisson_tpu.config import Problem
from poisson_tpu.ops.pallas_cg import (
    HALO,
    Canvas,
    _block_spec,
    _canvas_shape,
    _colmask_spec,
    _grid_params,
    _kahan_add,
    _resolve_serial,
    _scalar_spec,
    _strip_in_spec,
    build_canvases,
    canvas_cols,
    named,
    strip_height,
    _shift_col_minus,
    _shift_col_plus,
)
from poisson_tpu.solvers.pcg import PCGResult, _DENOM_TOL

# The basis sweep holds ~16 strip-sized buffers in flight (6 inputs,
# 4 outputs, intermediates), vs ~12 for the 2-sweep kernels.
_CA_BUFFERS = 16
N_GRAM = 12   # a1 b1 e f g h | wpp wpr wpt wrr wrt wtt


def pick_bm_ca(problem: Problem) -> int:
    """CA strip height: the shared heuristic at the deeper buffer count."""
    return strip_height(canvas_cols(problem), problem.M - 1,
                        buffers=_CA_BUFFERS)


def _stencil(pn, cs, cw, g, lo, hi):
    """Difference-form Ã on rows [lo, hi) of an in-register strip.

    ``pn``/``cs``/``cw``/``g`` are full-strip arrays (bm+2·HALO rows);
    the result has hi−lo rows. Row r of the output corresponds to strip
    row lo+r; the ±1 row neighbours are strip rows lo+r∓1.
    """
    c = pn[lo:hi, :]
    cs_c = cs[lo:hi, :]
    cs_n = cs[lo + 1 : hi + 1, :]
    cw_c = cw[lo:hi, :]
    return (
        cs_n * (c - pn[lo + 1 : hi + 1, :])
        + cs_c * (c - pn[lo - 1 : hi - 1, :])
        + _shift_col_plus(cw_c) * (c - _shift_col_plus(c))
        + cw_c * (c - _shift_col_minus(c))
        + g[lo:hi, :] * c
    )


def _make_basis_kernel(cv: Canvas, serial: bool,
                       band: tuple[int, int] | None = None,
                       masked: bool = False):
    """Kernel C. Outputs pn, t1, t2, t3 (center blocks) + Gram partials.

    The strip's center rows are [HALO, HALO+bm). t1 is needed on
    center±1 rows (for t2's stencil), which the in-band recompute of pn
    over the whole strip makes available — the same trick kernel A uses
    for the direction update, extended one application deeper. All
    canvases are zero outside the interior, so the extended rows compute
    correct (zero) values at the grid boundary without masking.

    ``band`` is the canvas-row range [lo, hi) on which the direction
    update is live (single-device: the interior band). The sharded
    caller widens it ±2 rows so pn is real on the shard's width-2 halo
    ring — t1 on ±1 (feeding t2 at the shard edge) then reads exchanged
    neighbour data, not zeros. ``masked`` adds a (1, C) column-mask
    operand multiplying the six unweighted Gram partials: sharded
    canvases carry real neighbour values in their halo columns, which
    must not enter owned-interior reductions (the six sc²-weighted
    partials need no mask — the sharded builder already restricts sc² to
    the owned interior, exactly like the fused path).
    """
    h = HALO
    band_lo, band_hi = band if band is not None else (h, cv.rows - h)

    def kernel(beta_ref, pprev_ref, r_ref, cs_ref, cw_ref, g_ref, sc2_ref,
               *rest):
        colmask_ref = None
        if masked:
            colmask_ref, *rest = rest
        comp_ref = None
        if serial:
            *rest, comp_ref = rest
        pn_ref, t1_ref, t2_ref, t3_ref, gram_ref = rest
        i = pl.program_id(0)
        beta = beta_ref[0, 0]
        off = i * cv.bm
        rows = off + lax.broadcasted_iota(
            jnp.int32, (cv.bm + 2 * h, 1), 0
        )
        in_band = (rows >= band_lo) & (rows < band_hi)
        pn = jnp.where(in_band, r_ref[:] + beta * pprev_ref[:], 0.0)
        cs = cs_ref[:]
        cw = cw_ref[:]
        g = g_ref[:]
        r = r_ref[:]

        # t1 on center±1 rows (strip rows h-1 .. h+bm+1), then t2 and t3
        # on the center rows only.
        t1_ext = _stencil(pn, cs, cw, g, h - 1, h + cv.bm + 1)
        t1 = t1_ext[1:-1, :]
        # Second application reads t1_ext through a zero-padded
        # strip-shaped view so _stencil's row indexing stays uniform
        # (static concatenation — no dynamic slicing in the kernel).
        zrows = jnp.zeros((h - 1, pn.shape[1]), pn.dtype)
        t1_pad = jnp.concatenate([zrows, t1_ext, zrows], axis=0)
        t2 = _stencil(t1_pad, cs, cw, g, h, h + cv.bm)
        t3 = _stencil(r, cs, cw, g, h, h + cv.bm)

        pn_c = pn[h:-h, :]
        r_c = r[h:-h, :]
        sc2 = sc2_ref[:]
        mask = colmask_ref[:] if masked else None

        pn_ref[:] = pn_c
        t1_ref[:] = t1
        t2_ref[:] = t2
        t3_ref[:] = t3

        def plain(u, v):
            uv = u * v
            if masked:
                uv = uv * mask
            return jnp.sum(uv, dtype=jnp.float32)

        sums = (
            plain(pn_c, t1),                          # a1
            plain(t1, t1),                            # b1
            plain(r_c, t1),                           # e
            plain(r_c, t3),                           # f
            plain(t1, t3),                            # g
            plain(t1, t2),                            # h
            jnp.sum(pn_c * pn_c * sc2, dtype=jnp.float32),   # wpp
            jnp.sum(pn_c * r_c * sc2, dtype=jnp.float32),    # wpr
            jnp.sum(pn_c * t1 * sc2, dtype=jnp.float32),     # wpt
            jnp.sum(r_c * r_c * sc2, dtype=jnp.float32),     # wrr
            jnp.sum(r_c * t1 * sc2, dtype=jnp.float32),      # wrt
            jnp.sum(t1 * t1 * sc2, dtype=jnp.float32),       # wtt
        )
        if serial:
            @pl.when(i == 0)
            def _():
                for j in range(N_GRAM):
                    gram_ref[0, j] = 0.0
                    comp_ref[j] = 0.0

            for j, val in enumerate(sums):
                y = val - comp_ref[j]
                t = gram_ref[0, j] + y
                comp_ref[j] = (t - gram_ref[0, j]) - y
                gram_ref[0, j] = t
        else:
            for j, val in enumerate(sums):
                gram_ref[i, j] = val

    return kernel


def _make_pair_update_kernel(cv: Canvas, serial: bool,
                             masked: bool = False):
    """Kernel D. Scalars arrive as a (1, 8) SMEM row:
    [c_p, a2, a2a1, alpha1, beta1, 0, 0, 0] (padded for alignment).
    ``masked`` adds a (1, C) column mask on the Σr'² partial (sharded
    canvases carry neighbour values in halo columns)."""

    def kernel(coef_ref, pn_ref, t1_ref, t2_ref, t3_ref, *rest):
        colmask_ref = None
        if masked:
            colmask_ref, *rest = rest
        x_ref, r_ref, *rest = rest
        comp_ref = None
        if serial:
            *rest, comp_ref = rest
        x_out_ref, r_out_ref, p1_ref, rr_ref = rest
        c_p = coef_ref[0, 0]
        a2 = coef_ref[0, 1]
        a2a1 = coef_ref[0, 2]
        alpha1 = coef_ref[0, 3]
        beta1 = coef_ref[0, 4]
        pn = pn_ref[:]
        t1 = t1_ref[:]
        r = r_ref[:]
        r_new = r - c_p * t1 + a2a1 * t2_ref[:] - a2 * t3_ref[:]
        x_out_ref[:] = x_ref[:] + c_p * pn + a2 * r - a2a1 * t1
        r_out_ref[:] = r_new
        p1_ref[:] = r - alpha1 * t1 + beta1 * pn
        rr2 = r_new * r_new
        if masked:
            rr2 = rr2 * colmask_ref[:]
        part = jnp.sum(rr2, dtype=jnp.float32)
        if serial:
            _kahan_add(pl.program_id(0) == 0, rr_ref, comp_ref, 0, part)
        else:
            rr_ref[pl.program_id(0), 0] = part

    return kernel


def _gram_out_spec(serial: bool, nb: int):
    # Both variants are whole-array SMEM windows: Mosaic exempts only
    # trivial-window SMEM blocks from its (8, 128) tiling rules, so the
    # per-row ``(1, N_GRAM) @ (i, 0)`` map this replaces lowered only
    # when nb == 1 (see ops.pallas_cg._partial_out_spec — the round-3
    # hardware-failure class). Strip i writes row i in-kernel.
    if serial:
        return (
            pl.BlockSpec(memory_space=pltpu.SMEM),
            jax.ShapeDtypeStruct((1, N_GRAM), jnp.float32),
        )
    return (
        pl.BlockSpec(memory_space=pltpu.SMEM),
        jax.ShapeDtypeStruct((nb, N_GRAM), jnp.float32),
    )


def basis_sweep(cv: Canvas, beta, pprev, r, cs, cw, g, sc2, *,
                interpret: bool, parallel: bool = False,
                serial: bool | None = None,
                band: tuple[int, int] | None = None, colmask=None):
    """pn, t1, t2, t3, Gram partials — one HBM sweep (kernel C).

    ``band``/``colmask`` select the sharded variant (see the kernel
    factory); defaults are the single-device interior band, no mask."""
    serial = _resolve_serial(serial, parallel)
    masked = colmask is not None
    gram_spec, gram_shape = _gram_out_spec(serial, cv.nb)
    in_specs = [
        _scalar_spec(),
        _strip_in_spec(cv),   # p_prev
        _strip_in_spec(cv),   # r
        _strip_in_spec(cv),   # cs
        _strip_in_spec(cv),   # cw (±1 rows feed the double apply)
        _strip_in_spec(cv),   # g  (ditto)
        _block_spec(cv),      # sc2 (center-only, weighted Gram)
    ]
    operands = [beta, pprev, r, cs, cw, g, sc2]
    if masked:
        in_specs.append(_colmask_spec(cv))
        operands.append(colmask)
    return pl.pallas_call(
        _make_basis_kernel(cv, serial, band, masked),
        grid=(cv.nb,),
        in_specs=in_specs,
        out_specs=[
            _block_spec(cv), _block_spec(cv), _block_spec(cv),
            _block_spec(cv), gram_spec,
        ],
        out_shape=[
            _canvas_shape(cv, r.dtype),
            _canvas_shape(cv, r.dtype),
            _canvas_shape(cv, r.dtype),
            _canvas_shape(cv, r.dtype),
            gram_shape,
        ],
        scratch_shapes=(
            [pltpu.SMEM((N_GRAM,), jnp.float32)] if serial else []
        ),
        interpret=interpret,
        **named("basis_sweep"),
        **_grid_params(parallel),
    )(*operands)


def pair_update(cv: Canvas, coefs, pn, t1, t2, t3, x, r, *,
                interpret: bool, parallel: bool = False,
                serial: bool | None = None, colmask=None):
    """x', r', p₁, Σr'² partials — one HBM sweep (kernel D)."""
    serial = _resolve_serial(serial, parallel)
    masked = colmask is not None
    # Whole-array SMEM windows (strip i writes its own cell in-kernel;
    # see _gram_out_spec / ops.pallas_cg._partial_out_spec for why the
    # per-cell block maps they replace could not lower for nb > 1).
    rr_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    rr_shape = jax.ShapeDtypeStruct((1, 1) if serial else (cv.nb, 1),
                                    jnp.float32)
    coef_spec = pl.BlockSpec((1, 8), lambda i: (0, 0),
                             memory_space=pltpu.SMEM)
    in_specs = [
        coef_spec,
        _block_spec(cv),   # pn
        _block_spec(cv),   # t1
        _block_spec(cv),   # t2
        _block_spec(cv),   # t3
    ]
    operands = [coefs, pn, t1, t2, t3]
    if masked:
        in_specs.append(_colmask_spec(cv))
        operands.append(colmask)
    x_idx = len(operands)
    in_specs += [_block_spec(cv), _block_spec(cv)]
    operands += [x, r]
    return pl.pallas_call(
        _make_pair_update_kernel(cv, serial, masked),
        grid=(cv.nb,),
        in_specs=in_specs,
        out_specs=[_block_spec(cv), _block_spec(cv), _block_spec(cv),
                   rr_spec],
        out_shape=[
            _canvas_shape(cv, x.dtype),
            _canvas_shape(cv, x.dtype),
            _canvas_shape(cv, x.dtype),
            rr_shape,
        ],
        input_output_aliases={x_idx: 0, x_idx + 1: 1},   # x → x', r → r'
        scratch_shapes=([pltpu.SMEM((1,), jnp.float32)] if serial else []),
        interpret=interpret,
        **named("pair_update"),
        **_grid_params(parallel),
    )(*operands)


class _CAState(NamedTuple):
    k: jnp.ndarray
    done: jnp.ndarray
    x: jnp.ndarray
    r: jnp.ndarray
    pprev: jnp.ndarray   # p₁ of the previous pair; β pending
    rr: jnp.ndarray      # ⟨r, r⟩·h1h2
    beta: jnp.ndarray    # pending β (applied at the top of kernel C)
    diff: jnp.ndarray


class PairDecision(NamedTuple):
    """Everything the pair-update sweep and the state assembly need, from
    one pair's (globally summed) Gram vector — shared by the
    single-device and sharded bodies so their scalar recurrences are
    identical by construction."""

    coefs: jnp.ndarray   # (1, 8) kernel-D scalar row
    only1: jnp.ndarray
    stop1: jnp.ndarray
    deg2: jnp.ndarray
    short: jnp.ndarray   # this pair advanced k by 1, not 2
    rr1: jnp.ndarray
    diff1: jnp.ndarray
    diff2: jnp.ndarray


def pair_scalars(problem: Problem, rr, k, gsum, dtype) -> PairDecision:
    """The α/β/convergence recurrences for one CA pair (module doc).

    ``gsum`` is the (12,) Gram vector already summed over strips (and,
    in the sharded caller, psum'd over the mesh) and scaled by h1·h2;
    ``rr`` = ⟨r, r⟩·h1h2 carried from the previous pair."""
    h1h2 = jnp.float32(problem.h1 * problem.h2)
    norm_w = h1h2 if problem.weighted_norm else jnp.float32(1.0)
    delta = jnp.float32(problem.delta)
    a1, b1, e, f, gg, hh = (gsum[j] for j in range(6))
    wpp, wpr, wpt, wrr, wrt, wtt = (gsum[6 + j] for j in range(6))

    deg1 = jnp.abs(a1) < _DENOM_TOL
    alpha1 = jnp.where(deg1, 0.0, rr / jnp.where(deg1, 1.0, a1))
    diff1 = jnp.abs(alpha1) * jnp.sqrt(
        jnp.maximum(wpp * norm_w / h1h2, 0.0)
    )
    rr1 = jnp.maximum(rr - 2 * alpha1 * e + alpha1 * alpha1 * b1, 0.0)
    beta1 = rr1 / jnp.where(rr == 0.0, 1.0, rr)
    rAr1 = f - 2 * alpha1 * gg + alpha1 * alpha1 * hh
    pAr1 = e - alpha1 * b1
    p1Ap1 = rAr1 + 2 * beta1 * pAr1 + beta1 * beta1 * a1
    deg2 = jnp.abs(p1Ap1) < _DENOM_TOL
    alpha2 = jnp.where(deg2, 0.0, rr1 / jnp.where(deg2, 1.0, p1Ap1))
    w11 = wrr - 2 * alpha1 * wrt + alpha1 * alpha1 * wtt
    w1p = wpr - alpha1 * wpt
    wp1p1 = w11 + 2 * beta1 * w1p + beta1 * beta1 * wpp
    diff2 = jnp.abs(alpha2) * jnp.sqrt(
        jnp.maximum(wp1p1 * norm_w / h1h2, 0.0)
    )

    stop1 = deg1 | (diff1 < delta)
    cap_stop = k + 1 >= problem.iteration_cap
    # Apply only the first inner step when: it converged (stop1), the
    # second step is degenerate (deg2 — its α would be garbage), or
    # the iteration cap allows exactly one more step (the 2-sweep
    # path reports iterations == cap exactly; so must this one).
    only1 = stop1 | deg2 | cap_stop
    a2 = jnp.where(only1, 0.0, alpha2)
    c_p = alpha1 + a2 * beta1
    coefs = jnp.stack(
        [c_p, a2, a2 * alpha1, alpha1, beta1,
         jnp.float32(0), jnp.float32(0), jnp.float32(0)]
    ).reshape(1, 8).astype(dtype)
    return PairDecision(
        coefs=coefs, only1=only1, stop1=stop1, deg2=deg2,
        short=stop1 | cap_stop, rr1=rr1, diff1=diff1, diff2=diff2,
    )


def assemble_pair_state(problem: Problem, s: _CAState, d: PairDecision,
                        x, r, pprev, rr2) -> _CAState:
    """Post-sweep state assembly, shared with the sharded body.

    When only step 1 was applied, the direction material for the next
    sweep is pn (with β = rr₂/rr), not p₁ — which keeps a cap-truncated
    pair mathematically identical to the 2-sweep path's state at the
    same k. k/diff mirror the 2-sweep path exactly, including the (never
    observed for this SPD system) degenerate second step: the 2-sweep
    loop COUNTS the degenerate iteration with α=0 and diff=0, so deg2
    increments by 2 and reports 0 — only a converged or cap-truncated
    first step increments by 1."""
    rr_prev = jnp.where(d.only1, s.rr, d.rr1)
    delta = jnp.float32(problem.delta)
    return _CAState(
        k=s.k + jnp.where(d.short, 1, 2).astype(jnp.int32),
        done=d.stop1 | d.deg2 | ((~d.only1) & (d.diff2 < delta)),
        x=x, r=r,
        pprev=pprev,
        rr=rr2,
        beta=rr2 / jnp.where(rr_prev == 0.0, 1.0, rr_prev),
        diff=jnp.where(
            d.short, d.diff1, jnp.where(d.deg2, jnp.float32(0.0), d.diff2)
        ),
    )


def _make_ca_body(problem: Problem, cv: Canvas, interpret: bool,
                  cs, cw, g, sc2, dtype, parallel: bool, serial: bool):
    h1h2 = jnp.float32(problem.h1 * problem.h2)

    def body(s: _CAState) -> _CAState:
        beta = jnp.reshape(s.beta, (1, 1)).astype(dtype)
        pn, t1, t2, t3, gram = basis_sweep(
            cv, beta, s.pprev, s.r, cs, cw, g, sc2,
            interpret=interpret, parallel=parallel, serial=serial,
        )
        gsum = jnp.sum(gram, axis=0) * h1h2
        d = pair_scalars(problem, s.rr, s.k, gsum, dtype)
        x, r, p1, rr_part = pair_update(
            cv, d.coefs, pn, t1, t2, t3, s.x, s.r,
            interpret=interpret, parallel=parallel, serial=serial,
        )
        rr2 = jnp.sum(rr_part) * h1h2
        return assemble_pair_state(
            problem, s, d, x, r, jnp.where(d.only1, pn, p1), rr2
        )

    return body


def _ca_init(problem: Problem, cv: Canvas, rhs) -> _CAState:
    """x=0, r=b̃, β=0 (the first basis sweep then forms pn ← r + 0 = r₀) —
    the ONE initial-state recipe, shared by the one-shot and checkpointed
    drivers so they start from bit-identical states."""
    zeros = jnp.zeros((cv.rows, cv.cols), rhs.dtype)
    rr0 = jnp.sum(rhs.astype(jnp.float32) ** 2) * jnp.float32(
        problem.h1 * problem.h2
    )
    return _CAState(
        k=jnp.zeros((), jnp.int32),
        done=jnp.asarray(False),
        x=zeros, r=rhs, pprev=zeros,
        rr=rr0,
        beta=jnp.float32(0.0),
        diff=jnp.float32(jnp.inf),
    )


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _ca_solve(problem: Problem, cv: Canvas, interpret: bool,
              parallel: bool, serial: bool, cs, cw, g, rhs, sc2):
    dtype = rhs.dtype
    body = _make_ca_body(problem, cv, interpret, cs, cw, g, sc2, dtype,
                         parallel, serial)

    def cond(s: _CAState):
        return (~s.done) & (s.k < problem.iteration_cap)

    return lax.while_loop(cond, body, _ca_init(problem, cv, rhs))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _ca_chunk(problem: Problem, cv: Canvas, interpret: bool, chunk: int,
              parallel: bool, serial: bool,
              cs, cw, g, sc2, s: _CAState) -> _CAState:
    """Advance the CA solve by ~``chunk`` iterations (a pair straddling
    the chunk boundary overshoots by one — chunking must not change the
    iterate sequence, so only the global cap ever truncates a pair)."""
    body = _make_ca_body(problem, cv, interpret, cs, cw, g, sc2,
                         s.r.dtype, parallel, serial)
    stop_at = jnp.minimum(s.k + chunk, problem.iteration_cap)

    def cond(st: _CAState):
        return (~st.done) & (st.k < stop_at)

    return lax.while_loop(cond, body, s)


def ca_cg_solve_checkpointed(problem: Problem, checkpoint_path: str,
                             chunk: int = 200, bm: int | None = None,
                             interpret: bool | None = None,
                             keep_checkpoint: bool = False,
                             parallel: bool = False,
                             serial: bool | None = None,
                             keep_last: int = 2) -> PCGResult:
    """CA solve with periodic state persistence and automatic resume.

    Same portable full-grid ``PCGState`` format and (float32, scaled)
    fingerprint as every other checkpointed solver: the CA state's
    pending pair (pprev, β) maps to the stored updated direction
    d = r + β·pprev exactly like the 2-sweep fused path's, so a CA
    checkpoint resumes on the fused or XLA fp32-scaled paths and vice
    versa — cross-ALGORITHM resume, not just cross-backend.
    """
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    serial = _resolve_serial(serial, parallel)
    from poisson_tpu.ops.pallas_cg import (
        pcg_state_to_pending,
        pending_to_pcg_state,
    )
    from poisson_tpu.solvers.checkpoint import (
        _fingerprint,
        load_state,
        run_chunked,
    )

    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    if bm is None:
        bm = pick_bm_ca(problem)
    cv, cs, cw, g, rhs, sc2, sc_int = build_canvases(
        problem, bm, "float32", 0
    )
    fp = _fingerprint(problem, "float32", True)

    def to_portable(s: _CAState):
        return pending_to_pcg_state(
            problem, cv, k=s.k, done=s.done, sol=s.x, r=s.r, pend=s.pprev,
            beta=s.beta, zr=s.rr, diff=s.diff,
        )

    saved = load_state(checkpoint_path, fp, keep_last=keep_last)
    if saved is None:
        s = _ca_init(problem, cv, rhs)
    else:
        f = pcg_state_to_pending(problem, cv, saved)
        s = _CAState(
            k=f["k"], done=f["done"], x=f["sol"], r=f["r"],
            pprev=f["pend"], rr=f["zr"], beta=f["beta"], diff=f["diff"],
        )

    s = run_chunked(
        s,
        advance=lambda st: _ca_chunk(problem, cv, interpret, chunk,
                                     parallel, serial, cs, cw, g, sc2, st),
        to_portable=to_portable,
        path=checkpoint_path, fingerprint=fp, cap=problem.iteration_cap,
        keep_checkpoint=keep_checkpoint, keep_last=keep_last,
    )

    M, N = problem.M, problem.N
    y = s.x[HALO : HALO + M - 1, 1:N]
    w = jnp.pad(y * sc_int, 1)
    return PCGResult(w=w, iterations=s.k, diff=s.diff, residual_dot=s.rr)


def ca_cg_solve(problem: Problem, bm: int | None = None,
                interpret: bool | None = None,
                dtype_name: str = "float32",
                rhs_gate=None, parallel: bool = False,
                serial: bool | None = None) -> PCGResult:
    """Single-device solve on the communication-avoiding fused path.

    Same system, same convergence criterion, same golden iteration
    counts as ``pallas_cg_solve`` — ~10.1 canvas passes per iteration
    instead of ~14.7 (module doc). Full-width canvases only.
    """
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    if bm is None:
        bm = pick_bm_ca(problem)
    cv, cs, cw, g, rhs, sc2, sc_int = build_canvases(
        problem, bm, dtype_name, 0
    )
    if rhs_gate is not None:
        rhs = rhs * jnp.asarray(rhs_gate, rhs.dtype)
    s = _ca_solve(problem, cv, interpret, parallel,
                  _resolve_serial(serial, parallel), cs, cw, g, rhs, sc2)
    M, N = problem.M, problem.N
    y = s.x[HALO : HALO + M - 1, 1:N]
    w = jnp.pad(y * sc_int, 1)
    return PCGResult(w=w, iterations=s.k, diff=s.diff, residual_dot=s.rr)
