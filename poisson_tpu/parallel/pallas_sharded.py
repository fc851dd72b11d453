"""Distributed fused-Pallas PCG: stage4's full combination, TPU-native.

The reference's final stage pairs accelerator kernels with distribution
(MPI+CUDA, ``stage4-mpi+cuda/poisson_mpi_cuda_f.cu:688-983``): CUDA kernels
per rank, host-staged halo exchange on the search direction p, Allreduce'd
scalars. This module is that combination re-designed for a TPU pod: the
fused two-sweep Pallas iteration (``ops.pallas_cg``) runs per shard inside
``shard_map`` over a 2D mesh, with ``ppermute`` halos and ``psum`` scalars.

**The halo exchange moves from p to r.** The reference refreshes p's ghost
ring every iteration because the stencil consumes p. But in the fused
restructuring the direction update ``p ← z + β·p`` runs *inside* the
stencil sweep, so a shard can compute its neighbour's edge values of the
new p by itself — z (= r on the scaled system) and the old p at the halo
ring suffice, and β is mesh-replicated. By induction the p halos stay
fresh without ever being communicated, provided r's halo ring is refreshed
once per iteration (r's halo cannot be recomputed locally: it would need a
second ghost ring for Ap). Per iteration the wire traffic is therefore the
same as the reference's — four thin ``ppermute`` slices (of r, not p) and
three ``psum`` scalars — while the arithmetic stays two HBM sweeps.

Shard canvas layout (cf. the single-device canvas, ``ops.pallas_cg``):

  - the shard owns m̂ interior rows × n̂ interior columns, with
    m̂ = ⌈(M−1)/Px⌉ rounded up to a multiple of the strip height bm (so the
    strip grid tiles the owned band exactly and the halo rows fall in the
    guard bands, outside every kernel reduction);
  - canvas row HALO+li ↔ global grid row ix·m̂+1+li; canvas column lj ↔
    global grid column iy·n̂+lj (column 0 / n̂+1 are the halo columns);
  - halo *columns* live inside the summed band, so kernel reductions take a
    (1, C) column mask; halo *rows* sit outside the written band and the
    guard rows are absorbed by the kernels' band gating;
  - canvas columns beyond n̂+1 (lane padding) are zeroed in every
    coefficient canvas — on a shard they would otherwise alias a further
    neighbour's data (the global grid continues past the halo).

Correctness of the zero-padded decomposition follows the same induction as
``parallel.pcg_sharded``: padded rows/columns have zero scaled coefficients
and zero RHS, so p, Ap, r stay identically zero there through every sweep.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from poisson_tpu import obs
from poisson_tpu.config import Problem
from poisson_tpu.ops.pallas_cg import (
    HALO,
    LANE,
    SUBLANE,
    Canvas,
    _resolve_serial,
    direction_and_stencil,
    fused_update,
    diagonal_residual_canvas,
    scaled_stencil_fields,
    strip_height,
)
from poisson_tpu.parallel.mesh import X_AXIS, Y_AXIS
from poisson_tpu.solvers.pcg import PCGResult, _DENOM_TOL
from jax import shard_map

_AXES = (X_AXIS, Y_AXIS)


class ShardSpec(NamedTuple):
    """Static per-shard canvas geometry (hashable; jit static arg)."""

    cv: Canvas
    m_blk: int   # owned interior rows per shard (= cv.nb · cv.bm)
    n_blk: int   # owned interior cols per shard


def shard_spec(problem: Problem, px: int, py: int,
               bm: int | None = None) -> ShardSpec:
    n_blk = -(-(problem.N - 1) // py)
    cols = ((n_blk + 2 + LANE - 1) // LANE) * LANE
    if bm is None:
        bm = strip_height(cols, -(-(problem.M - 1) // px))
    if bm <= 0 or bm % SUBLANE != 0:
        raise ValueError(f"bm must be a positive multiple of {SUBLANE}, got {bm}")
    # Owned rows rounded up to the strip height: strips tile the owned band
    # exactly, so the halo rows stay outside every kernel reduction.
    m_min = -(-(problem.M - 1) // px)
    nb = -(-m_min // bm)
    m_blk = nb * bm
    cv = Canvas(bm=bm, nb=nb, rows=nb * bm + 2 * HALO, cols=cols)
    return ShardSpec(cv=cv, m_blk=m_blk, n_blk=n_blk)


@functools.lru_cache(maxsize=8)
def _shard_canvases(problem: Problem, px: int, py: int, spec: ShardSpec,
                    dtype_name: str):
    """Host fp64 setup → stacked per-shard canvases (mesh order, x-major).

    Returns (cs, cw, rhs, sc2) of shape (P, R, C), sc_int of shape
    (P, m̂, n̂) for solution extraction, and the (1, C) column mask."""
    cv = spec.cv
    m_blk, n_blk = spec.m_blk, spec.n_blk
    dtype = jnp.dtype(dtype_name)
    M, N = problem.M, problem.N

    gcs, gcw, sc2_64, rhs64, sc64 = scaled_stencil_fields(problem)

    # One zero-padded global scratch big enough for every shard's
    # (row0 + canvas extent) slice; canvas row HALO-1 maps to global grid
    # row ix·m̂, canvas col 0 to global grid col iy·n̂.
    height = (px - 1) * m_blk + (cv.rows - (HALO - 1)) + 1
    width = (py - 1) * n_blk + cv.cols + 1
    big = np.zeros((max(height, M + 1), max(width, N + 1)), np.float64)

    def stacked(field, zero_pad_cols: bool, zero_halo_cols: bool = False):
        big[:] = 0.0
        big[: M + 1, : N + 1] = field
        out = np.zeros((px * py, cv.rows, cv.cols), np.float64)
        for ix in range(px):
            for iy in range(py):
                sl = big[
                    ix * m_blk : ix * m_blk + cv.rows - (HALO - 1),
                    iy * n_blk : iy * n_blk + cv.cols,
                ]
                out[ix * py + iy, HALO - 1 :, :] = sl
        if zero_pad_cols:
            out[:, :, n_blk + 2 :] = 0.0
        if zero_halo_cols:
            out[:, :, 0] = 0.0
            out[:, :, n_blk + 1] = 0.0
        return out

    cs_st = stacked(gcs, zero_pad_cols=True)
    cw_st = stacked(gcw, zero_pad_cols=True)
    # Diagonal residual per shard, from its own canvases (fp64) — the
    # difference-form stencil weight (ops.pallas_cg.diagonal_residual_canvas).
    g_st = np.stack([
        diagonal_residual_canvas(cs_st[s], cw_st[s])
        for s in range(px * py)
    ])
    # rhs keeps real values in its halo ring: that ring seeds r's (and via
    # p0 = r0, p's) fresh halos at iteration 0.
    rhs_st = stacked(rhs64, zero_pad_cols=True)
    # sc2 is a pure reduction weight: restrict it to the owned interior.
    sc2_st = stacked(sc2_64, zero_pad_cols=True, zero_halo_cols=True)

    sc_int = np.zeros((px * py, m_blk, n_blk), np.float64)
    for ix in range(px):
        for iy in range(py):
            blk = sc64[
                1 + ix * m_blk : 1 + ix * m_blk + m_blk,
                1 + iy * n_blk : 1 + iy * n_blk + n_blk,
            ]
            sc_int[ix * py + iy, : blk.shape[0], : blk.shape[1]] = blk
    sc_int = jnp.asarray(sc_int, dtype)

    colmask = np.zeros((1, cv.cols), np.float64)
    colmask[0, 1 : n_blk + 1] = 1.0
    as_dev = lambda x: jnp.asarray(x, dtype)
    return (as_dev(cs_st), as_dev(cw_st), as_dev(g_st), as_dev(rhs_st),
            as_dev(sc2_st), sc_int, as_dev(colmask))


class _State(NamedTuple):
    k: jnp.ndarray
    done: jnp.ndarray
    w: jnp.ndarray
    r: jnp.ndarray
    p: jnp.ndarray
    zr: jnp.ndarray
    beta: jnp.ndarray
    diff: jnp.ndarray


def _exchange_r_halo(r, spec: ShardSpec, px: int, py: int):
    """Refresh r's halo ring: 4 thin ppermute slices (the reference's four
    MPI messages, ``stage2:…cpp:241-347`` — but of r, see module doc).
    Mesh-edge shards receive ppermute's zero fill = Dirichlet data."""
    from poisson_tpu.parallel.halo import _shift_down, _shift_up

    lo, hi = HALO, HALO + spec.m_blk
    top = _shift_down(r[hi - 1, :], X_AXIS, px)
    bot = _shift_up(r[lo, :], X_AXIS, px)
    r = r.at[lo - 1, :].set(top).at[hi, :].set(bot)
    left = _shift_down(r[:, spec.n_blk], Y_AXIS, py)
    right = _shift_up(r[:, 1], Y_AXIS, py)
    return r.at[:, 0].set(left).at[:, spec.n_blk + 1].set(right)


def _make_shard_body(problem: Problem, spec: ShardSpec, px: int, py: int,
                     interpret: bool, cs, cw, g, sc2, colmask, dtype,
                     parallel: bool = False, serial: bool = False):
    """One fused sharded iteration as a pure state→state function — shared
    by the convergence while_loop and the chunked checkpointed solve."""
    cv = spec.cv
    h1h2 = jnp.float32(problem.h1 * problem.h2)
    norm_w = h1h2 if problem.weighted_norm else jnp.float32(1.0)
    band = (HALO - 1, HALO + spec.m_blk + 1)  # owned rows + halo ring
    lo, hi = HALO, HALO + spec.m_blk

    def psum(x):
        return lax.psum(x, _AXES)

    def body(s: _State) -> _State:
        beta = jnp.reshape(s.beta, (1, 1)).astype(dtype)
        pn, ap, denom_part = direction_and_stencil(
            cv, beta, s.r, s.p, cs, cw, g, interpret=interpret,
            band=band, colmask=colmask, parallel=parallel, serial=serial,
        )
        # Halo rows of the new direction: identical to what the row
        # neighbour computed for its own edge (z = r and old-p halos are
        # fresh, β is replicated). Halo *columns* were computed in-sweep.
        b = s.beta.astype(dtype)
        pn = pn.at[lo - 1, :].set(s.r[lo - 1, :] + b * s.p[lo - 1, :])
        pn = pn.at[hi, :].set(s.r[hi, :] + b * s.p[hi, :])

        denom = psum(jnp.sum(denom_part)) * h1h2
        degenerate = jnp.abs(denom) < _DENOM_TOL
        alpha32 = jnp.where(
            degenerate, 0.0, s.zr / jnp.where(degenerate, 1.0, denom)
        )
        alpha = jnp.reshape(alpha32, (1, 1)).astype(dtype)

        w, r, diff_part, zr_part = fused_update(
            cv, alpha, pn, ap, sc2, s.w, s.r, interpret=interpret,
            colmask=colmask, parallel=parallel, serial=serial,
        )
        diff = jnp.abs(alpha32) * jnp.sqrt(psum(jnp.sum(diff_part)) * norm_w)
        zr_new = psum(jnp.sum(zr_part)) * h1h2
        converged = diff < problem.delta

        r = _exchange_r_halo(r, spec, px, py)
        return _State(
            k=s.k + 1,
            done=degenerate | converged,
            w=w, r=r, p=pn,
            zr=zr_new,
            beta=zr_new / jnp.where(s.zr == 0.0, 1.0, s.zr),
            diff=diff,
        )

    return body


def _shard_init(problem: Problem, spec: ShardSpec, rhs, colmask) -> _State:
    """w=0, r=b̃ (halo ring seeded by the rhs canvas), p=0 with β=0."""
    cv = spec.cv
    lo, hi = HALO, HALO + spec.m_blk
    h1h2 = jnp.float32(problem.h1 * problem.h2)
    zeros = jnp.zeros((cv.rows, cv.cols), rhs.dtype)
    center = rhs[lo:hi, :].astype(jnp.float32)
    zr0 = lax.psum(
        jnp.sum(center * center * colmask.astype(jnp.float32)), _AXES
    ) * h1h2
    return _State(
        k=jnp.zeros((), jnp.int32),
        done=jnp.asarray(False),
        w=zeros, r=rhs, p=zeros,
        zr=zr0,
        beta=jnp.float32(0.0),   # first iteration: p ← z + 0·p = z₀ = r₀
        diff=jnp.float32(jnp.inf),
    )


def _run_shard(problem: Problem, spec: ShardSpec, px: int, py: int,
               interpret: bool, cs, cw, g, rhs, sc2, sc_int, colmask,
               parallel: bool = False, serial: bool = False):
    lo, hi = HALO, HALO + spec.m_blk
    body = _make_shard_body(problem, spec, px, py, interpret,
                            cs, cw, g, sc2, colmask, rhs.dtype, parallel,
                            serial)

    def cond(s: _State):
        return (~s.done) & (s.k < problem.iteration_cap)

    s = lax.while_loop(cond, body, _shard_init(problem, spec, rhs, colmask))
    w_own = s.w[lo:hi, 1 : spec.n_blk + 1] * sc_int
    return w_own, s.k, s.diff, s.zr


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 11, 12))
def _fused_solve_sharded(problem: Problem, mesh: Mesh, spec: ShardSpec,
                         interpret: bool, cs, cw, g, rhs, sc2, sc_int,
                         colmask, parallel: bool = False,
                         serial: bool = False) -> PCGResult:
    px = mesh.shape[X_AXIS]
    py = mesh.shape[Y_AXIS]

    def shard_fn(cs_b, cw_b, g_b, rhs_b, sc2_b, sc_int_b, colmask_b):
        return _run_shard(
            problem, spec, px, py, interpret,
            cs_b[0], cw_b[0], g_b[0], rhs_b[0], sc2_b[0], sc_int_b[0],
            colmask_b, parallel, serial,
        )

    stacked = P((X_AXIS, Y_AXIS))
    w_int, k, diff, zr = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(stacked, stacked, stacked, stacked, stacked, stacked, P()),
        out_specs=(P(X_AXIS, Y_AXIS), P(), P(), P()),
        check_vma=False,
    )(cs, cw, g, rhs, sc2, sc_int, colmask)
    w = jnp.pad(w_int[: problem.M - 1, : problem.N - 1], 1)
    return PCGResult(w=w, iterations=k, diff=diff, residual_dot=zr)


def pallas_cg_solve_sharded(problem: Problem, mesh: Mesh,
                            bm: int | None = None,
                            interpret: bool | None = None,
                            dtype_name: str = "float32",
                            rhs_gate=None,
                            parallel: bool = False,
                            serial: bool | None = None) -> PCGResult:
    """Distributed solve on the fused Pallas path (fp32, scaled system).

    The stage4-equivalent configuration: per-shard fused kernels + mesh
    collectives. ``interpret`` defaults to True off-TPU so the kernels run
    (and are tested) on the virtual CPU mesh. ``rhs_gate`` as in
    ``pallas_cg_solve``; ``parallel`` marks each shard's strip grid
    parallel (megacore TensorCore split within a chip).

    Runs under the span ``pallas_cg_solve_sharded`` with the children
    ``.prepare`` (shard canvases, gate) and ``.launch``: see
    :func:`poisson_tpu.obs.span`.
    """
    with obs.span("pallas_cg_solve_sharded"):
        with obs.span("pallas_cg_solve_sharded.prepare"):
            if interpret is None:
                interpret = jax.devices()[0].platform != "tpu"
            px = mesh.shape[X_AXIS]
            py = mesh.shape[Y_AXIS]
            spec = shard_spec(problem, px, py, bm)
            cs, cw, g, rhs, sc2, sc_int, colmask = _shard_canvases(
                problem, px, py, spec, dtype_name
            )
            if rhs_gate is not None:
                rhs = rhs * jnp.asarray(rhs_gate, rhs.dtype)
            serial = _resolve_serial(serial, parallel)
        with obs.span("pallas_cg_solve_sharded.launch"):
            return _fused_solve_sharded(
                problem, mesh, spec, interpret,
                cs, cw, g, rhs, sc2, sc_int, colmask, parallel, serial)


# ---------------------------------------------------------------------------
# Checkpoint/resume on the distributed fused path. Same portable full-grid
# .npz format and (float32, scaled) fingerprint as every other checkpointed
# solver — a pod-scale fused solve can be resumed by the XLA paths, on a
# different mesh shape, or single-device (see ops.pallas_cg and
# parallel.checkpoint_sharded). Fused-state mapping as in ops.pallas_cg:
# save forms the updated direction d = r + β·p; resume sets p := d − r,
# β := 1. Halo rings are dropped at save and refreshed by one exchange at
# chunk start (idempotent for in-memory state: the exchanged values equal
# the locally-recomputed ones by the r-halo induction argument above).
# ---------------------------------------------------------------------------


def _gather_full(problem: Problem, spec, px: int, py: int,
                 stacked, col0: int = 1) -> np.ndarray:
    """Stacked per-shard canvases → owned interiors on the (M+1, N+1) grid.

    ``col0`` is the canvas column of a shard's first owned cell (1 on the
    fused layout's width-1 ring; 2 on the CA layout's width-2 ring —
    ``parallel.pallas_ca_sharded`` shares these helpers)."""
    M, N = problem.M, problem.N
    stacked = np.asarray(stacked)
    full = np.zeros((M + 1, N + 1), stacked.dtype)
    for ix in range(px):
        for iy in range(py):
            gi0, gj0 = 1 + ix * spec.m_blk, 1 + iy * spec.n_blk
            nr = min(spec.m_blk, M - gi0)
            nc = min(spec.n_blk, N - gj0)
            if nr <= 0 or nc <= 0:
                continue
            blk = stacked[ix * py + iy]
            full[gi0 : gi0 + nr, gj0 : gj0 + nc] = blk[
                HALO : HALO + nr, col0 : col0 + nc
            ]
    return full


def _scatter_canvases(problem: Problem, spec, px: int, py: int,
                      full, col0: int = 1) -> np.ndarray:
    """(M+1, N+1) grid → stacked per-shard canvases, owned interiors only
    (halo rings and padding zero; one exchange at chunk start refreshes).
    ``col0`` as in :func:`_gather_full`."""
    M, N = problem.M, problem.N
    cv = spec.cv
    full = np.asarray(full, np.float32)
    out = np.zeros((px * py, cv.rows, cv.cols), np.float32)
    for ix in range(px):
        for iy in range(py):
            gi0, gj0 = 1 + ix * spec.m_blk, 1 + iy * spec.n_blk
            nr = min(spec.m_blk, M - gi0)
            nc = min(spec.n_blk, N - gj0)
            if nr <= 0 or nc <= 0:
                continue
            out[ix * py + iy, HALO : HALO + nr, col0 : col0 + nc] = full[
                gi0 : gi0 + nr, gj0 : gj0 + nc
            ]
    return out


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _chunk_solve(problem: Problem, mesh: Mesh, spec: ShardSpec,
                 interpret: bool, chunk: int, parallel: bool, serial: bool,
                 cs, cw, g, sc2, colmask,
                 w_st, r_st, p_st, k, done, zr, beta, diff):
    px = mesh.shape[X_AXIS]
    py = mesh.shape[Y_AXIS]

    def shard_fn(cs_b, cw_b, g_b, sc2_b, colmask_b,
                 w_b, r_b, p_b, k, done, zr, beta, diff):
        body = _make_shard_body(problem, spec, px, py, interpret,
                                cs_b[0], cw_b[0], g_b[0], sc2_b[0],
                                colmask_b, w_b.dtype, parallel, serial)
        # Refresh halo rings (resume reconstructs them as zeros; for
        # in-memory state the exchange is value-idempotent).
        r = _exchange_r_halo(r_b[0], spec, px, py)
        p = _exchange_r_halo(p_b[0], spec, px, py)
        s0 = _State(k=k, done=done, w=w_b[0], r=r, p=p,
                    zr=zr, beta=beta, diff=diff)
        stop_at = jnp.minimum(k + chunk, problem.iteration_cap)

        def cond(s: _State):
            return (~s.done) & (s.k < stop_at)

        s = lax.while_loop(cond, body, s0)
        return (s.w[None], s.r[None], s.p[None],
                s.k, s.done, s.zr, s.beta, s.diff)

    stacked = P((X_AXIS, Y_AXIS))
    rep = P()
    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(stacked, stacked, stacked, stacked, rep,
                  stacked, stacked, stacked, rep, rep, rep, rep, rep),
        out_specs=(stacked, stacked, stacked, rep, rep, rep, rep, rep),
        check_vma=False,
    )(cs, cw, g, sc2, colmask, w_st, r_st, p_st, k, done, zr, beta, diff)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _init_stacked(problem: Problem, mesh: Mesh, spec: ShardSpec,
                  rhs, colmask):
    def shard_fn(rhs_b, colmask_b):
        s = _shard_init(problem, spec, rhs_b[0], colmask_b)
        return (s.w[None], s.r[None], s.p[None],
                s.k, s.done, s.zr, s.beta, s.diff)

    stacked = P((X_AXIS, Y_AXIS))
    rep = P()
    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(stacked, rep),
        out_specs=(stacked, stacked, stacked, rep, rep, rep, rep, rep),
        check_vma=False,
    )(rhs, colmask)


class _CkptState(NamedTuple):
    """Stacked-canvas solver state in the canonical field order shared by
    both sharded checkpointed drivers: ``w`` the (scaled) solution
    canvases, ``r`` the residual, ``p`` the direction material (fused:
    the pending-β direction; CA: the pending pair's p₁ — both resume as
    p := d − r, β := 1)."""

    w: jnp.ndarray
    r: jnp.ndarray
    p: jnp.ndarray
    k: jnp.ndarray
    done: jnp.ndarray
    zr: jnp.ndarray
    beta: jnp.ndarray
    diff: jnp.ndarray


def run_sharded_checkpointed(problem: Problem, mesh: Mesh,
                             checkpoint_path: str, chunk: int,
                             keep_checkpoint: bool, spec, col0: int,
                             canvases, make_runners,
                             keep_last: int = 2) -> PCGResult:
    """Shared scaffolding for the sharded checkpointed drivers (fused and
    CA — one copy of the multi-process wrapping, portable-state mapping,
    gather/scatter plumbing, and final unscale).

    ``canvases`` is the process-local ``(cs, cw, g, rhs, sc2, colmask)``
    tuple; ``make_runners(wrapped_canvases)`` returns ``(init, advance)``
    where ``init()`` produces the initial :class:`_CkptState` and
    ``advance(state)`` runs one ~``chunk``-iteration leg. ``col0`` is the
    canvas column of the first owned cell (driver-layout dependent).
    Multi-process meshes: state is gathered to every process before the
    primary-only write, with barrier-ordered file handoff."""
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    from poisson_tpu.parallel.checkpoint_sharded import (
        _global_array,
        _multiprocess,
        _replicator,
        _sync,
    )
    from poisson_tpu.parallel.multihost import is_primary
    from poisson_tpu.solvers.checkpoint import (
        _fingerprint,
        load_state,
        run_chunked,
    )
    from poisson_tpu.solvers.pcg import PCGState, host_fields64

    px = mesh.shape[X_AXIS]
    py = mesh.shape[Y_AXIS]
    cs, cw, g, rhs, sc2, colmask = canvases
    stacked_sp = P((X_AXIS, Y_AXIS))
    if _multiprocess():
        # Re-wrap the process-local canvases as global arrays (sc_int is
        # not used on this path — solution unscaling is host-side).
        wrap = lambda c, sp: _global_array(np.asarray(c), mesh, sp)
        cs, cw, g, rhs, sc2 = (
            wrap(c, stacked_sp) for c in (cs, cw, g, rhs, sc2)
        )
        colmask = wrap(colmask, P())
    fp = _fingerprint(problem, "float32", True)
    init, advance = make_runners((cs, cw, g, rhs, sc2, colmask))

    def stacked_state(full_state) -> _CkptState:
        d = np.asarray(full_state.p, np.float32)
        r = np.asarray(full_state.r, np.float32)
        as_global = lambda host: (
            _global_array(host, mesh, stacked_sp)
            if _multiprocess() else jnp.asarray(host)
        )
        scalar = lambda x, dt: (
            _global_array(np.asarray(x, dt), mesh, P())
            if _multiprocess() else jnp.asarray(np.asarray(x, dt))
        )
        scat = lambda full: _scatter_canvases(
            problem, spec, px, py, full, col0=col0
        )
        return _CkptState(
            w=as_global(scat(full_state.w)),
            r=as_global(scat(r)),
            p=as_global(scat(d - r)),
            k=scalar(full_state.k, np.int32),
            done=scalar(full_state.done, bool),
            zr=scalar(full_state.zr, np.float32),
            beta=scalar(1.0, np.float32),      # β := 1 with p := d − r
            diff=scalar(full_state.diff, np.float32),
        )

    saved = load_state(checkpoint_path, fp, keep_last=keep_last)
    state = init() if saved is None else stacked_state(saved)

    def fetch(x):
        return _replicator(mesh)(x) if _multiprocess() else x

    def gather(x):
        return _gather_full(problem, spec, px, py, fetch(x), col0=col0)

    def to_portable(s: _CkptState) -> PCGState:
        r_full = gather(s.r)
        d_full = r_full + float(s.beta) * gather(s.p)
        return PCGState(
            k=np.asarray(s.k), done=np.asarray(s.done),
            w=gather(s.w), r=r_full, z=r_full, p=d_full,
            zr=np.asarray(s.zr), diff=np.asarray(s.diff),
        )

    state = run_chunked(
        state,
        advance=advance,
        to_portable=to_portable,
        path=checkpoint_path, fingerprint=fp, cap=problem.iteration_cap,
        keep_checkpoint=keep_checkpoint, primary=is_primary, sync=_sync,
        keep_last=keep_last,
    )

    # Solution: gather owned w interiors and unscale with sc on the host
    # (value-identical to the one-shot drivers' per-shard w·sc_int: same
    # fp32 operands, elementwise).
    _, _, _, aux64 = host_fields64(problem, True)
    w_y = gather(state.w)
    w = w_y * np.asarray(aux64, w_y.dtype)
    return PCGResult(w=jnp.asarray(w), iterations=state.k, diff=state.diff,
                     residual_dot=state.zr)


def pallas_cg_solve_sharded_checkpointed(
        problem: Problem, mesh: Mesh, checkpoint_path: str,
        chunk: int = 200, bm: int | None = None,
        interpret: bool | None = None,
        keep_checkpoint: bool = False,
        parallel: bool = False,
        serial: bool | None = None,
        keep_last: int = 2) -> PCGResult:
    """Distributed fused-path solve with periodic state persistence and
    automatic resume (portable format — see module comment; hardened
    format with ``keep_last`` retained generations). fp32 only."""
    serial = _resolve_serial(serial, parallel)
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    px = mesh.shape[X_AXIS]
    py = mesh.shape[Y_AXIS]
    spec = shard_spec(problem, px, py, bm)
    cs, cw, g, rhs, sc2, _, colmask = _shard_canvases(
        problem, px, py, spec, "float32"
    )

    def make_runners(wrapped):
        cs, cw, g, rhs, sc2, colmask = wrapped
        init = lambda: _CkptState(
            *_init_stacked(problem, mesh, spec, rhs, colmask)
        )
        advance = lambda s: _CkptState(*_chunk_solve(
            problem, mesh, spec, interpret, chunk, parallel, serial,
            cs, cw, g, sc2, colmask,
            s.w, s.r, s.p, s.k, s.done, s.zr, s.beta, s.diff,
        ))
        return init, advance

    return run_sharded_checkpointed(
        problem, mesh, checkpoint_path, chunk, keep_checkpoint, spec, 1,
        (cs, cw, g, rhs, sc2, colmask), make_runners, keep_last=keep_last,
    )

