"""Distributed communication-avoiding (s=2) CG: the CA kernels over a mesh.

Completes the backend × distribution matrix: the fused 2-sweep kernels
have a sharded form (``parallel.pallas_sharded`` — stage4's combination,
``stage4-mpi+cuda/poisson_mpi_cuda_f.cu:688-983``, re-designed TPU-native)
and the CA pair iteration (``ops.pallas_ca``) is the framework's own
algorithmic traffic reducer; this module runs the CA sweeps per shard
inside ``shard_map`` with ``ppermute`` halos and one ``psum`` round per
sweep. Per PAIR of iterations the wire cost is: one 12-entry Gram
``psum`` + one Σr'² ``psum`` (vs the fused path's 3 scalar rounds per
iteration — a 3× reduction in reduction-latency rounds, the classic
s-step communication win) and two width-2 halo exchanges.

**Width-2 halos, corners included.** The basis sweep applies the stencil
twice: t2 at an owned cell reads t1 at ±1, which reads pn at ±2 and at
the (±1, ±1) diagonals — so unlike the 5-point fused path (width-1,
corners never read, ``parallel.halo`` module doc), the CA shard needs
its ``r``/``pprev`` rings fresh at depth 2 *and* at corner cells. The
exchange shifts rows first and then columns over the full canvas height,
so corner blocks transit two hops (row neighbour → column neighbour)
and arrive correct without diagonal ``ppermute`` edges. The fused path's
r-only induction (recompute p's ring locally) does not extend to s=2 —
reconstructing p₁'s ring would need t1 there, which needs pn on a ring
that grows by one per pair — so both arrays are exchanged explicitly.

Shard canvas layout (cf. ``parallel.pallas_sharded``): the shard owns
m̂ × n̂ interior cells, m̂ a multiple of the strip height (strips tile the
owned band; halo rows live in the HALO-deep guard bands). Columns shift
by one vs the fused layout: owned column lj sits at canvas column
2 + lj, leaving TWO halo columns on each side (0..1 and n̂+2..n̂+3).
Kernel reductions mask halo columns with the (1, C) column mask
(unweighted Gram entries; sc² is builder-restricted to the owned
interior for the weighted ones) and halo rows stay outside every
reduction because strips tile the owned band exactly. The basis sweep's
direction update runs on a band widened ±2 rows so pn is real on the
ring (``ops.pallas_ca._make_basis_kernel``).

Correctness of the zero-padded decomposition follows the same induction
as ``parallel.pcg_sharded``: padded rows/columns have zero scaled
coefficients and zero RHS, so every iterate stays identically zero there.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from poisson_tpu.config import Problem
from poisson_tpu.ops.pallas_ca import (
    _CA_BUFFERS,
    _CAState,
    assemble_pair_state,
    basis_sweep,
    pair_scalars,
    pair_update,
)
from poisson_tpu.ops.pallas_cg import (
    HALO,
    LANE,
    SUBLANE,
    Canvas,
    _resolve_serial,
    diagonal_residual_canvas,
    scaled_stencil_fields,
    strip_height,
)
from poisson_tpu.parallel.halo import exchange_halos
from poisson_tpu.parallel.mesh import X_AXIS, Y_AXIS
from poisson_tpu.solvers.pcg import PCGResult
from jax import shard_map

_AXES = (X_AXIS, Y_AXIS)
_RING = 2          # halo ring width (the s=2 stencil depth)
_COL0 = _RING      # first owned canvas column


class CAShardSpec(NamedTuple):
    """Static per-shard CA canvas geometry (hashable; jit static arg)."""

    cv: Canvas
    m_blk: int   # owned interior rows per shard (= cv.nb · cv.bm)
    n_blk: int   # owned interior cols per shard


def ca_shard_spec(problem: Problem, px: int, py: int,
                  bm: int | None = None) -> CAShardSpec:
    n_blk = -(-(problem.N - 1) // py)
    cols = ((n_blk + 2 * _RING + LANE - 1) // LANE) * LANE
    if bm is None:
        bm = strip_height(cols, -(-(problem.M - 1) // px),
                          buffers=_CA_BUFFERS)
    if bm <= 0 or bm % SUBLANE != 0:
        raise ValueError(
            f"bm must be a positive multiple of {SUBLANE}, got {bm}"
        )
    m_min = -(-(problem.M - 1) // px)
    nb = -(-m_min // bm)
    m_blk = nb * bm
    cv = Canvas(bm=bm, nb=nb, rows=nb * bm + 2 * HALO, cols=cols)
    return CAShardSpec(cv=cv, m_blk=m_blk, n_blk=n_blk)


@functools.lru_cache(maxsize=8)
def _ca_shard_canvases(problem: Problem, px: int, py: int,
                       spec: CAShardSpec, dtype_name: str):
    """Host fp64 setup → stacked per-shard canvases (mesh order, x-major).

    Canvas (row w, col c) of shard (ix, iy) holds global grid cell
    (ix·m̂ + w − HALO + 1, iy·n̂ + c − _RING + 1): owned rows at
    w ∈ [HALO, HALO+m̂), owned cols at c ∈ [_RING, _RING+n̂), and a
    2-deep ring of real neighbour/boundary values around them (the rhs
    ring seeds r's — and via p₀ = r₀, pprev's — halos at iteration 0).
    """
    cv = spec.cv
    m_blk, n_blk = spec.m_blk, spec.n_blk
    dtype = jnp.dtype(dtype_name)
    M, N = problem.M, problem.N

    gcs, gcw, sc2_64, rhs64, sc64 = scaled_stencil_fields(problem)

    # Zero-padded global scratch with a _RING-cell guard before the
    # origin so every shard's slice — including shard (0, 0)'s, whose
    # ring reaches global row/col −2 — stays in bounds.
    height = (px - 1) * m_blk + (cv.rows - (HALO - _RING)) + _RING + 1
    width = (py - 1) * n_blk + cv.cols + _RING + 1
    big = np.zeros((max(height, M + 1 + _RING), max(width, N + 1 + _RING)),
                   np.float64)

    def stacked(field, zero_pad_cols: bool, zero_halo_cols: bool = False,
                zero_halo_rows: bool = False):
        big[:] = 0.0
        big[_RING : _RING + M + 1, _RING : _RING + N + 1] = field
        out = np.zeros((px * py, cv.rows, cv.cols), np.float64)
        w0 = HALO - _RING   # first canvas row the slice fills
        for ix in range(px):
            for iy in range(py):
                # canvas (w0, 0) ↔ global (ix·m̂ + 1 − _RING, iy·n̂ + 1 − _RING)
                r0 = _RING + ix * m_blk + 1 - _RING
                c0 = _RING + iy * n_blk + 1 - _RING
                out[ix * py + iy, w0:, :] = big[
                    r0 : r0 + cv.rows - w0, c0 : c0 + cv.cols
                ]
        if zero_pad_cols:
            out[:, :, n_blk + 2 * _RING :] = 0.0
        if zero_halo_cols:
            out[:, :, :_COL0] = 0.0
            out[:, :, _COL0 + n_blk :] = 0.0
        if zero_halo_rows:
            out[:, : HALO, :] = 0.0
            out[:, HALO + m_blk :, :] = 0.0
        return out

    cs_st = stacked(gcs, zero_pad_cols=True)
    cw_st = stacked(gcw, zero_pad_cols=True)
    g_st = np.stack([
        diagonal_residual_canvas(cs_st[s], cw_st[s])
        for s in range(px * py)
    ])
    rhs_st = stacked(rhs64, zero_pad_cols=True)
    # sc2 is a pure reduction weight: restrict to the owned interior
    # (halo rows AND columns zeroed — the weighted Gram entries then
    # need no separate mask).
    sc2_st = stacked(sc2_64, zero_pad_cols=True, zero_halo_cols=True,
                     zero_halo_rows=True)

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
    colmask[0, _COL0 : _COL0 + n_blk] = 1.0
    as_dev = lambda x: jnp.asarray(x, dtype)
    return (as_dev(cs_st), as_dev(cw_st), as_dev(g_st), as_dev(rhs_st),
            as_dev(sc2_st), sc_int, as_dev(colmask))


def _exchange_ring2(u, spec: CAShardSpec, px: int, py: int):
    """Refresh the width-2 halo ring around the canvas's owned band
    (``halo.exchange_halos``: rows, then columns over the FULL canvas
    height, so corner blocks arrive correct via two hops, module doc).
    Mesh-edge shards receive ppermute's zero fill = Dirichlet data."""
    return exchange_halos(u, px, py, _RING,
                          owned=((HALO, HALO + spec.m_blk),
                                 (_COL0, _COL0 + spec.n_blk)))


def _make_ca_shard_body(problem: Problem, spec: CAShardSpec, px: int,
                        py: int, interpret: bool, cs, cw, g, sc2, colmask,
                        dtype, parallel: bool, serial: bool):
    """One CA pair as a pure state→state function on shard canvases."""
    cv = spec.cv
    h1h2 = jnp.float32(problem.h1 * problem.h2)
    band = (HALO - _RING, HALO + spec.m_blk + _RING)

    def body(s: _CAState) -> _CAState:
        beta = jnp.reshape(s.beta, (1, 1)).astype(dtype)
        pn, t1, t2, t3, gram = basis_sweep(
            cv, beta, s.pprev, s.r, cs, cw, g, sc2,
            interpret=interpret, parallel=parallel, serial=serial,
            band=band, colmask=colmask,
        )
        gsum = lax.psum(jnp.sum(gram, axis=0), _AXES) * h1h2
        d = pair_scalars(problem, s.rr, s.k, gsum, dtype)
        x, r, p1, rr_part = pair_update(
            cv, d.coefs, pn, t1, t2, t3, s.x, s.r,
            interpret=interpret, parallel=parallel, serial=serial,
            colmask=colmask,
        )
        rr2 = lax.psum(jnp.sum(rr_part), _AXES) * h1h2
        pprev = jnp.where(d.only1, pn, p1)
        # Both 2-rings refreshed per pair. Deeper guard rows of pn/p1
        # are UNDEFINED in compiled mode (non-aliased pallas outputs,
        # guard rows never written; interpret mode zero-fills, so CPU
        # tests cannot see this) — safe only because the basis kernel's
        # in_band where() discards every read outside the ±2 band. Do
        # not read pprev beyond the ring. r's deep guards stay zero
        # (aliased through kernel D from the zero-initialised canvas).
        r = _exchange_ring2(r, spec, px, py)
        pprev = _exchange_ring2(pprev, spec, px, py)
        return assemble_pair_state(problem, s, d, x, r, pprev, rr2)

    return body


def _ca_shard_init(problem: Problem, spec: CAShardSpec, rhs,
                   colmask) -> _CAState:
    """x=0, r=b̃ (2-ring seeded by the rhs canvas), β=0 — the first basis
    sweep then forms pn ← r + 0 = r₀, real on the ring."""
    cv = spec.cv
    lo, hi = HALO, HALO + spec.m_blk
    h1h2 = jnp.float32(problem.h1 * problem.h2)
    zeros = jnp.zeros((cv.rows, cv.cols), rhs.dtype)
    center = rhs[lo:hi, :].astype(jnp.float32)
    rr0 = lax.psum(
        jnp.sum(center * center * colmask.astype(jnp.float32)), _AXES
    ) * h1h2
    return _CAState(
        k=jnp.zeros((), jnp.int32),
        done=jnp.asarray(False),
        x=zeros, r=rhs, pprev=zeros,
        rr=rr0,
        beta=jnp.float32(0.0),
        diff=jnp.float32(jnp.inf),
    )


def _run_ca_shard(problem: Problem, spec: CAShardSpec, px: int, py: int,
                  interpret: bool, cs, cw, g, rhs, sc2, sc_int, colmask,
                  parallel: bool, serial: bool):
    lo, hi = HALO, HALO + spec.m_blk
    body = _make_ca_shard_body(problem, spec, px, py, interpret,
                               cs, cw, g, sc2, colmask, rhs.dtype,
                               parallel, serial)

    def cond(s: _CAState):
        return (~s.done) & (s.k < problem.iteration_cap)

    s = lax.while_loop(
        cond, body, _ca_shard_init(problem, spec, rhs, colmask)
    )
    x_own = s.x[lo:hi, _COL0 : _COL0 + spec.n_blk] * sc_int
    return x_own, s.k, s.diff, s.rr


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 11, 12))
def _ca_solve_sharded(problem: Problem, mesh: Mesh, spec: CAShardSpec,
                      interpret: bool, cs, cw, g, rhs, sc2, sc_int,
                      colmask, parallel: bool = False,
                      serial: bool = False) -> PCGResult:
    px = mesh.shape[X_AXIS]
    py = mesh.shape[Y_AXIS]

    def shard_fn(cs_b, cw_b, g_b, rhs_b, sc2_b, sc_int_b, colmask_b):
        return _run_ca_shard(
            problem, spec, px, py, interpret,
            cs_b[0], cw_b[0], g_b[0], rhs_b[0], sc2_b[0], sc_int_b[0],
            colmask_b, parallel, serial,
        )

    stacked = P((X_AXIS, Y_AXIS))
    w_int, k, diff, rr = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(stacked, stacked, stacked, stacked, stacked, stacked,
                  P()),
        out_specs=(P(X_AXIS, Y_AXIS), P(), P(), P()),
        check_vma=False,
    )(cs, cw, g, rhs, sc2, sc_int, colmask)
    w = jnp.pad(w_int[: problem.M - 1, : problem.N - 1], 1)
    return PCGResult(w=w, iterations=k, diff=diff, residual_dot=rr)


def ca_cg_solve_sharded(problem: Problem, mesh: Mesh,
                        bm: int | None = None,
                        interpret: bool | None = None,
                        dtype_name: str = "float32",
                        rhs_gate=None,
                        parallel: bool = False,
                        serial: bool | None = None) -> PCGResult:
    """Distributed solve on the communication-avoiding CA(s=2) path.

    Same system, same convergence criterion, same golden iteration
    counts as every other backend; ≈10.1 canvas passes and ONE Gram +
    ONE norm reduction round per pair of iterations (module doc).
    ``interpret`` defaults to True off-TPU so the kernels run (and are
    tested) on the virtual CPU mesh; ``rhs_gate``/``parallel`` as in
    ``ops.pallas_ca.ca_cg_solve``.
    """
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    px = mesh.shape[X_AXIS]
    py = mesh.shape[Y_AXIS]
    spec = ca_shard_spec(problem, px, py, bm)
    cs, cw, g, rhs, sc2, sc_int, colmask = _ca_shard_canvases(
        problem, px, py, spec, dtype_name
    )
    if rhs_gate is not None:
        rhs = rhs * jnp.asarray(rhs_gate, rhs.dtype)
    return _ca_solve_sharded(problem, mesh, spec, interpret,
                             cs, cw, g, rhs, sc2, sc_int, colmask,
                             parallel, _resolve_serial(serial, parallel))


# ---------------------------------------------------------------------------
# Checkpoint/resume on the distributed CA path. Same portable full-grid
# .npz format and (float32, scaled) fingerprint as every other fp32 path:
# the CA pending pair (pprev, β) maps to the stored updated direction
# d = r + β·pprev (resume sets pprev := d − r, β := 1), exactly like the
# single-device CA driver — so a pod-scale CA solve resumes on the fused
# sharded, single-device, or XLA paths and vice versa. Halo rings are
# dropped at save and refreshed by one width-2 exchange at chunk start
# (value-idempotent for in-memory state: the exchanged values equal the
# owned values the neighbour would send again).
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _ca_chunk_sharded(problem: Problem, mesh: Mesh, spec: CAShardSpec,
                      interpret: bool, chunk: int, parallel: bool,
                      serial: bool, cs, cw, g, sc2, colmask,
                      x_st, r_st, pprev_st, k, done, rr, beta, diff):
    """Advance the sharded CA solve by ~``chunk`` iterations (a pair
    straddling the chunk boundary overshoots by one — chunking must not
    change the iterate sequence, so only the global cap truncates)."""
    px = mesh.shape[X_AXIS]
    py = mesh.shape[Y_AXIS]

    def shard_fn(cs_b, cw_b, g_b, sc2_b, colmask_b,
                 x_b, r_b, p_b, k, done, rr, beta, diff):
        body = _make_ca_shard_body(problem, spec, px, py, interpret,
                                   cs_b[0], cw_b[0], g_b[0], sc2_b[0],
                                   colmask_b, x_b.dtype, parallel, serial)
        r = _exchange_ring2(r_b[0], spec, px, py)
        pprev = _exchange_ring2(p_b[0], spec, px, py)
        s0 = _CAState(k=k, done=done, x=x_b[0], r=r, pprev=pprev,
                      rr=rr, beta=beta, diff=diff)
        stop_at = jnp.minimum(k + chunk, problem.iteration_cap)

        def cond(s: _CAState):
            return (~s.done) & (s.k < stop_at)

        s = lax.while_loop(cond, body, s0)
        return (s.x[None], s.r[None], s.pprev[None],
                s.k, s.done, s.rr, s.beta, s.diff)

    stacked = P((X_AXIS, Y_AXIS))
    rep = P()
    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(stacked, stacked, stacked, stacked, rep,
                  stacked, stacked, stacked, rep, rep, rep, rep, rep),
        out_specs=(stacked, stacked, stacked, rep, rep, rep, rep, rep),
        check_vma=False,
    )(cs, cw, g, sc2, colmask, x_st, r_st, pprev_st, k, done, rr, beta,
      diff)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _ca_init_stacked(problem: Problem, mesh: Mesh, spec: CAShardSpec,
                     rhs, colmask):
    def shard_fn(rhs_b, colmask_b):
        s = _ca_shard_init(problem, spec, rhs_b[0], colmask_b)
        return (s.x[None], s.r[None], s.pprev[None],
                s.k, s.done, s.rr, s.beta, s.diff)

    stacked = P((X_AXIS, Y_AXIS))
    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(stacked, P()),
        out_specs=(stacked, stacked, stacked, P(), P(), P(), P(), P()),
        check_vma=False,
    )(rhs, colmask)


def ca_cg_solve_sharded_checkpointed(
        problem: Problem, mesh: Mesh, checkpoint_path: str,
        chunk: int = 200, bm: int | None = None,
        interpret: bool | None = None,
        keep_checkpoint: bool = False,
        parallel: bool = False,
        serial: bool | None = None,
        keep_last: int = 2) -> PCGResult:
    """Distributed CA solve with periodic state persistence and automatic
    resume (portable cross-backend, cross-mesh, cross-ALGORITHM format —
    module comment above). fp32 only. All scaffolding is the shared
    sharded driver (``parallel.pallas_sharded.run_sharded_checkpointed``)
    with this layout's column offset; only the init/advance legs are
    CA-specific."""
    from poisson_tpu.parallel.pallas_sharded import (
        _CkptState,
        run_sharded_checkpointed,
    )

    serial = _resolve_serial(serial, parallel)
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    px = mesh.shape[X_AXIS]
    py = mesh.shape[Y_AXIS]
    spec = ca_shard_spec(problem, px, py, bm)
    cs, cw, g, rhs, sc2, _, colmask = _ca_shard_canvases(
        problem, px, py, spec, "float32"
    )

    def make_runners(wrapped):
        cs, cw, g, rhs, sc2, colmask = wrapped
        init = lambda: _CkptState(
            *_ca_init_stacked(problem, mesh, spec, rhs, colmask)
        )
        advance = lambda s: _CkptState(*_ca_chunk_sharded(
            problem, mesh, spec, interpret, chunk, parallel, serial,
            cs, cw, g, sc2, colmask,
            s.w, s.r, s.p, s.k, s.done, s.zr, s.beta, s.diff,
        ))
        return init, advance

    return run_sharded_checkpointed(
        problem, mesh, checkpoint_path, chunk, keep_checkpoint, spec,
        _COL0, (cs, cw, g, rhs, sc2, colmask), make_runners,
        keep_last=keep_last,
    )
