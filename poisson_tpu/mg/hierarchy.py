"""Geometric multigrid level hierarchy over the fictitious-domain canvases.

The whole cost of a PCG solve is iterations × bytes/iteration, and the
Jacobi preconditioner's iteration count scales with resolution (989 at
800×1200, 1858 at 1600×2400 — BENCH_TPU_GOOD*.json): doubling the grid
doubles the iterations *and* quadruples the bytes. A geometric V-cycle
preconditioner (Briggs/Henson/McCormick, PAPERS.md) makes the count
near-flat in resolution, because every error frequency is smoothed on
the level where it is local.

This module builds the level data the V-cycle (``mg.cycle``) consumes:

- **Level plan** (:func:`plan_levels`): vertex-centred factor-2
  coarsening, (M, N) → (M/2, N/2), as long as both dimensions stay even
  and the coarser grid stays above ``MGConfig.min_size``. Power-of-two
  bench grids (400×600 … 3200×4800) all bottom out at the SAME 50×75
  coarsest level, which is what makes their iteration counts
  comparable.
- **Coefficient coarsening** (:func:`coarsen_a`/:func:`coarsen_b`):
  the face coefficients a/b are *flux* quantities, so a coarse face
  averages the fine faces it geometrically covers — the two in-line
  faces in series (arithmetic mean keeps the penalty region stiff: the
  fictitious-domain blend must stay ~1/ε outside D or the coarse
  correction would let the solution leak through the boundary) and the
  (¼, ½, ¼)-weighted transverse neighbours the doubled face length
  spans. Constant fields coarsen exactly to themselves. The SAME rule
  serves every :mod:`poisson_tpu.geometry` family — coarsening is
  canvas-only, it never needs the spec's closed form.
- **Coarsest-level solve**: below ``coarse_dense_limit`` interior
  unknowns the coarsest operator is materialised as a dense matrix and
  inverted ONCE on the host in fp64 (symmetrised, so the V-cycle stays
  an exact SPD preconditioner); the inverse is applied in-graph as one
  matmul — MXU-friendly on TPU, and exact coarse solves are what make
  the V-cycle contraction genuinely resolution-independent. Above the
  limit the coarsest level falls back to extra weighted-Jacobi sweeps
  (``coarse_sweeps``) — audibly, via the ``mg.coarse_dense`` gauge.

Everything is derived on the host in fp64 from the same ``a``/``b``
canvases the solve itself uses (``host_fields64`` for the reference
ellipse, ``geometry.canvas.build_geometry_fields`` for DSL specs) and
cast once — the ``host_fields64`` precision idiom. Device-side level
data is cached per (problem, dtype, scaled, geometry fingerprint,
config) with ``mg.hierarchy_cache.{hits,misses}`` counters, mirroring
the geometry canvas cache.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import numpy as np

from poisson_tpu.config import Problem


@dataclasses.dataclass(frozen=True)
class MGConfig:
    """The V-cycle knobs (hashable: rides jit static args).

    pre_smooth/post_smooth: weighted-Jacobi sweeps per level, down- and
        up-leg. Equal counts keep the cycle symmetric — weighted Jacobi
        is A-self-adjoint, so with the bilinear/full-weighting transfer
        pair (exact transposes up to the 2D factor 4) the V-cycle is an
        SPD preconditioner, which plain (non-flexible) CG requires.
    omega: Jacobi damping. 0.8 ≈ 4/5, the classic 2D 5-point choice.
    coarse_sweeps: smoother sweeps standing in for the coarsest solve
        when the dense inverse is over its size limit.
    coarse_dense_limit: max interior unknowns for the dense coarsest
        inverse (n² floats of host memory, one n³ fp64 factorisation).
    min_size: stop coarsening when min(M, N)/2 would fall below this.
    max_levels: hierarchy depth cap (the bench grids use 4–7).
    """

    pre_smooth: int = 2
    post_smooth: int = 2
    omega: float = 0.8
    coarse_sweeps: int = 32
    coarse_dense_limit: int = 4096
    min_size: int = 10
    max_levels: int = 16


DEFAULT_MG = MGConfig()

PRECONDITIONERS = ("jacobi", "mg")


def resolve_preconditioner(preconditioner) -> str:
    """Validate a preconditioner name; None means the default."""
    name = "jacobi" if preconditioner is None else str(preconditioner)
    if name not in PRECONDITIONERS:
        raise ValueError(
            f"unknown preconditioner {preconditioner!r}: expected one of "
            f"{PRECONDITIONERS}"
        )
    return name


def plan_levels(M: int, N: int,
                config: MGConfig = DEFAULT_MG) -> tuple:
    """The (M_l, N_l) ladder, finest first. Level l+1 exists iff both
    dimensions of level l are even and the halved grid stays at or above
    ``config.min_size`` (and the depth cap allows it)."""
    levels = [(int(M), int(N))]
    while len(levels) < config.max_levels:
        m, n = levels[-1]
        if m % 2 or n % 2 or min(m, n) // 2 < config.min_size:
            break
        levels.append((m // 2, n // 2))
    return tuple(levels)


def validate_mg_problem(problem: Problem,
                        config: MGConfig = DEFAULT_MG) -> tuple:
    """The level plan for ``problem``, or a loud ValueError when the
    grid cannot coarsen at all (odd dimensions, or too small) — an
    uncoarsenable 'multigrid' would silently be an expensive smoother."""
    levels = plan_levels(problem.M, problem.N, config)
    if len(levels) < 2:
        raise ValueError(
            f"preconditioner='mg' needs a grid that coarsens at least "
            f"once: {problem.M}x{problem.N} does not (both M and N must "
            f"be even, with min(M, N) >= {2 * config.min_size}). Use "
            f"preconditioner='jacobi' for this grid."
        )
    return levels


# -- coefficient coarsening ---------------------------------------------


def coarsen_a(a: np.ndarray) -> np.ndarray:
    """Coarsen the x-face coefficient field (…fine (M+1, N+1) →
    coarse (M/2+1, N/2+1)).

    The coarse face between coarse nodes (I−1, J) and (I, J) covers the
    two fine faces (2I−1, ·) and (2I, ·) in series along x (averaged
    arithmetically — the blend must stay stiff across the fictitious
    region) and spans transverse fine positions 2J−1, 2J, 2J+1 with
    weights ¼, ½, ¼ (the doubled face length covers the neighbouring
    fine lines by half each). Row 0 / columns 0 and N_c are never read
    by the operators and are filled by injection for shape regularity.
    """
    pair = 0.5 * (a[1::2, :] + a[2::2, :])        # series avg, I = 1..Mc
    core = (0.25 * pair[:, 1:-2:2] + 0.5 * pair[:, 2:-1:2]
            + 0.25 * pair[:, 3::2])               # J = 1..Nc-1
    ac = np.ascontiguousarray(a[::2, ::2])        # injection filler
    ac[1:, 1:-1] = core
    return ac


def coarsen_b(b: np.ndarray) -> np.ndarray:
    """Coarsen the y-face coefficient field — :func:`coarsen_a` with
    the axis roles transposed."""
    pair = 0.5 * (b[:, 1::2] + b[:, 2::2])        # series avg, J = 1..Nc
    core = (0.25 * pair[1:-2:2, :] + 0.5 * pair[2:-1:2, :]
            + 0.25 * pair[3::2, :])               # I = 1..Mc-1
    bc = np.ascontiguousarray(b[::2, ::2])
    bc[1:-1, 1:] = core
    return bc


def _dense_operator(a: np.ndarray, b: np.ndarray, h1: float,
                    h2: float) -> np.ndarray:
    """The 5-point operator on the interior as a dense (n, n) fp64
    matrix, row-major over (i, j) with j fastest — the coarsest-level
    materialisation the dense inverse factors."""
    from poisson_tpu.ops.stencil import diag_D

    M, N = a.shape[0] - 1, a.shape[1] - 1
    mi, nj = M - 1, N - 1
    n = mi * nj
    d = diag_D(a, b, h1, h2)
    A = np.zeros((n, n))
    A[np.arange(n), np.arange(n)] = d.ravel()
    # x-neighbours: (i, j) <-> (i+1, j), coefficient -a[i+1, j]/h1².
    off_x = (-a[2:-1, 1:-1] / (h1 * h1)).ravel()
    rows = np.arange(n - nj)
    A[rows, rows + nj] = off_x
    A[rows + nj, rows] = off_x
    # y-neighbours: (i, j) <-> (i, j+1), coefficient -b[i, j+1]/h2²;
    # the flat offset 1 wraps at row ends, so those links are masked.
    off_y = (-b[1:-1, 2:-1] / (h2 * h2)).ravel(order="C")
    rows_y = np.asarray([i * nj + j for i in range(mi)
                         for j in range(nj - 1)])
    A[rows_y, rows_y + 1] = off_y
    A[rows_y + 1, rows_y] = off_y
    return A


class MGLevels(NamedTuple):
    """Device-side level data, a pytree of jit operands.

    levels: one (a, b, dinv) triple per level, finest first — the
        coefficient canvases and the zero-ring-padded inverse Jacobi
        diagonal (the smoother reads it; the ring keeps smoothed
        iterates zero on the Dirichlet boundary for free).
    coarse_inv: the dense coarsest-operator inverse (n, n), or None
        when the coarsest level is over the dense limit (it then runs
        ``coarse_sweeps`` of the smoother instead).
    scinv: √d on the full grid (zero ring) — the w-space wrap for the
        symmetrically-scaled outer system, or None for unscaled solves.
    strips: the (a, b, dinv) of the leading levels that the solo
        program smooths on the Pallas strip kernels, transposed as the
        kernels read them (``ops.pallas_mg``); empty where no level
        takes them (:func:`kernel_levels`).
    """

    levels: tuple
    coarse_inv: object = None
    scinv: object = None
    strips: tuple = ()


def build_hierarchy64(problem: Problem, a64: np.ndarray, b64: np.ndarray,
                      config: MGConfig = DEFAULT_MG) -> dict:
    """All host-fp64 level data for ``problem``'s canvases: per-level
    (a, b, dinv_padded), the dense coarsest inverse when within the
    size limit, and √d for the scaled wrap. Derivation precision policy
    matches ``host_fields64`` — everything fp64, cast once by the
    caller."""
    from poisson_tpu.ops.stencil import diag_D

    dims = validate_mg_problem(problem, config)
    levels, coarse_inv = _tail64(problem, np.asarray(a64, np.float64),
                                 np.asarray(b64, np.float64), dims, config)
    d0 = diag_D(np.asarray(a64, np.float64), np.asarray(b64, np.float64),
                problem.h1, problem.h2)
    return {
        "dims": dims,
        "levels": levels,
        "coarse_inv": coarse_inv,
        "scinv": np.pad(np.sqrt(d0), 1),
    }


def _levels64(problem: Problem, a: np.ndarray, b: np.ndarray,
              dims) -> list:
    """fp64 (a, b, dinv_padded) of each level of ``dims``, the first from
    ``a``/``b`` and each next one coarsened from the one before. Every
    value is elementwise in the canvases it comes from, so a block of a
    grid (odd-sized, starting on an even row and column of the level
    below) gives the same numbers as the whole grid, save its outer
    row and columns (``coarsen_a``'s injection filler)."""
    from poisson_tpu.ops.stencil import diag_D

    levels = []
    for k, (m, n) in enumerate(dims):
        if k:
            a, b = coarsen_a(a), coarsen_b(b)
        h1 = (problem.x_max - problem.x_min) / m
        h2 = (problem.y_max - problem.y_min) / n
        levels.append((a, b, np.pad(1.0 / diag_D(a, b, h1, h2), 1)))
    return levels


def _tail64(problem: Problem, a: np.ndarray, b: np.ndarray, dims,
            config: MGConfig):
    """The levels of ``dims`` from the whole-grid ``a``/``b`` of its first,
    and the dense coarsest inverse when within the size limit."""
    levels = _levels64(problem, a, b, dims)
    mc, nc = dims[-1]
    coarse_inv = None
    if (mc - 1) * (nc - 1) <= config.coarse_dense_limit:
        ac, bc, _ = levels[-1]
        h1c = (problem.x_max - problem.x_min) / mc
        h2c = (problem.y_max - problem.y_min) / nc
        Ac = _dense_operator(ac, bc, h1c, h2c)
        inv = np.linalg.inv(Ac)
        coarse_inv = 0.5 * (inv + inv.T)   # exactly symmetric: SPD cycle
    return levels, coarse_inv


def _cast_levels(host: dict, dtype_name: str, scaled: bool) -> MGLevels:
    import jax.numpy as jnp

    dt = jnp.dtype(dtype_name)
    levels = tuple(
        (jnp.asarray(a, dt), jnp.asarray(b, dt), jnp.asarray(dinv, dt))
        for a, b, dinv in host["levels"]
    )
    coarse_inv = (None if host["coarse_inv"] is None
                  else jnp.asarray(host["coarse_inv"], dt))
    scinv = jnp.asarray(host["scinv"], dt) if scaled else None
    return MGLevels(levels=levels, coarse_inv=coarse_inv, scinv=scinv)


def kernel_levels(platform: str, dtype_name: str, dims: tuple,
                  config: MGConfig = DEFAULT_MG) -> int:
    """How many leading levels of ``dims`` the solo MG program smooths
    on the Pallas strip kernels: on a TPU, in fp32, with sweep counts
    the strip halo holds, each level above the coarsest whose grid is
    large enough to be bandwidth-bound (``ops.pallas_mg.bandwidth_bound``).
    Zero everywhere else, so the CPU runs the XLA cycle."""
    from poisson_tpu.ops.pallas_cg import HALO
    from poisson_tpu.ops.pallas_mg import bandwidth_bound

    if (platform != "tpu" or dtype_name != "float32"
            or max(config.pre_smooth, config.post_smooth) > HALO):
        return 0
    count = 0
    while count < len(dims) - 1 and bandwidth_bound(*dims[count]):
        count += 1
    return count


def mesh_kernel_levels(platform: str, dtype_name: str, plan: MeshPlan,
                       config: MGConfig = DEFAULT_MG) -> int:
    """How many leading levels of ``plan``'s sharded levels the MG solve
    over a mesh smooths on the strip kernels: :func:`kernel_levels`'s
    rule read on what a chip holds, the blocks ``(m̂ >> l, n̂ >> l)`` of
    the levels above ``plan.replicated_from``. Zero where a pass's
    sweeps outrun the blocks' ``STRIP_RING``-line ring (each sweep and
    the residual read one more line past the block)."""
    if max(config.pre_smooth, config.post_smooth) > STRIP_RING:
        return 0
    blocks = tuple((plan.m_blk >> lvl, plan.n_blk >> lvl)
                   for lvl in range(plan.replicated_from + 1))
    return kernel_levels(platform, dtype_name, blocks, config)


def with_strips(hier: MGLevels, count: int) -> MGLevels:
    """``hier`` with its first ``count`` levels' (a, b, dinv) also laid
    out as the strip kernels read them: transposed, each its own
    row-major array (``ops.pallas_mg``)."""
    import jax.numpy as jnp

    strips = tuple(tuple(jnp.transpose(f) for f in fields)
                   for fields in hier.levels[:count])
    return hier._replace(strips=strips)


def _platform() -> str:
    import jax

    return jax.devices()[0].platform


# Device hierarchies this process has built, keyed like the geometry
# canvas cache: (normalized problem, dtype, scaled, fingerprint, config).
# The blend canvases are f_val-independent, so the key normalizes it away
# — every RHS magnitude of a domain shares one hierarchy.
_HIERARCHIES: dict = {}


def reset_hierarchy_cache() -> None:
    """Forget cached device hierarchies (tests; pair with
    ``obs.metrics.reset()`` or the hit/miss arithmetic goes stale)."""
    _HIERARCHIES.clear()


def device_hierarchy(problem: Problem, dtype_name: str, scaled: bool,
                     geometry=None,
                     config: MGConfig = DEFAULT_MG) -> MGLevels:
    """The fingerprint-keyed device-resident hierarchy for ``problem``
    (+ optional :mod:`poisson_tpu.geometry` spec): host-fp64 build and
    dense coarsest factorisation paid once per domain, then cached —
    ``mg.hierarchy_cache.{hits,misses}``. A miss builds under the span
    ``mg.hierarchy.build``, and also lays out the levels
    :func:`kernel_levels` gives this process's platform
    (:func:`with_strips`)."""
    from poisson_tpu import obs

    fp = None
    if geometry is not None:
        from poisson_tpu.geometry.dsl import parse_geometry

        geometry = parse_geometry(geometry)
        fp = geometry.fingerprint
    key = (problem.with_(f_val=1.0), dtype_name, bool(scaled), fp, config)
    cached = _HIERARCHIES.get(key)
    if cached is not None:
        obs.inc("mg.hierarchy_cache.hits")
        return cached
    obs.inc("mg.hierarchy_cache.misses")
    with obs.span("mg.hierarchy.build"):
        if geometry is None:
            from poisson_tpu.solvers.pcg import host_fields64

            # The solve's own canvases (``host_fields64`` keeps them): a
            # and b are the same for every f and either scaling.
            a64, b64, _, _ = host_fields64(problem, bool(scaled))
        else:
            from poisson_tpu.geometry.canvas import build_geometry_fields

            a64, b64, _ = build_geometry_fields(problem, geometry)
        host = build_hierarchy64(problem, a64, b64, config)
        hier = with_strips(_cast_levels(host, dtype_name, scaled),
                           kernel_levels(_platform(), dtype_name,
                                         host["dims"], config))
    _HIERARCHIES[key] = hier
    obs.gauge("mg.levels", len(hier.levels))
    obs.gauge("mg.coarse_dense", 1 if hier.coarse_inv is not None else 0)
    obs.event("mg.hierarchy", grid=f"{problem.M}x{problem.N}",
              levels=len(hier.levels),
              coarsest="x".join(map(str, host["dims"][-1])),
              dense_coarse=hier.coarse_inv is not None,
              fingerprint=fp)
    return hier


# -- the hierarchy split over a device mesh --------------------------------


class MeshPlan(NamedTuple):
    """How an MG solve splits over a ``px × py`` mesh: every level
    ``l < replicated_from`` as per-shard blocks of ``m_blk >> l`` ×
    ``n_blk >> l`` owned nodes with a halo ring (the layout of
    ``parallel.pcg_sharded``: shard (px, py) owns global rows
    ``px·m̂_l + 1 … px·m̂_l + m̂_l`` and columns likewise), the levels from
    ``replicated_from`` down whole on every device."""

    dims: tuple
    px: int
    py: int
    m_blk: int
    n_blk: int
    replicated_from: int


# The ring of a sharded level's strip-kernel blocks: the kernels' two
# stencil reads a pass (two sweeps, or a sweep and the residual) reach two
# lines past the owned ones (``parallel.mg_sharded``).
STRIP_RING = 2


def shard_fields64(problem: Problem, plan: MeshPlan, px: int, py: int,
                   scaled: bool, strips: int = 0) -> dict:
    """Host-fp64 fields of shard (px, py), built from its own rows and
    columns only (``models.fictitious_domain`` closed forms), each a
    (m̂_l + 2, n̂_l + 2) block with its halo ring:

    - ``levels``: (a, b, dinv) of every sharded level, dinv zero off the
      shard's owned interior (so smoothing never writes off it);
    - ``rhs``, ``aux``, ``scinv``: level 0's right-hand side (scaled by
      D^{-1/2} when ``scaled``; zero off the owned interior), D^{-1/2}
      (scaled) or D, and √d (zero off the owned interior) —
      ``solvers.pcg.host_fields64``'s derivation;
    - ``tail``: (rows, cols, a, b), the shard's owned part of level
      ``replicated_from``'s a and b and where it sits in the whole grid;
    - ``strips``: of the first ``strips`` levels, (a, b, dinv) as the
      strip kernels read them: (m̂_l + 4, n̂_l + 4) blocks with a
      ``STRIP_RING``-line ring, transposed, dinv zero off the grid's
      interior (not the shard's), so each ring node holds what its owner
      computes there.

    The block grows by a margin of 2^(R+1) lines on each side
    (R = ``replicated_from``), clipped at the grid's edges, and is
    coarsened as a whole grid would be: every value is elementwise in
    the canvases it comes from and starts on an even line of the level
    below, so each block equals the slice of :func:`build_hierarchy64`'s
    levels (zero past the grid's far edges) save the margin that
    ``coarsen_a``'s injection filler spoils and that the slice leaves out
    (``tests/test_mg_sharded.py``). The filler spoils a level's outer
    line only, and a ring node's dinv reads one line further out: at
    least four lines of margin at every sharded level keep the rings
    clear of it."""
    from poisson_tpu.models.fictitious_domain import (
        coefficient_fields,
        rhs_field,
    )
    from poisson_tpu.ops.stencil import diag_D

    R = plan.replicated_from
    margin = 2 << R
    spans = []
    for p, blk, size in ((px, plan.m_blk, problem.M),
                         (py, plan.n_blk, problem.N)):
        spans.append((max(p * blk - margin, 0),
                      min(p * blk + blk + margin, size)))
    (lo_i, hi_i), (lo_j, hi_j) = spans
    i_idx, j_idx = np.arange(lo_i, hi_i + 1), np.arange(lo_j, hi_j + 1)
    a, b = coefficient_fields(problem, i_idx, j_idx, np.float64, np)
    levels = _levels64(problem, a, b, plan.dims[:R + 1])

    def block(u, lvl):
        ml, nl = plan.m_blk >> lvl, plan.n_blk >> lvl
        oi, oj = px * ml - (lo_i >> lvl), py * nl - (lo_j >> lvl)
        part = u[oi:oi + ml + 2, oj:oj + nl + 2]
        out = np.zeros((ml + 2, nl + 2))
        out[:part.shape[0], :part.shape[1]] = part
        return out

    def owned(lvl):
        ml, nl = plan.m_blk >> lvl, plan.n_blk >> lvl
        Ml, Nl = plan.dims[lvl]
        i, j = np.arange(ml + 2), np.arange(nl + 2)
        rows = (i >= 1) & (i <= ml) & (px * ml + i <= Ml - 1)
        cols = (j >= 1) & (j <= nl) & (py * nl + j <= Nl - 1)
        return rows[:, None] & cols[None, :]

    def strip(u, lvl, interior_only=False):
        """The transposed (m̂_l + 4, n̂_l + 4) block of level ``lvl``'s
        ``u``, which starts one line before the block's own ring."""
        ml, nl = plan.m_blk >> lvl, plan.n_blk >> lvl
        ring = STRIP_RING
        gi = px * ml + 1 - ring + np.arange(ml + 2 * ring)
        gj = py * nl + 1 - ring + np.arange(nl + 2 * ring)
        oi, oj = gi[0] - (lo_i >> lvl) + ring, gj[0] - (lo_j >> lvl) + ring
        out = np.pad(u, ring)[oi:oi + ml + 2 * ring, oj:oj + nl + 2 * ring]
        if interior_only:
            Ml, Nl = plan.dims[lvl]
            out = out * (((gi >= 1) & (gi <= Ml - 1))[:, None]
                         & ((gj >= 1) & (gj <= Nl - 1))[None, :])
        return np.ascontiguousarray(out.T)

    d0 = diag_D(a, b, problem.h1, problem.h2)
    rhs = rhs_field(problem, i_idx, j_idx, np.float64, np)
    if scaled:
        inv_sqrt_d = 1.0 / np.sqrt(d0)
        rhs = np.pad(rhs[1:-1, 1:-1] * inv_sqrt_d, 1)
        aux = np.pad(inv_sqrt_d, 1)
    else:
        aux = np.pad(d0, 1)
    mask0 = owned(0)
    # Level R's a and b on the rows and columns this shard owns (and the
    # grid's edge lines on the edge shards): together they are its whole
    # grid.
    mR, nR = plan.m_blk >> R, plan.n_blk >> R
    r0, c0 = (0 if px == 0 else px * mR + 1), (0 if py == 0 else py * nR + 1)
    r1, c1 = px * mR + mR + 1, py * nR + nR + 1
    aR, bR, _ = levels[R]
    cut = (slice(r0 - (lo_i >> R), r1 - (lo_i >> R)),
           slice(c0 - (lo_j >> R), c1 - (lo_j >> R)))
    return {
        "levels": [(block(la, lvl), block(lb, lvl),
                    block(ldinv, lvl) * owned(lvl))
                   for lvl, (la, lb, ldinv) in enumerate(levels[:R])],
        "rhs": block(rhs, 0) * mask0,
        "aux": block(aux, 0),
        "scinv": block(np.pad(np.sqrt(d0), 1), 0) * mask0,
        "tail": ((r0, r1), (c0, c1), aR[cut], bR[cut]),
        "strips": [(strip(la, lvl), strip(lb, lvl),
                    strip(ldinv, lvl, interior_only=True))
                   for lvl, (la, lb, ldinv) in enumerate(levels[:strips])],
    }


def mesh_hierarchy64(problem: Problem, plan: MeshPlan, scaled: bool,
                     config: MGConfig = DEFAULT_MG, cast=None,
                     strips: int = 0) -> dict:
    """Every shard's :func:`shard_fields64` (with ``strips`` strip-kernel
    levels; in threads, one a shard:
    numpy's array passes release the interpreter lock), each passed
    through ``cast`` as it is made, and the replicated tail: levels
    ``replicated_from …`` and the dense coarsest inverse, coarsened from
    level ``replicated_from``'s whole grid, which the shards' owned parts
    tile. Returns ``{"shards": {(px, py): fields}, "tail": levels,
    "coarse_inv": inv}``; the whole fine grid is never held."""
    from concurrent.futures import ThreadPoolExecutor

    R = plan.replicated_from
    keys = [(px, py) for px in range(plan.px) for py in range(plan.py)]

    def build(key):
        fields = shard_fields64(problem, plan, *key, scaled, strips)
        tail = fields.pop("tail")
        return (fields if cast is None else cast(fields)), tail

    with ThreadPoolExecutor(max_workers=len(keys)) as pool:
        built = dict(zip(keys, pool.map(build, keys)))
    MR, NR = plan.dims[R]
    aR, bR = np.zeros((MR + 1, NR + 1)), np.zeros((MR + 1, NR + 1))
    for _, ((r0, r1), (c0, c1), sa, sb) in built.values():
        aR[r0:r1, c0:c1] = sa
        bR[r0:r1, c0:c1] = sb
    tail, coarse_inv = _tail64(problem, aR, bR, plan.dims[R:], config)
    return {"shards": {k: v[0] for k, v in built.items()}, "tail": tail,
            "coarse_inv": coarse_inv}


def mesh_hierarchy(problem: Problem, dtype_name: str, scaled: bool, mesh,
                   plan: MeshPlan, config: MGConfig = DEFAULT_MG):
    """(hierarchy, rhs, aux) of an MG solve over ``mesh``, cached per
    (problem, dtype, scaled, config, mesh, plan) like
    :func:`device_hierarchy` (``mg.hierarchy_cache.{hits,misses}``).

    Each shard's fp64 fields (:func:`mesh_hierarchy64`) are cast once and
    placed on their own device as that device's block of a
    ``NamedSharding(mesh, P('x', 'y'))`` array of shape
    (px·(m̂_l + 2), py·(n̂_l + 2)); the tail levels and the coarsest
    inverse are placed whole on every device (``P()``). The hierarchy's
    ``levels`` mix the two: blocks down to ``plan.replicated_from``,
    whole grids below; level 0's a and b are the operator's too. Its
    ``strips`` hold the levels :func:`mesh_kernel_levels` gives this
    process's platform, each shard's transposed block placed as its
    device's block of a ``P('y', 'x')`` array; those levels' blocks are
    None where the cycle never reads them (all but level 0's a and b)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from poisson_tpu import obs
    from poisson_tpu.parallel.mesh import X_AXIS, Y_AXIS

    key = ("mesh", problem, dtype_name, bool(scaled), config, mesh, plan)
    cached = _HIERARCHIES.get(key)
    if cached is not None:
        obs.inc("mg.hierarchy_cache.hits")
        return cached
    obs.inc("mg.hierarchy_cache.misses")
    dt = np.dtype(jax.numpy.dtype(dtype_name))
    whole = NamedSharding(mesh, PartitionSpec())
    count = mesh_kernel_levels(_platform(), dtype_name, plan, config)

    def unread(lvl: int, k: int) -> bool:
        """Whether the cycle never reads field ``k`` of sharded level
        ``lvl``: a strip-kernel level reads its strips, and of its
        (a, b, dinv) only level 0's a and b serve (CG's operator)."""
        return lvl < count and (lvl > 0 or k == 2)

    def cast(fields):
        out = {k: np.asarray(v, dt) for k, v in fields.items()
               if k not in ("levels", "strips")}
        out["levels"] = [tuple(None if unread(lvl, k) else np.asarray(f, dt)
                               for k, f in enumerate(lv))
                         for lvl, lv in enumerate(fields["levels"])]
        out["strips"] = [tuple(np.asarray(f, dt) for f in lv)
                         for lv in fields["strips"]]
        return out

    def place(pick, axes=(X_AXIS, Y_AXIS)):
        """Each shard's ``pick`` as its device's block of an array split
        over ``axes`` (the mesh's x and y, or y and x for a transposed
        block)."""
        parts = {k: pick(f) for k, f in host["shards"].items()}
        rows, cols = parts[(0, 0)].shape

        def part(idx):
            at = {axes[0]: (idx[0].start or 0) // rows,
                  axes[1]: (idx[1].start or 0) // cols}
            return parts[(at[X_AXIS], at[Y_AXIS])]

        return jax.make_array_from_callback(
            (mesh.shape[axes[0]] * rows, mesh.shape[axes[1]] * cols),
            NamedSharding(mesh, PartitionSpec(*axes)), part)

    with obs.span("mg.hierarchy.build"):
        host = mesh_hierarchy64(problem, plan, scaled, config, cast, count)
        levels = tuple(
            tuple(None if unread(lvl, k) else
                  place(lambda f, l=lvl, k=k: f["levels"][l][k])
                  for k in range(3))
            for lvl in range(plan.replicated_from))
        strips = tuple(
            tuple(place(lambda f, l=lvl, k=k: f["strips"][l][k],
                        (Y_AXIS, X_AXIS))
                  for k in range(3))
            for lvl in range(count))
        levels += tuple(tuple(jax.device_put(np.asarray(f, dt), whole)
                              for f in lv) for lv in host["tail"])
        coarse_inv = (None if host["coarse_inv"] is None else
                      jax.device_put(np.asarray(host["coarse_inv"], dt),
                                     whole))
        hier = MGLevels(levels=levels, coarse_inv=coarse_inv,
                        scinv=place(lambda f: f["scinv"]), strips=strips)
        out = (hier, place(lambda f: f["rhs"]), place(lambda f: f["aux"]))
    _HIERARCHIES[key] = out
    obs.gauge("mg.levels", len(levels))
    obs.gauge("mg.coarse_dense", 1 if coarse_inv is not None else 0)
    obs.event("mg.hierarchy", grid=f"{problem.M}x{problem.N}",
              levels=len(levels),
              coarsest="x".join(map(str, plan.dims[-1])),
              dense_coarse=coarse_inv is not None, fingerprint=None,
              mesh=f"{plan.px}x{plan.py}",
              replicated_from=plan.replicated_from)
    return out


def hierarchy_from_fields(problem: Problem, a64: np.ndarray,
                          b64: np.ndarray, dtype_name: str, scaled: bool,
                          config: MGConfig = DEFAULT_MG) -> MGLevels:
    """Uncached hierarchy straight from explicit host canvases — the
    manufactured-solution oracle's path (``geometry.manufactured``
    builds its own fields and must precondition exactly those)."""
    return _cast_levels(build_hierarchy64(problem, a64, b64, config),
                        dtype_name, scaled)
