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
    levels = []
    a, b = np.asarray(a64, np.float64), np.asarray(b64, np.float64)
    for lvl, (m, n) in enumerate(dims):
        h1 = (problem.x_max - problem.x_min) / m
        h2 = (problem.y_max - problem.y_min) / n
        d = diag_D(a, b, h1, h2)
        levels.append((a, b, np.pad(1.0 / d, 1)))
        if lvl + 1 < len(dims):
            a, b = coarsen_a(a), coarsen_b(b)
    mc, nc = dims[-1]
    coarse_inv = None
    if (mc - 1) * (nc - 1) <= config.coarse_dense_limit:
        ac, bc, _ = levels[-1]
        h1c = (problem.x_max - problem.x_min) / mc
        h2c = (problem.y_max - problem.y_min) / nc
        Ac = _dense_operator(ac, bc, h1c, h2c)
        inv = np.linalg.inv(Ac)
        coarse_inv = 0.5 * (inv + inv.T)   # exactly symmetric: SPD cycle
    d0 = diag_D(np.asarray(a64, np.float64), np.asarray(b64, np.float64),
                problem.h1, problem.h2)
    return {
        "dims": dims,
        "levels": levels,
        "coarse_inv": coarse_inv,
        "scinv": np.pad(np.sqrt(d0), 1),
    }


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

            a64, b64, _, _ = host_fields64(problem.with_(f_val=1.0), False)
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


def hierarchy_from_fields(problem: Problem, a64: np.ndarray,
                          b64: np.ndarray, dtype_name: str, scaled: bool,
                          config: MGConfig = DEFAULT_MG) -> MGLevels:
    """Uncached hierarchy straight from explicit host canvases — the
    manufactured-solution oracle's path (``geometry.manufactured``
    builds its own fields and must precondition exactly those)."""
    return _cast_levels(build_hierarchy64(problem, a64, b64, config),
                        dtype_name, scaled)
