"""Geometric multigrid preconditioning (``preconditioner="mg"``).

Breaks the iteration wall: Jacobi-preconditioned CG pays iterations
that scale with resolution (989 @ 800×1200 → 1858 @ 1600×2400); one
V-cycle per CG iteration over coarsened copies of the same
fictitious-domain blend canvases makes the count near-flat in
resolution. See README "Multigrid preconditioning".

Layout:

- ``hierarchy`` — level planning, coefficient coarsening, the
  fingerprint-keyed device hierarchy cache, the dense coarsest inverse;
- ``cycle`` — full-weighting restriction, bilinear prolongation,
  weighted-Jacobi smoothing, the symmetric V-cycle (whose largest levels
  smooth on the Pallas strip kernels of ``ops.pallas_mg`` in the solo
  program on a TPU);
- ``preconditioner`` — the ops bundle (``apply_Dinv`` = one V-cycle)
  and the jitted MG twins of every flag-off solve program;
- ``selfcheck`` — ``python -m poisson_tpu.mg.selfcheck``: the two-grid
  contraction smoke (< 0.2 on the model problem) plus an MG-vs-Jacobi
  iteration comparison.
"""

from poisson_tpu.mg.cycle import (                      # noqa: F401
    prolong_bilinear,
    restrict_full_weighting,
    smooth_jacobi,
    v_cycle,
)
from poisson_tpu.mg.hierarchy import (                  # noqa: F401
    DEFAULT_MG,
    MGConfig,
    MGLevels,
    PRECONDITIONERS,
    build_hierarchy64,
    coarsen_a,
    coarsen_b,
    device_hierarchy,
    hierarchy_from_fields,
    plan_levels,
    reset_hierarchy_cache,
    resolve_preconditioner,
    validate_mg_problem,
)
from poisson_tpu.mg.preconditioner import (             # noqa: F401
    mg_ops,
    mg_solve_setup,
)
