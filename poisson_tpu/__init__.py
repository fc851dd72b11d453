"""poisson_tpu — a TPU-native (JAX/XLA/Pallas) fictitious-domain Poisson framework.

Re-implements, TPU-first, the full capability surface of the reference
``mxy-kit/poisson-ellipse-openmp-mpi-cuda-new`` (a five-stage C++/OpenMP/MPI/CUDA
PCG solver for the 2D Poisson equation on the elliptic domain x² + 4y² < 1 via
the fictitious-domain method — see SURVEY.md):

- ``models``   — problem setup: geometry, fictitious-domain coefficients, RHS,
                 analytic solution (reference layer 4, SURVEY §2.1).
- ``ops``      — the operator library: 5-point variable-coefficient stencil,
                 Jacobi preconditioner, weighted dots, fused updates; pure-JAX
                 reference ops plus Pallas TPU kernels (reference layer 3, §2.2).
- ``solvers``  — the PCG iteration controller as a ``lax.while_loop``
                 (reference layer 2, §1).
- ``parallel`` — the distributed runtime: 2D device mesh, ``shard_map``,
                 ``ppermute`` halo exchange, ``psum`` reductions — the TPU-native
                 equivalent of the reference's MPI decomposition (§2.3-2.4).
- ``utils``    — instrumentation, timing, reporting (reference layer 7, §5).
- ``obs``      — unified telemetry: spans on the profiler's clock (and
                 Chrome/Perfetto traces +
                 JSONL event logs, per-rank mergeable), always-on counters,
                 and opt-in streamed convergence out of the fused loop —
                 the production observability layer the reference's five
                 hand-placed ``MPI_Wtime`` accumulators only hinted at.
- ``serve``    — the request-lifecycle layer over the solvers: bounded
                 admission with typed shedding, per-request deadlines
                 propagated into chunked solves, retry/backoff with
                 poisoned-member bucket isolation, per-cohort circuit
                 breaking, and a graceful-degradation ladder — chaos-
                 tested (``testing.chaos``; ``python -m poisson_tpu
                 chaos --all``) against the no-lost-request invariant.
- ``mg``       — geometric multigrid preconditioning
                 (``preconditioner="mg"``): a symmetric V-cycle over
                 coarsened copies of the same fictitious-domain blend
                 canvases, plugged into the shared PCG body through the
                 ``apply_Dinv`` seam — near-flat iteration counts in
                 resolution where the Jacobi diagonal's double per
                 refinement (the measured 10–50× lever at the
                 large-grid end; README "Multigrid preconditioning").

The single-device solver is the stage0/stage1 equivalent; the sharded solver is
the stage2/3/4 equivalent; Pallas kernels play the role of stage4's CUDA kernels.
"""

from poisson_tpu.config import Problem
from poisson_tpu.solvers.pcg import pcg_solve, PCGResult

__version__ = "0.1.0"

__all__ = ["Problem", "pcg_solve", "PCGResult", "__version__"]
