"""MG smoke check: ``python -m poisson_tpu.mg.selfcheck``.

Three checks, each a one-line verdict, exit 0 iff all pass:

1. **Two-grid convergence factor** — the stationary cycle
   x ← x + B⁻¹(0 − Ax) on the literature's model problem (unit
   coefficients, square domain, h1 = h2 — Briggs/Henson/McCormick
   ch. 4) with a depth-2 hierarchy (exact dense coarse solve) must
   contract by < 0.2 per cycle. This is the smoothing+coarse-correction
   identity working at all; measured ≈ 0.13. (The production domain is
   2:1.2 anisotropic, which degrades a point-smoother cycle to ≈ 0.4–0.7
   — the outer CG absorbs that, see README "Multigrid preconditioning";
   the model problem is where the algorithm has no excuses.)
2. **Deep V-cycle on the model problem** — the full hierarchy keeps the
   factor < 0.25 (depth must not break the cycle).
3. **Iteration wall** — ``preconditioner="mg"`` beats Jacobi's
   iteration count by ≥ 3× on the reference problem at two resolutions,
   converging to the same δ.
"""

from __future__ import annotations

import argparse
import sys

import numpy as np


def two_grid_factor(M: int, N: int, max_levels: int, cycles: int = 8,
                    ) -> float:
    """Worst per-cycle contraction of the stationary MG iteration on
    the isotropic unit-coefficient model problem."""
    import jax
    import jax.numpy as jnp

    from poisson_tpu.config import Problem
    from poisson_tpu.mg import MGConfig, hierarchy_from_fields, v_cycle
    from poisson_tpu.ops.stencil import apply_A

    p = Problem(M=M, N=N, x_min=-1.0, x_max=1.0, y_min=-1.0, y_max=1.0)
    cfg = MGConfig(max_levels=max_levels)
    ones = np.ones((p.M + 1, p.N + 1))
    dtype_name = ("float64" if jax.config.jax_enable_x64 else "float32")
    hier = hierarchy_from_fields(p, ones, ones, dtype_name, False, cfg)
    a = b = jnp.asarray(ones, jnp.dtype(dtype_name))
    rng = np.random.default_rng(0)
    x0 = np.zeros((p.M + 1, p.N + 1))
    x0[1:-1, 1:-1] = rng.standard_normal((p.M - 1, p.N - 1))
    x = jnp.asarray(x0, jnp.dtype(dtype_name))

    step = jax.jit(lambda x: x + v_cycle(
        hier, -apply_A(x, a, b, p.h1, p.h2), p.h1, p.h2, cfg))
    prev = float(jnp.linalg.norm(x))
    worst = 0.0
    for _ in range(cycles):
        x = step(x)
        cur = float(jnp.linalg.norm(x))
        worst = max(worst, cur / prev)
        prev = cur
    return worst


def run_selfcheck() -> int:
    from poisson_tpu.config import Problem
    from poisson_tpu.solvers.pcg import pcg_solve

    failures = 0

    tg = two_grid_factor(64, 64, max_levels=2)
    ok = tg < 0.2
    print(f"[{'ok' if ok else 'FAIL'}] two-grid contraction on the "
          f"model problem: {tg:.4f} (< 0.2 required)")
    failures += 0 if ok else 1

    deep = two_grid_factor(64, 64, max_levels=16)
    ok = deep < 0.25
    print(f"[{'ok' if ok else 'FAIL'}] deep V-cycle contraction on the "
          f"model problem: {deep:.4f} (< 0.25 required)")
    failures += 0 if ok else 1

    for M, N in ((32, 32), (64, 96)):
        p = Problem(M=M, N=N)
        rj = pcg_solve(p)
        rm = pcg_solve(p, preconditioner="mg")
        kj, km = int(rj.iterations), int(rm.iterations)
        ok = (int(rm.flag) == 1 and float(rm.diff) < p.delta
              and km * 3 <= kj)
        print(f"[{'ok' if ok else 'FAIL'}] iteration wall {M}x{N}: "
              f"jacobi {kj} -> mg {km} (>=3x fewer, converged, "
              f"flag={int(rm.flag)})")
        failures += 0 if ok else 1

    if failures:
        print(f"mg selfcheck: {failures} check(s) FAILED")
        return 1
    print("mg selfcheck OK")
    return 0


def main(argv=None) -> int:
    argparse.ArgumentParser(
        prog="python -m poisson_tpu.mg.selfcheck",
        description=__doc__.splitlines()[0],
    ).parse_args(argv)
    return run_selfcheck()


if __name__ == "__main__":
    sys.exit(main())
