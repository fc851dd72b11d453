"""Batched-path smoke check: ``python -m poisson_tpu.solvers.batched_selfcheck``.

The ``obs.selfcheck`` pattern applied to the multi-RHS driver: a tiny
batch with distinct RHS gates must reproduce the sequential solver
bit-for-bit per member (iterates, flags, iteration counts — the masked
freeze working), pad to its bucket invisibly, and count its bucket-cache
traffic in ``obs.metrics``. Exit 0 on success, 1 with a reason on the
first failure — a few CPU seconds, so CI can prove the batched pipeline
end to end.
"""

from __future__ import annotations

import sys


def run_selfcheck() -> int:
    import numpy as np

    from poisson_tpu.config import Problem
    from poisson_tpu.obs import metrics
    from poisson_tpu.solvers.batched import bucket_size, solve_batched
    from poisson_tpu.solvers.pcg import FLAG_CONVERGED, pcg_solve

    def fail(reason: str) -> int:
        print(f"batched selfcheck FAILED: {reason}", file=sys.stderr)
        return 1

    problem = Problem(M=40, N=40)
    gates = (0.25, 1.0, 4.0)
    seq = [pcg_solve(problem, rhs_gate=g) for g in gates]
    bat = solve_batched(problem, rhs_gates=gates)

    iters = np.asarray(bat.iterations)
    if iters.shape != (len(gates),):
        return fail(f"iterations not per-member: shape {iters.shape}")
    for i, r in enumerate(seq):
        if int(iters[i]) != int(r.iterations):
            return fail(f"member {i}: iterations {int(iters[i])} != "
                        f"sequential {int(r.iterations)}")
        if int(np.asarray(bat.flag)[i]) != int(r.flag):
            return fail(f"member {i}: flag mismatch")
        if not np.array_equal(np.asarray(bat.w)[i], np.asarray(r.w)):
            return fail(f"member {i}: solution not bit-identical")
    if len({int(k) for k in iters}) < 2:
        return fail("gates did not produce distinct iteration counts — "
                    "the masked freeze went unexercised")
    if not (np.asarray(bat.flag) == FLAG_CONVERGED).all():
        # Equality, not min(): the failure flags (breakdown/nonfinite/
        # stagnated) rank ABOVE converged numerically.
        return fail("not every member converged")
    if int(bat.max_iterations) != max(int(r.iterations) for r in seq):
        return fail("max_iterations disagrees with the member vector")
    if bucket_size(len(gates)) != 4:
        return fail("bucket ladder changed: 3 members should bucket to 4")
    hits0 = metrics.get("batched.bucket_cache.hits")
    solve_batched(problem, rhs_gates=gates)   # same bucket: a cache hit
    if metrics.get("batched.bucket_cache.hits") <= hits0:
        return fail("bucket-cache hit not counted on reuse")
    print(f"batched selfcheck OK: {len(gates)} members (bucket 4), "
          f"iterations {[int(k) for k in iters]}, all converged "
          "bit-identical to sequential")
    return 0


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m poisson_tpu.solvers.batched_selfcheck",
        description=__doc__.splitlines()[0],
    )
    ap.parse_args(argv)
    return run_selfcheck()


if __name__ == "__main__":
    sys.exit(main())
