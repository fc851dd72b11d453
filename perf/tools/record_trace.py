"""Record a small profiler trace on the chip for perf/tests/test_trace.py:
a few solves of a small grid, each under the harness's own host spans,
on one chip (the fused kernel) or on a 2x2 mesh of four (with the halo
permutes and all-reduces), with a host-only pause between solves so that
the trace holds idle gaps to attribute.

    python -m perf.tools.record_trace --chips 4 --out perf/tests/data/x.xplane.pb
"""

from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import time


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--grid", default="96x128")
    parser.add_argument("--solves", type=int, default=3)
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)

    from perf import run as harness

    harness.configure_jax()
    import jax
    from jax.profiler import TraceAnnotation

    from poisson_tpu.config import Problem

    devices = harness._ensure_chip(args.chips)
    M, N = (int(v) for v in args.grid.split("x"))
    problem = Problem(M=M, N=N)
    if args.chips == 4:
        from poisson_tpu.parallel import (
            make_solver_mesh,
            pallas_cg_solve_sharded,
        )

        mesh = make_solver_mesh(devices, grid=(2, 2))
        solve = lambda g: pallas_cg_solve_sharded(problem, mesh, rhs_gate=g)
    else:
        from poisson_tpu.ops.pallas_cg import pallas_cg_solve

        solve = lambda g: pallas_cg_solve(problem, rhs_gate=g)
    jax.block_until_ready(solve(1.0).w)
    trace_dir = str(harness.TRACE_DIR)
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    with TraceAnnotation("perf.window"):
        for i in range(args.solves):
            with TraceAnnotation("perf.dispatch"):
                r = solve(1.0 + 0.1 * i)
            with TraceAnnotation("perf.fetch"):
                jax.block_until_ready((r.w, r.iterations))
                int(r.iterations)
            with TraceAnnotation("perf.wait"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    shutil.copy(found[0], args.out)
    print(f"{args.out}: {os.path.getsize(args.out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
