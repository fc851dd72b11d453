"""Benchmark sweep: reproduce the reference's published tables (SURVEY §7.7).

The reference publishes grid × parallelism-config tables per stage
(BASELINE.md). This harness regenerates the same shape of data for the new
framework's backends and reports each row against the best published
reference number for that grid:

    python benchmarks/sweep.py                       # default sweep
    python benchmarks/sweep.py --grids 40x40,400x600 --backends xla,native
    python benchmarks/sweep.py --meshes 1x1,2x2,2x4  # sharded scaling sweep
    python benchmarks/sweep.py --threads 1,2,4,8     # native thread sweep
    python benchmarks/sweep.py --curve 400x600:600 --curve-out curve.csv

Output: a markdown table (stdout, optionally --out FILE) with one row per
(backend, config, grid): iterations, best solve time, MLUPS, speedup vs the
reference's best published time for that grid, L2(D) error. ``--curve``
writes the per-iteration ‖Δw‖ / L2-error history (the report's
L2-error-vs-iteration curve, SURVEY §4.2) as CSV.

Timing: best of --repeat fenced runs. For headline numbers prefer
bench.py's differenced-chain method; this sweep favors breadth over
per-row methodology.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Best published reference time per grid: (config, seconds, iterations).
# Sources: BASELINE.md (Этап1-4 PDFs' tables).
REFERENCE_BEST = {
    (40, 40): ("stage2 MPI 2p", 0.00186, 60),
    (400, 600): ("stage3 2MPIx8OMP", 0.313, 546),
    (800, 1200): ("stage4 2xP100", 0.64, 989),
    (1600, 2400): ("stage4 2xP100", 3.19, 1858),
    (2400, 3200): ("stage4 2xP100", 7.67, 2449),
}


def _parse_pair(spec: str, sep: str = "x") -> tuple[int, int]:
    a, b = spec.lower().split(sep)
    return int(a), int(b)


def _parse_curve(spec: str) -> tuple[int, int, int]:
    try:
        grid, iters = spec.rsplit(":", 1)
        M, N = _parse_pair(grid)
        return M, N, int(iters)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"curve must look like '400x600:600', got {spec!r}"
        )


def _parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--grids", default="40x40,400x600,800x1200")
    p.add_argument("--backends", default="auto",
                   help="comma list of xla,pallas,pallas-ca,pallas-resident,"
                        "sharded,pallas-sharded,pallas-ca-sharded,native; "
                        "'auto' = xla+native, plus sharded when >1 device, "
                        "plus pallas (and pallas-sharded when >1 device) on "
                        "TPU (pallas-resident skips grids that exceed VMEM)")
    p.add_argument("--meshes", default=None,
                   help="comma list like 1x1,2x2,2x4 (sharded rows; default: "
                        "near-square over all devices)")
    p.add_argument("--threads", default="1,8",
                   help="comma list of OpenMP team sizes (native rows)")
    p.add_argument("--repeat", type=int, default=2)
    p.add_argument("--out", default=None, help="also write the table here")
    p.add_argument("--curve", default=None, type=_parse_curve,
                   metavar="MxN:ITERS",
                   help="record a per-iteration convergence/error curve")
    p.add_argument("--curve-out", default="curve.csv")
    return p.parse_args(argv)


def _row(backend: str, config: str, problem, iters: int,
         seconds: float, l2: float) -> dict:
    from poisson_tpu.utils.timing import mlups

    grid = (problem.M, problem.N)
    ref = REFERENCE_BEST.get(grid)
    return {
        "backend": backend, "config": config, "grid": f"{grid[0]}x{grid[1]}",
        "iters": iters, "seconds": seconds,
        "mlups": mlups(problem, iters, seconds),
        "speedup_vs_ref": (ref[1] / seconds) if ref else None,
        "ref": ref[0] if ref else "-", "l2_error": l2,
    }


def _fmt_table(rows: list[dict]) -> str:
    head = ("| backend | config | grid | iters | time (s) | MLUPS | "
            "vs ref best | ref best | L2 err |")
    sep = "|---" * 9 + "|"
    out = [head, sep]
    for r in rows:
        vs = f"{r['speedup_vs_ref']:.2f}x" if r["speedup_vs_ref"] else "-"
        out.append(
            f"| {r['backend']} | {r['config']} | {r['grid']} | {r['iters']} "
            f"| {r['seconds']:.4f} | {r['mlups']:.0f} | {vs} | {r['ref']} "
            f"| {r['l2_error']:.2e} |"
        )
    return "\n".join(out)


def _timed(run, fence, repeat: int):
    result = run()
    fence(result)  # compile + first
    best = None
    for _ in range(max(1, repeat)):
        t0 = time.perf_counter()
        result = run()
        fence(result.iterations)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return result, best


def main(argv=None) -> int:
    args = _parse_args(argv)

    import jax

    from poisson_tpu.analysis import l2_error_host as l2
    from poisson_tpu.config import Problem
    from poisson_tpu.utils.timing import fence

    devices = jax.devices()
    platform = devices[0].platform

    if args.backends == "auto":
        backends = ["xla", "native"]
        if len(devices) > 1:
            backends.append("sharded")
        if platform == "tpu":
            backends.append("pallas")
            if len(devices) > 1:
                backends.append("pallas-sharded")
    else:
        backends = args.backends.split(",")

    grids = [_parse_pair(g) for g in args.grids.split(",")]
    threads = [int(t) for t in args.threads.split(",")]

    rows = []
    for grid in grids:
        problem = Problem(M=grid[0], N=grid[1])

        for backend in backends:
            if backend == "xla":
                from poisson_tpu.solvers.pcg import pcg_solve

                res, best = _timed(lambda: pcg_solve(problem), fence,
                                   args.repeat)
                rows.append(_row("xla", f"1 dev ({platform})", problem,
                                 int(res.iterations), best, l2(problem, res.w)))
            elif backend == "pallas":
                from poisson_tpu.ops.pallas_cg import pallas_cg_solve

                res, best = _timed(lambda: pallas_cg_solve(problem), fence,
                                   args.repeat)
                rows.append(_row("pallas", "1 dev fused", problem,
                                 int(res.iterations), best, l2(problem, res.w)))
            elif backend == "pallas-ca":
                from poisson_tpu.ops.pallas_ca import ca_cg_solve

                res, best = _timed(lambda: ca_cg_solve(problem), fence,
                                   args.repeat)
                rows.append(_row("pallas-ca", "1 dev s=2 pairs", problem,
                                 int(res.iterations), best, l2(problem, res.w)))
            elif backend == "pallas-resident":
                from poisson_tpu.ops.pallas_resident import (
                    fits_resident,
                    resident_cg_solve,
                )

                if not fits_resident(problem):
                    print(f"  skip: pallas-resident does not fit {grid}",
                          file=sys.stderr)
                    continue
                res, best = _timed(lambda: resident_cg_solve(problem),
                                   fence, args.repeat)
                rows.append(_row("pallas-resident", "1 dev VMEM-resident",
                                 problem, int(res.iterations), best,
                                 l2(problem, res.w)))
            elif backend in ("sharded", "pallas-sharded",
                             "pallas-ca-sharded"):
                from poisson_tpu.parallel import (
                    ca_cg_solve_sharded,
                    make_solver_mesh,
                    pallas_cg_solve_sharded,
                    pcg_solve_sharded,
                )

                meshes = (
                    [_parse_pair(m) for m in args.meshes.split(",")]
                    if args.meshes
                    else [None]
                )
                for shape in meshes:
                    subset = (
                        devices[: shape[0] * shape[1]] if shape else None
                    )
                    mesh = make_solver_mesh(subset, grid=shape)
                    px, py = mesh.shape["x"], mesh.shape["y"]
                    if backend == "pallas-sharded":
                        run = lambda: pallas_cg_solve_sharded(problem, mesh)
                    elif backend == "pallas-ca-sharded":
                        run = lambda: ca_cg_solve_sharded(problem, mesh)
                    else:
                        run = lambda: pcg_solve_sharded(problem, mesh)
                    res, best = _timed(run, fence, args.repeat)
                    rows.append(_row(backend, f"mesh {px}x{py} ({platform})",
                                     problem, int(res.iterations), best,
                                     l2(problem, res.w)))
            elif backend == "native":
                from poisson_tpu.native import build, native_solve

                build()
                for t in threads:
                    def run():
                        return native_solve(problem, num_threads=t)

                    res, best = _timed(run, lambda x: None, args.repeat)
                    rows.append(_row("native", f"OpenMP {t}t", problem,
                                     res.iterations, best, l2(problem, res.w)))
            else:
                print(f"unknown backend {backend!r}", file=sys.stderr)
                return 2
            print(f"  done: {backend} {grid}", file=sys.stderr)

    table = _fmt_table(rows)
    print(table)
    if args.out:
        with open(args.out, "w") as f:
            f.write(table + "\n")

    if args.curve:
        from poisson_tpu.solvers.history import pcg_solve_history

        M, N, iters = args.curve
        h = pcg_solve_history(Problem(M=M, N=N), budget=iters)
        with open(args.curve_out, "w") as f:
            f.write("iteration,diff_norm,residual_dot,l2_error\n")
            for k in range(iters):
                f.write(
                    f"{k + 1},{float(h.diffs[k]):.6e},"
                    f"{float(h.residual_dots[k]):.6e},"
                    f"{float(h.l2_errors[k]):.6e}\n"
                )
        print(f"curve ({int(h.iterations)} real iterations) -> "
              f"{args.curve_out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
