"""Memory-roofline probe for the fused Pallas PCG path.

Answers the question BENCH.md's 2400x3200 plateau raises: is the fused
path at the chip's memory-bandwidth ceiling, or is there pipelining
headroom? Three measurements, one JSON report:

1. **Device identity** — ``device_kind`` + HBM stats. The plateau analysis
   depends on which chip it runs on (HBM peak differs ~2.3x
   between TPU generations, and some have a large on-chip common memory
   that can hold the smaller grids' whole working set).
2. **Stream ceiling** — achievable HBM bandwidth measured with the same
   timing discipline the solver bench uses: a jitted ``y = x * gate``
   (one read + one write per element) over an array sized like the
   solve's working set, chained through a data dependency so runs cannot
   overlap, differenced to cancel the constant dispatch/fetch latency.
3. **Solver traffic** — per-iteration wall time of the fused solve at a
   fixed iteration budget (convergence disabled via a tiny delta), at one
   or more strip heights, converted to implied bytes/s through the
   pass-count model below and compared against (2).

Pass model (canvas bytes = rows x cols x 4, fp32):
  kernel A reads z, p, cs as halo-inclusive strips ((bm+2H)/bm overfetch)
  plus cw, g as blocks, and writes p_new, Ap:   (3*(bm+2H)/bm + 2) + 2
  kernel B reads p, Ap, sc2, w, r and writes w, r:              5 + 2
An implied/stream ratio near 1.0 means the kernels saturate the memory
system and further speedup at that grid must come from traffic reduction,
not scheduling; a low ratio means pipelining/geometry is leaving
bandwidth on the table. Ratios above 1.0 indicate on-chip residency
(the working set partially living in cache/CMEM, so HBM is not the
limiting channel at that size).

Usage:
    python benchmarks/roofline.py [M N] [--bm 48,72,96] [--iters 200]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _stream_gbps(jnp, jax, n_elems: int, reps: int = 5) -> float:
    """Best achieved GB/s for a 1-read + 1-write elementwise pass over
    ``n_elems`` fp32 elements, overlap-proof and latency-differenced.
    Returns 0.0 when the differenced time is within timer noise (array too
    small to measure) — callers treat 0 as 'no stream ceiling available'."""
    n_elems = max(n_elems, 8 * 2**20)  # ≥32 MB: keep the slope above noise
    x = jnp.ones((n_elems,), jnp.float32)

    @jax.jit
    def step(v):
        return v * jnp.float32(1.0000001)

    step(x).block_until_ready()  # compile

    def chain(k: int) -> float:
        t0 = time.perf_counter()
        v = x
        for _ in range(k):
            v = step(v)
        v[0].block_until_ready()
        return time.perf_counter() - t0

    k_lo, k_hi = 2, 12
    t_lo = min(chain(k_lo) for _ in range(reps))
    t_hi = min(chain(k_hi) for _ in range(reps))
    per_pass = (t_hi - t_lo) / (k_hi - k_lo)
    if per_pass <= 0:
        return 0.0
    return (n_elems * 4 * 2) / per_pass / 1e9


def _solver_iter_seconds(problem, bm: int | None, iters: int,
                         interpret: bool,
                         parallel: bool = False,
                         bn: int | None = None) -> tuple[float, dict]:
    """Wall seconds per fused-solve iteration at a fixed iteration budget
    (delta set below any reachable diff, so exactly ``iters`` iterations
    run), differenced between two budgets to cancel setup/fetch."""
    import dataclasses

    from poisson_tpu.ops.pallas_cg import build_canvases, _fused_solve

    if iters < 20:
        raise ValueError(f"need --iters >= 20 for a meaningful slope, got {iters}")
    lo = dataclasses.replace(problem, delta=1e-30, max_iter=iters // 4)
    hi = dataclasses.replace(problem, delta=1e-30, max_iter=iters)

    from poisson_tpu.ops.pallas_cg import _resolve_serial

    # Resolve BEFORE the canvas build: a doomed serial+parallel row must
    # fail instantly (still recorded as an error row), not after a
    # multi-GB host build + device transfer. Also guarantees a sweep can
    # never record a 'parallel' row that actually ran serial.
    serial = _resolve_serial(None, parallel)
    cv, cs, cw, g, rhs, sc2, _ = build_canvases(hi, bm, "float32", bn)

    def run(p):
        s = _fused_solve(p, cv, interpret, parallel, serial,
                         cs, cw, g, rhs, sc2)
        s.diff.block_until_ready()
        return s

    run(lo)  # compile both budgets before timing
    run(hi)

    def timed(p) -> float:
        t0 = time.perf_counter()
        run(p)
        return time.perf_counter() - t0

    t_lo = min(timed(lo) for _ in range(3))
    t_hi = min(timed(hi) for _ in range(3))
    per_iter = (t_hi - t_lo) / (hi.max_iter - lo.max_iter)

    from poisson_tpu.ops.pallas_cg import HALO

    canvas_bytes = cv.rows * cv.cols * 4
    row_of = (cv.bm + 2 * HALO) / cv.bm
    col_of = ((cv.bn + 2 * cv.cg) / cv.bn) if cv.cg else 1.0
    # kernel A: z, p overfetch both ways; cs rows only; cw cols only.
    passes = (2 * row_of * col_of + row_of + col_of + 1 + 2) + (5 + 2)
    geom = {
        "bm": cv.bm, "nb": cv.nb, "bn": cv.bn or None, "ncb": cv.ncb,
        "serial_reduce": serial,
        "canvas_rows": cv.rows,
        "canvas_cols": cv.cols, "canvas_mb": round(canvas_bytes / 2**20, 1),
        "model_passes": round(passes, 2),
        "model_bytes_per_iter_mb": round(passes * canvas_bytes / 2**20, 1),
    }
    return per_iter, geom


def _ca_iter_seconds(problem, bm: int | None, iters: int,
                     interpret: bool,
                     parallel: bool = False) -> tuple[float, dict]:
    """Per-iteration slope of the CA(s=2) pair path (full-width only).

    Pass model per PAIR of iterations: kernel C reads pprev, r, cs, cw, g
    as halo-inclusive strips plus the sc2 block and writes pn, t1, t2, t3
    (5·row_of + 1 + 4); kernel D reads six center blocks and writes three
    (9). Per iteration: (5·row_of + 14)/2 ≈ 10.1 at the plateau
    geometry — the 1.46× traffic reduction BENCH.md's CA section claims,
    now measurable against the same stream ceiling as the fused rows."""
    import dataclasses

    from poisson_tpu.ops.pallas_ca import _ca_solve, pick_bm_ca
    from poisson_tpu.ops.pallas_cg import (
        HALO,
        _resolve_serial,
        build_canvases,
    )

    if iters < 20:
        raise ValueError(f"need --iters >= 20 for a meaningful slope, got {iters}")
    lo = dataclasses.replace(problem, delta=1e-30, max_iter=iters // 4)
    hi = dataclasses.replace(problem, delta=1e-30, max_iter=iters)
    serial = _resolve_serial(None, parallel)
    if bm is None:
        bm = pick_bm_ca(problem)
    cv, cs, cw, g, rhs, sc2, _ = build_canvases(hi, bm, "float32", 0)

    def run(p):
        s = _ca_solve(p, cv, interpret, parallel, serial,
                      cs, cw, g, rhs, sc2)
        s.diff.block_until_ready()
        return s

    run(lo)
    run(hi)

    def timed(p) -> float:
        t0 = time.perf_counter()
        run(p)
        return time.perf_counter() - t0

    t_lo = min(timed(lo) for _ in range(3))
    t_hi = min(timed(hi) for _ in range(3))
    per_iter = (t_hi - t_lo) / (hi.max_iter - lo.max_iter)

    canvas_bytes = cv.rows * cv.cols * 4
    row_of = (cv.bm + 2 * HALO) / cv.bm
    passes = (5 * row_of + 1 + 4 + 9) / 2.0   # per iteration (pair / 2)
    geom = {
        "backend": "ca", "bm": cv.bm, "nb": cv.nb, "bn": None, "ncb": 1,
        "serial_reduce": serial,
        "canvas_rows": cv.rows,
        "canvas_cols": cv.cols, "canvas_mb": round(canvas_bytes / 2**20, 1),
        "model_passes": round(passes, 2),
        "model_bytes_per_iter_mb": round(passes * canvas_bytes / 2**20, 1),
    }
    return per_iter, geom


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("M", nargs="?", type=int, default=2400)
    ap.add_argument("N", nargs="?", type=int, default=3200)
    ap.add_argument("--bm", default=None,
                    help="comma-separated strip heights (default: auto pick)")
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--parallel", action="store_true",
                    help="also measure each geometry with the strip grid "
                         "marked parallel (megacore TensorCore split)")
    ap.add_argument("--bn", default=None,
                    help="comma-separated column-block widths to add to the "
                         "sweep (each paired with every --bm; 0 = full "
                         "width)")
    ap.add_argument("--backend", default="fused",
                    help="comma list of fused,ca — the 2-sweep path and/or "
                         "the CA(s=2) pair path (CA ignores --bn: "
                         "full-width only)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from poisson_tpu.config import Problem

    dev = jax.devices()[0]
    interpret = dev.platform != "tpu"
    try:
        mem = dev.memory_stats() or {}
    except Exception:
        mem = {}
    report = {
        "device_kind": dev.device_kind,
        "platform": dev.platform,
        "hbm_limit_gb": round(mem.get("bytes_limit", 0) / 2**30, 1) or None,
    }

    problem = Problem(M=args.M, N=args.N)
    # Stream array sized like the solve's state working set (4 canvases),
    # capped to stay comfortably allocatable alongside the solve.
    n_interior = (problem.M - 1) * (problem.N + 1)
    # Same clamps _stream_gbps applies, so the report matches what ran.
    n_stream = max(min(4 * n_interior, 512 * 2**20 // 4), 8 * 2**20)
    report["stream_gbps"] = round(_stream_gbps(jnp, jax, n_stream), 1)
    report["stream_elems_mb"] = round(n_stream * 4 / 2**20, 1)

    bms = ([int(b) for b in args.bm.split(",")] if args.bm else [None])
    # bn=0 is canvas_spec's force-full-width sentinel; None (no flag) is
    # the shipping auto-pick.
    bns = ([int(b) for b in args.bn.split(",")] if args.bn else [None])
    backends = args.backend.split(",")
    unknown = set(backends) - {"fused", "ca"}
    if unknown:
        print(f"unknown --backend {sorted(unknown)}", file=sys.stderr)
        return 2
    rows = []
    for backend in backends:
        for bm in bms:
            for bn in (bns if backend == "fused" else [None]):
                for parallel in ([False, True] if args.parallel
                                 else [False]):
                    try:
                        if backend == "ca":
                            per_iter, geom = _ca_iter_seconds(
                                problem, bm, args.iters, interpret, parallel
                            )
                        else:
                            per_iter, geom = _solver_iter_seconds(
                                problem, bm, args.iters, interpret,
                                parallel, bn
                            )
                    except Exception as e:
                        rows.append({"backend": backend, "bm": bm, "bn": bn,
                                     "parallel": parallel,
                                     "error": repr(e)[:200]})
                        continue
                    implied = (
                        geom["model_bytes_per_iter_mb"] * 2**20
                        / per_iter / 1e9
                    )
                    mlups = (
                        (problem.M - 1) * (problem.N - 1) / per_iter / 1e6
                    )
                    rows.append({
                        "backend": backend,
                        **geom,
                        "parallel": parallel,
                        "iter_seconds": round(per_iter, 6),
                        "mlups": round(mlups, 1),
                        "implied_gbps": round(implied, 1),
                        "implied_over_stream": round(
                            implied / report["stream_gbps"], 2
                        ) if report["stream_gbps"] else None,
                    })
    report["grid"] = [args.M, args.N]
    report["solver"] = rows
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
