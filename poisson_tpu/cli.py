"""Command-line driver: the framework's equivalent of the reference `main()`s.

The reference drives each stage with positional ``M N`` argv, compile-time
constants for everything else, and rank-0 stdout reporting
(``stage2-mpi/poisson_mpi_decomp.cpp:463-502``,
``stage4-mpi+cuda/poisson_mpi_cuda_f.cu:986-1039``). This driver exposes the
same workloads over one interface with every constant promoted to a flag:

    python -m poisson_tpu M N [--backend auto|xla|pallas|sharded|native]
                              [--mesh PxxPy] [--dtype ...] [--delta ...]
                              [--threads T] [--repeat K] [--json]
                              [--categories] [--profile DIR]

plus the batched multi-RHS workload (``solvers.batched`` — hundreds of
Poisson problems per dispatch):

    python -m poisson_tpu solve-batched M N --batch B [--vary-rhs]
                              [--compare-sequential] [--dtype ...] [--json]

plus the solve-service fire drill and its chaos campaign
(``poisson_tpu.serve`` / ``testing.chaos`` — README "Solve service &
chaos testing"):

    python -m poisson_tpu serve M N --requests R [--deadline S]
                              [--workers W] [--journal PATH] [--recover]
                              [--kill-worker-at T] [--kill-after K]
                              [--fault-poison K] [--prom-out PATH]
                              [--trace-dir DIR] [--json]
    python -m poisson_tpu chaos --all --seed 0 [--out-dir DIR] [--json]

plus durable solver sessions (``serve.session`` — README "Solver
sessions"): a crash-safe ordered stream of dependent solves (moving
ellipse, or implicit-Euler heat with ``--heat``) warm-started step to
step, journaled, and replayable to the exact step boundary:

    python -m poisson_tpu session M N --steps K [--heat --dt S]
                              [--journal PATH] [--recover]
                              [--kill-after K] [--json]

plus the flight-recorder viewer (``obs.flight`` — one request's causal
timeline and latency decomposition, read from the JSONL event log):

    python -m poisson_tpu trace REQUEST_ID --telemetry DIR [--json]

plus geometry-as-a-request (``poisson_tpu.geometry`` — README "Geometry
requests"): ``--geometry SPEC`` (inline JSON or ``@file.json``) on
``solve``, ``solve-batched`` (repeatable: members round-robin across the
specs and co-batch in one bucket executable), and ``serve``; and a spec
debugger:

    python -m poisson_tpu geometry SPEC [--M 64 --N 64] [--render|--json]

Every entry point keeps the JAX persistent compilation cache
(``utils.compile_cache``) in ``$JAX_COMPILATION_CACHE_DIR`` when set,
else in ``<repo>/.jax_cache``: traced programs persist across processes,
and cache hits/misses land in the metrics snapshot next to
``time.compile_seconds``.

Instrumentation (stage4's ``MPI_Wtime`` bracketing + timer table, SURVEY §5):
- phase wall-clock: setup / compile+first-solve / solve (best of --repeat);
- ``--categories``: reconstructed per-op decomposition of one iteration
  (stencil / preconditioner / dots / axpy), the analog of stage4's
  gpu/precond/dot table — *reconstructed* because the real solve is one
  fused device program, which is the point;
- ``--profile DIR``: a real device timeline via ``jax.profiler.trace``
  (what stage4's hand-inserted timers approximated).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Optional

import numpy as np

from poisson_tpu.config import Problem
from poisson_tpu.utils.timing import PhaseTimer, fence, solve_report


def _parse_geometry_arg(spec: str):
    """A ``--geometry`` value — inline JSON or ``@file.json`` — to a
    normalized spec. Called AFTER parse_args (the parser stays
    jax-import-free); errors exit like every other flag validation."""
    label = spec if len(spec) < 60 else spec[:57] + "..."
    if spec.startswith("@"):
        try:
            with open(spec[1:]) as f:
                spec = f.read()
        except OSError as e:
            raise SystemExit(f"--geometry {label}: {e}")
    from poisson_tpu.geometry import parse_geometry

    try:
        return parse_geometry(spec)
    except ValueError as e:
        raise SystemExit(f"--geometry {label}: {e}")


def _parse_mesh(spec: str) -> tuple[int, int]:
    try:
        px, py = spec.lower().split("x")
        return int(px), int(py)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"mesh must look like '2x4', got {spec!r}"
        )


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m poisson_tpu",
        description="Fictitious-domain Poisson PCG solve (TPU-native framework).",
    )
    p.add_argument("M", type=int, nargs="?", default=None,
                   help="grid cells in x (nodes: M+1)")
    p.add_argument("N", type=int, nargs="?", default=None,
                   help="grid cells in y (nodes: N+1)")
    # Flag aliases for the grid (automation-friendly invocations pass
    # every parameter as a flag); exactly one of the two forms per axis.
    p.add_argument("--M", type=int, default=None, dest="M_opt",
                   metavar="M", help="grid cells in x (same as positional M)")
    p.add_argument("--N", type=int, default=None, dest="N_opt",
                   metavar="N", help="grid cells in y (same as positional N)")
    p.add_argument("--delta", type=float, default=1e-6,
                   help="convergence threshold on ||w(k+1)-w(k)|| (default 1e-6)")
    p.add_argument("--max-iter", type=int, default=None,
                   help="iteration cap (default (M-1)(N-1))")
    p.add_argument("--backend",
                   choices=("auto", "xla", "pallas", "pallas-ca",
                            "pallas-resident", "sharded", "pallas-sharded",
                            "pallas-ca-sharded", "native"),
                   default="auto",
                   help="auto: pallas-sharded on >1 TPU, sharded on >1 CPU "
                        "device, pallas on 1 TPU, else xla. pallas-ca[-"
                        "sharded]: the communication-avoiding s=2 pair "
                        "iteration (fp32, full-width; opt-in), single-device "
                        "or over the mesh with width-2 halos. "
                        "pallas-resident: the whole solve in one "
                        "VMEM-resident kernel (grids that fit, ~<=400x600)")
    p.add_argument("--mesh", type=_parse_mesh, default=None, metavar="PXxPY",
                   help="device mesh shape for --backend sharded (default: "
                        "near-square over all devices)")
    p.add_argument("--setup", choices=("host", "device"), default="host",
                   help="sharded field setup: host fp64 or per-shard on-device")
    p.add_argument("--dtype", choices=("float32", "float64"), default=None,
                   help="state precision (default: float64 if x64 on, else float32)")
    p.add_argument("--threads", type=int, default=0,
                   help="OpenMP threads for --backend native (0 = runtime default)")
    p.add_argument("--bm", type=int, default=None,
                   help="pallas strip height (multiple of 8; default: "
                        "VMEM-budget heuristic)")
    p.add_argument("--bn", type=int, default=None,
                   help="pallas column-block width (multiple of 128). "
                        "Default: auto — full-width strips unless the "
                        "canvas is too wide for a sane strip height, then "
                        "column-blocked. 0 forces full width.")
    p.add_argument("--parallel-grid", action="store_true",
                   help="mark the pallas tile grid parallel (megacore "
                        "TensorCore split; pallas backends)")
    p.add_argument("--serial-reduce", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="reduction-partial layout in the pallas kernels: "
                        "--serial-reduce selects the serial "
                        "Kahan-compensated layout, --no-serial-reduce the "
                        "per-strip tree-summed partials. Tri-state so the "
                        "CLI can override the POISSON_TPU_SERIAL_REDUCE "
                        "env default in BOTH directions (unset: the env "
                        "default, which is per-strip partials)")
    p.add_argument("--unweighted-norm", action="store_true",
                   help="stage0's unweighted convergence norm")
    p.add_argument("--repeat", type=int, default=1,
                   help="timed solve repetitions; report the best")
    p.add_argument("--geometry", metavar="SPEC", default=None,
                   help="solve this domain instead of the reference "
                        "ellipse: a geometry-DSL JSON spec inline or "
                        "@file.json (poisson_tpu.geometry; single-device "
                        "xla backend). Preview specs with `python -m "
                        "poisson_tpu geometry SPEC`")
    p.add_argument("--preconditioner", choices=("jacobi", "mg"),
                   default="jacobi",
                   help="M^-1 for the CG recurrence: jacobi (the "
                        "historical diagonal; default, byte-identical "
                        "executables) or mg — one geometric V-cycle per "
                        "iteration (poisson_tpu.mg: near-flat iteration "
                        "counts in resolution; xla-family backends only; "
                        "the grid must coarsen, i.e. even M and N). "
                        "Check the cycle with `python -m "
                        "poisson_tpu.mg.selfcheck`")
    p.add_argument("--checkpoint", metavar="PATH", default=None,
                   help="persist solver state to PATH every --chunk "
                        "iterations and resume from it (every JAX backend; "
                        "fp32 checkpoints are portable across backends and "
                        "mesh shapes)")
    p.add_argument("--chunk", type=int, default=None,
                   help="iterations between checkpoints (default 200; "
                        "with --fault-nan-at K, min(200, K) so the "
                        "injection boundary lands before a fast solve "
                        "converges)")
    r = p.add_argument_group(
        "resilience",
        "divergence recovery, hardened checkpoints, watchdog, fault "
        "injection (README 'Resilient solves')",
    )
    r.add_argument("--resilient", action="store_true",
                   help="self-healing solve (--backend xla): in-loop "
                        "divergence detection plus restart-from-last-good-"
                        "iterate recovery with precision escalation")
    r.add_argument("--max-restarts", type=int, default=3,
                   help="recovery attempts before the resilient solve "
                        "fails loudly (default 3)")
    r.add_argument("--escalate-precision",
                   action=argparse.BooleanOptionalAction, default=True,
                   help="allow the resilient solve to move up the "
                        "bf16->f32->f64 precision ladder after a repeated "
                        "failure at the same precision (default on)")
    r.add_argument("--stagnation-window", type=int, default=None,
                   metavar="ITERS",
                   help="in-loop stagnation detection: stop after this "
                        "many iterations without a new best ||dw|| "
                        "(default: 200 with --resilient, off otherwise)")
    r.add_argument("--keep-last", type=int, default=2, metavar="K",
                   help="checkpoint generations to retain for corruption "
                        "fallback (default 2)")
    r.add_argument("--heartbeat", metavar="PATH", default=None,
                   help="write a JSON heartbeat file at every chunk "
                        "boundary (chunked solvers)")
    r.add_argument("--watchdog-timeout", type=float, default=None,
                   metavar="SECONDS",
                   help="abort with diagnostics if no chunk completes "
                        "within this window (first chunk includes "
                        "compile time — size generously)")
    r.add_argument("--verify-every", type=int, default=0, metavar="K",
                   help="in-loop integrity probe (poisson_tpu.integrity, "
                        "--backend xla): every K iterations (and on "
                        "every convergence event) recompute the true "
                        "residual ||b-Aw|| and stop with an 'integrity' "
                        "verdict when it drifts from the recurrence — "
                        "silent-data-corruption detection; with "
                        "--resilient the recovery is a verified restart. "
                        "0 (default) traces no probe: the program is "
                        "byte-identical and golden counts bit-for-bit")
    r.add_argument("--verify-tol", type=float, default=None,
                   help="relative drift tolerance for --verify-every "
                        "(default: dtype-aware — 1e-6 f64, 2e-5 f32)")
    r.add_argument("--fault-nan-at", type=int, default=None, metavar="K",
                   help="fault injection: poison the residual with a NaN "
                        "at the first chunk boundary at/after iteration K")
    r.add_argument("--fault-bitflip-at", default=None,
                   metavar="ITER[:BUF[:BIT]]",
                   help="fault injection: flip one storage bit of buffer "
                        "BUF (w/r/p/z/Ap; default w) at the first chunk "
                        "boundary at/after ITER — finite, SILENT "
                        "corruption the NaN rail cannot see; only "
                        "--verify-every detects it (drill: --resilient "
                        "--verify-every 5 --fault-bitflip-at 100)")
    r.add_argument("--fault-preempt-after", type=int, default=None,
                   metavar="CHUNKS",
                   help="fault injection: simulate preemption (exit code "
                        "75) after this many chunks; the checkpoint "
                        "survives for the resumed run")
    r.add_argument("--fault-corrupt-checkpoint",
                   choices=("flip", "truncate", "zero"), default=None,
                   help="fault injection: damage the newest checkpoint "
                        "generation on disk before solving (exercises the "
                        "CRC fallback)")
    o = p.add_argument_group(
        "observability",
        "unified telemetry: spans, counters, streamed convergence "
        "(README 'Observability')",
    )
    o.add_argument("--trace-dir", metavar="DIR", default=None,
                   help="write telemetry here: a Perfetto-loadable "
                        "trace-rank{R}.trace.json, an events-rank{R}.jsonl "
                        "event log, metrics-rank{R}.json counters, and "
                        "(with --stream-every) the convergence curve")
    o.add_argument("--metrics-out", metavar="PATH", default=None,
                   help="write the counters/gauges snapshot to this single "
                        "JSON file at exit (restarts, checkpoint writes, "
                        "watchdog beats, iterations by verdict, ...)")
    o.add_argument("--stream-every", type=int, default=0, metavar="K",
                   help="stream (iteration, ||dw||) out of the fused loop "
                        "every K iterations — live progress + recorded "
                        "curve (XLA backends; 0 = off, the default: the "
                        "compiled program is byte-identical)")
    o.add_argument("--prom-out", metavar="PATH", default=None,
                   help="write the counters/gauges as a Prometheus text-"
                        "format snapshot to PATH at exit (the node-"
                        "exporter textfile convention; README "
                        "'Performance attribution')")
    o.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                   help="serve a live GET /metrics endpoint on "
                        "127.0.0.1:PORT for the run's lifetime (0 = OS-"
                        "assigned, reported on the export.http_port "
                        "gauge) — the scrape contract for long multi-"
                        "solve sessions")
    p.add_argument("--save-solution", metavar="PATH", default=None,
                   help="write the solution grid to PATH (.npy) — the "
                        "reference never persisted its solution")
    p.add_argument("--json", action="store_true", help="one JSON line instead of a table")
    p.add_argument("--categories", action="store_true",
                   help="reconstructed per-op timing decomposition (stage4's table)")
    p.add_argument("--profile", metavar="DIR", default=None,
                   help="capture a jax.profiler trace of one solve into DIR")
    return p


def _problem(args) -> Problem:
    return Problem(
        M=args.M, N=args.N, delta=args.delta, max_iter=args.max_iter,
        weighted_norm=not args.unweighted_norm,
    )


def _run_native(args, problem: Problem):
    from poisson_tpu.analysis import l2_error_host
    from poisson_tpu.native import build, native_solve

    build()  # one-time g++ compile stays out of the timed phases
    timer = PhaseTimer()
    with timer.phase("first_solve"):
        result = native_solve(problem, num_threads=args.threads)
    best = timer.times["first_solve"]
    for _ in range(max(0, args.repeat - 1)):
        t0 = time.perf_counter()
        result = native_solve(problem, num_threads=args.threads)
        best = min(best, time.perf_counter() - t0)
    report = solve_report(
        problem, result, best, compile_seconds=0.0, dtype="float64",
        devices=0, l2_error=l2_error_host(problem, result.w),
        backend="native",
    )
    return report, timer, result.w


def _pick_backend(args) -> str:
    import jax

    if args.backend != "auto":
        return args.backend
    if args.resilient:
        # --resilient drives the single-device xla recovery driver; auto
        # must not outsmart it onto a backend that would then reject it.
        return "xla"
    if getattr(args, "geometry", None):
        # --geometry likewise: the geometry canvases ride the
        # single-device xla solve (the pallas/sharded paths bake the
        # reference ellipse).
        return "xla"
    devices = jax.devices()
    if getattr(args, "preconditioner", "jacobi") == "mg":
        # --preconditioner mg likewise: the V-cycle rides the xla solve
        # body (poisson_tpu.mg), on one device or split over the mesh
        # (parallel.mg_sharded); the pallas kernels have no MG program
        # and reject it loudly when forced.
        if len(devices) > 1 or args.mesh is not None:
            return "sharded"
        return "xla"
    tpu = devices[0].platform == "tpu"
    # --checkpoint needs no special-casing: every JAX backend auto-pick can
    # reach (pallas, pallas-sharded, sharded, xla) has a checkpointed driver.
    if len(devices) > 1 or args.mesh is not None:
        # pallas-sharded builds its canvases on the host; an explicit
        # --setup device request keeps the XLA sharded path.
        if tpu and args.dtype != "float64" and args.setup != "device":
            return "pallas-sharded"
        if args.checkpoint and args.setup == "device" and args.mesh is None:
            # Sharded checkpointing gathers state on the host, which
            # --setup device declines; keep auto's historical behaviour
            # (the single-device xla checkpointed path) instead of making
            # a formerly-valid invocation an error. Only when sharding was
            # device-count-inferred: an explicit --mesh (like an explicit
            # --backend sharded) still gets the actionable SystemExit
            # rather than a silently ignored mesh.
            return "xla"
        return "sharded"
    if tpu and args.dtype != "float64":
        return "pallas"  # the fused paths are fp32-only
    return "xla"


def _resilience_kit(args):
    """Watchdog + fault-injection hook from the CLI flags (None, None when
    the flags are unused)."""
    watchdog = None
    if args.heartbeat or args.watchdog_timeout is not None:
        from poisson_tpu.parallel.watchdog import Watchdog

        watchdog = Watchdog(heartbeat_path=args.heartbeat,
                            timeout=args.watchdog_timeout)
    hooks = []
    if args.fault_nan_at is not None or args.fault_preempt_after is not None:
        from poisson_tpu.testing.faults import FaultPlan, chunk_hook

        hooks.append(chunk_hook(FaultPlan(
            nan_at_iteration=args.fault_nan_at,
            preempt_after_chunks=args.fault_preempt_after,
        )))
    if getattr(args, "fault_bitflip_at", None):
        from poisson_tpu.testing.faults import (
            bitflip_hook,
            parse_bitflip_spec,
        )

        it, buf, bit = parse_bitflip_spec(args.fault_bitflip_at)
        hooks.append(bitflip_hook(it, buffer=buf, bit=bit))
    if not hooks:
        on_chunk = None
    elif len(hooks) == 1:
        on_chunk = hooks[0]
    else:
        def on_chunk(state, chunks_done):
            # Chain the chunk hooks (faults compose: a NaN drill and a
            # bit-flip drill may both be armed); each sees the previous
            # hook's mutation, None means "no change" per the contract.
            changed = None
            for hook in hooks:
                new = hook(changed if changed is not None else state,
                           chunks_done)
                if new is not None:
                    changed = new
            return changed
    return watchdog, on_chunk


def _run_jax(args, problem: Problem, backend: str, watchdog=None,
             on_chunk=None, stream_every: int = 0):
    import jax

    from poisson_tpu.analysis import l2_error_host

    timer = PhaseTimer()
    mesh_shape: Optional[tuple[int, int]] = None
    devices = jax.devices()

    if backend in ("sharded", "pallas-sharded", "pallas-ca-sharded"):
        from poisson_tpu.parallel import (
            make_solver_mesh,
            pallas_cg_solve_sharded,
            pcg_solve_sharded,
        )

        if args.mesh is not None:
            n_sub = args.mesh[0] * args.mesh[1]
            mesh = make_solver_mesh(devices[:n_sub], grid=args.mesh)
        else:
            mesh = make_solver_mesh()
        mesh_shape = (mesh.shape["x"], mesh.shape["y"])
        if backend == "pallas-ca-sharded":
            if args.dtype == "float64":
                raise SystemExit(
                    "--backend pallas-ca-sharded is the fp32 fused path; "
                    "use --backend sharded for float64"
                )
            if args.setup == "device":
                raise SystemExit(
                    "--backend pallas-ca-sharded builds its canvases on "
                    "the host; use --backend sharded for --setup device"
                )
            # Validate the CA canvas geometry up front so a bad --bm exits
            # like every other flag-validation path instead of surfacing a
            # raw ValueError traceback mid-solve.
            from poisson_tpu.parallel.pallas_ca_sharded import ca_shard_spec

            try:
                ca_shard_spec(problem, mesh_shape[0], mesh_shape[1],
                              bm=args.bm)
            except ValueError as e:
                raise SystemExit(f"--backend pallas-ca-sharded: {e}")
            if args.checkpoint:
                from poisson_tpu.parallel.pallas_ca_sharded import (
                    ca_cg_solve_sharded_checkpointed,
                )

                run = lambda: ca_cg_solve_sharded_checkpointed(
                    problem, mesh, args.checkpoint, chunk=args.chunk,
                    bm=args.bm, parallel=args.parallel_grid,
                    serial=args.serial_reduce, keep_last=args.keep_last,
                )
            else:
                from poisson_tpu.parallel import ca_cg_solve_sharded

                run = lambda: ca_cg_solve_sharded(
                    problem, mesh, bm=args.bm,
                    parallel=args.parallel_grid, serial=args.serial_reduce,
                )
        elif backend == "pallas-sharded":
            if args.dtype == "float64":
                raise SystemExit(
                    "--backend pallas-sharded is the fp32 fused path; use "
                    "--backend sharded for float64"
                )
            if args.setup == "device":
                raise SystemExit(
                    "--backend pallas-sharded builds its canvases on the "
                    "host; use --backend sharded for --setup device"
                )
            serial = args.serial_reduce
            if args.checkpoint:
                from poisson_tpu.parallel import (
                    pallas_cg_solve_sharded_checkpointed,
                )

                run = lambda: pallas_cg_solve_sharded_checkpointed(
                    problem, mesh, args.checkpoint, chunk=args.chunk,
                    bm=args.bm, parallel=args.parallel_grid, serial=serial,
                    keep_last=args.keep_last,
                )
            else:
                run = lambda: pallas_cg_solve_sharded(
                    problem, mesh, bm=args.bm,
                    parallel=args.parallel_grid, serial=serial,
                )
        elif args.checkpoint:
            if args.setup == "device":
                raise SystemExit(
                    "--checkpoint gathers state on the host; use the "
                    "default --setup host"
                )
            from poisson_tpu.parallel import pcg_solve_sharded_checkpointed

            run = lambda: pcg_solve_sharded_checkpointed(
                problem, mesh, args.checkpoint, chunk=args.chunk,
                dtype=args.dtype, keep_last=args.keep_last,
                stagnation_window=args.stagnation_window or 0,
                watchdog=watchdog, on_chunk=on_chunk,
            )
        elif args.preconditioner == "mg":
            from poisson_tpu.solvers.pcg import pcg_solve

            run = lambda: pcg_solve(problem, dtype=args.dtype,
                                    preconditioner="mg", mesh=mesh)
        else:
            run = lambda: pcg_solve_sharded(
                problem, mesh, dtype=args.dtype, setup=args.setup
            )
        n_dev = mesh_shape[0] * mesh_shape[1]
    elif backend == "pallas-resident":
        if args.dtype == "float64":
            raise SystemExit(
                "--backend pallas-resident is the fp32 fused path; use "
                "--backend xla for float64"
            )
        if args.checkpoint:
            raise SystemExit(
                "--backend pallas-resident runs the whole solve in one "
                "kernel launch; there is no chunk boundary to checkpoint "
                "at — use --backend pallas (the portable format resumes "
                "across backends)"
            )
        from poisson_tpu.ops.pallas_resident import (
            fits_resident,
            resident_cg_solve,
        )

        if not fits_resident(problem):
            raise SystemExit(
                f"--backend pallas-resident: grid {problem.M}x{problem.N} "
                "exceeds the VMEM residency budget (~<=400x600); use "
                "--backend pallas or pallas-ca"
            )
        run = lambda: resident_cg_solve(problem)
        n_dev = 1
    elif backend == "pallas-ca":
        if args.dtype == "float64":
            raise SystemExit(
                "--backend pallas-ca is the fp32 fused path; use --backend "
                "xla for float64"
            )
        serial = args.serial_reduce
        if args.checkpoint:
            from poisson_tpu.ops.pallas_ca import ca_cg_solve_checkpointed

            run = lambda: ca_cg_solve_checkpointed(
                problem, args.checkpoint, chunk=args.chunk, bm=args.bm,
                parallel=args.parallel_grid, serial=serial,
                keep_last=args.keep_last,
            )
        else:
            from poisson_tpu.ops.pallas_ca import ca_cg_solve

            run = lambda: ca_cg_solve(
                problem, bm=args.bm, parallel=args.parallel_grid,
                serial=serial,
            )
        n_dev = 1
    elif backend == "pallas":
        if args.dtype == "float64":
            raise SystemExit(
                "--backend pallas is the fp32 fused path; use --backend xla "
                "for float64"
            )
        serial = args.serial_reduce
        if args.checkpoint:
            from poisson_tpu.ops.pallas_cg import pallas_cg_solve_checkpointed

            run = lambda: pallas_cg_solve_checkpointed(
                problem, args.checkpoint, chunk=args.chunk, bm=args.bm,
                parallel=args.parallel_grid, bn=args.bn, serial=serial,
                keep_last=args.keep_last,
            )
        else:
            from poisson_tpu.ops.pallas_cg import pallas_cg_solve

            run = lambda: pallas_cg_solve(
                problem, bm=args.bm, bn=args.bn,
                parallel=args.parallel_grid, serial=serial,
            )
        n_dev = 1
    elif args.resilient:
        from poisson_tpu.solvers.resilient import (
            RecoveryPolicy,
            pcg_solve_resilient,
        )

        window = (200 if args.stagnation_window is None
                  else args.stagnation_window)
        policy = RecoveryPolicy(
            max_restarts=args.max_restarts,
            escalate=args.escalate_precision,
            stagnation_window=window,
        )
        run = lambda: pcg_solve_resilient(
            problem, dtype=args.dtype, chunk=args.chunk, policy=policy,
            checkpoint_path=args.checkpoint, keep_last=args.keep_last,
            stream_every=stream_every,
            watchdog=watchdog, on_chunk=on_chunk,
            verify_every=args.verify_every, verify_tol=args.verify_tol,
            preconditioner=args.preconditioner,
        )
        n_dev = 1
    elif args.checkpoint:
        from poisson_tpu.solvers.checkpoint import pcg_solve_checkpointed

        run = lambda: pcg_solve_checkpointed(
            problem, args.checkpoint, chunk=args.chunk, dtype=args.dtype,
            keep_last=args.keep_last,
            stagnation_window=args.stagnation_window or 0,
            stream_every=stream_every,
            watchdog=watchdog, on_chunk=on_chunk,
            verify_every=args.verify_every, verify_tol=args.verify_tol,
            preconditioner=args.preconditioner,
        )
        n_dev = 1
    else:
        from poisson_tpu.solvers.pcg import pcg_solve

        geom = (_parse_geometry_arg(args.geometry)
                if getattr(args, "geometry", None) else None)
        run = lambda: pcg_solve(problem, dtype=args.dtype,
                                stream_every=stream_every, geometry=geom,
                                verify_every=args.verify_every,
                                verify_tol=args.verify_tol,
                                preconditioner=args.preconditioner)
        n_dev = 1

    from poisson_tpu import obs

    with timer.phase("compile_and_first_solve"):
        result = run()
        fence(result)
    # Recovery provenance can land on any run (an injected fault fires
    # once per hook, usually during warm-up); keep the richest record so
    # the report's recovered-line survives the timed re-runs.
    recovered = (getattr(result, "restarts", None),
                 getattr(result, "recovery_history", ()))
    warm_flag = getattr(result, "flag", None)
    failed_warmup = False
    if warm_flag is not None:
        from poisson_tpu.solvers.pcg import FLAG_CONVERGED, FLAG_NONE

        failed_warmup = int(warm_flag) not in (FLAG_NONE, FLAG_CONVERGED)
    if failed_warmup:
        # The solve stopped with a failure verdict. Re-running it for
        # timing would MASK that: a checkpointed re-run resumes from the
        # last good generation and may converge, overwriting the verdict
        # and timing only the residual iterations (inflated MLUPS).
        # Report the failed run as what it is.
        best = timer.times["compile_and_first_solve"]
    else:
        best = None
        with obs.span("timed_solves",
                      repeat=max(1, args.repeat)):
            for _ in range(max(1, args.repeat)):
                t0 = time.perf_counter()
                result = run()
                fence(result.iterations)
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
    if recovered[0] and not getattr(result, "restarts", None):
        result = result._replace(restarts=recovered[0],
                                 recovery_history=recovered[1])

    # One extra untimed solve through the shared fenced capture path
    # (obs.profile) when --profile names a dir OR POISSON_TPU_PROFILE_DIR
    # configured one — the capture lands on the span timeline too.
    from poisson_tpu.obs import profile as obs_profile

    if args.profile or obs_profile.enabled():
        with obs_profile.capture("cli.solve", profile_dir=args.profile):
            fence(run().iterations)

    from poisson_tpu.solvers.pcg import resolve_dtype

    dtype_name = (
        "float32"
        if backend in ("pallas", "pallas-ca", "pallas-resident",
                       "pallas-sharded", "pallas-ca-sharded")
        else resolve_dtype(args.dtype)
    )
    report = solve_report(
        problem, result, best,
        compile_seconds=timer.times["compile_and_first_solve"] - best,
        dtype=dtype_name, devices=n_dev, mesh=mesh_shape,
        # The analytic L2 control is the ELLIPSE oracle; a custom
        # geometry has its own manufactured-solution gate
        # (geometry.manufactured) and reports no ellipse error.
        l2_error=(None if getattr(args, "geometry", None)
                  else l2_error_host(problem, result.w)),
        backend=backend,
        device_kind=getattr(devices[0], "device_kind", None),
    )
    return report, timer, np.asarray(result.w)


def _categories_table(problem: Problem, dtype, iters: int) -> list[str]:
    """Reconstructed per-iteration op decomposition — the stage4 timer table
    (``…cu:969-980``) rebuilt by timing each op in isolation. The production
    solve fuses these; the table shows where the per-iteration work would go
    if it were staged like the reference."""
    import jax
    import jax.numpy as jnp

    from poisson_tpu.ops.stencil import apply_A, apply_Dinv, dot_weighted
    from poisson_tpu.solvers.pcg import host_setup

    a, b, rhs, aux = host_setup(problem, jnp.dtype(dtype).name, False)
    d = aux[1:-1, 1:-1]
    h1, h2 = problem.h1, problem.h2
    p = rhs

    ops = {
        "stencil (mat_A)": jax.jit(lambda u: apply_A(u, a, b, h1, h2)),
        "preconditioner (mat_D)": jax.jit(lambda u: apply_Dinv(u, d)),
        "dot products x3": jax.jit(
            lambda u: (dot_weighted(u, u, h1, h2),
                       dot_weighted(u, rhs, h1, h2),
                       dot_weighted(rhs, rhs, h1, h2))
        ),
        "axpy sweeps (w,r,p)": jax.jit(
            lambda u: (u + 0.5 * rhs, u - 0.5 * rhs, rhs + 0.5 * u)
        ),
    }
    reps = 20
    rows, total = [], 0.0
    for name, fn in ops.items():
        fence(fn(p))  # compile
        t0 = time.perf_counter()
        out = None
        for _ in range(reps):
            out = fn(p)
        fence(out)
        per_iter = (time.perf_counter() - t0) / reps
        total += per_iter
        rows.append((name, per_iter))
    lines = [f"  {'op':<24} {'s/iter':>12} {'est. total (x{} iters)'.format(iters):>24}"]
    for name, per_iter in rows:
        lines.append(f"  {name:<24} {per_iter:>12.3e} {per_iter * iters:>24.3f}")
    lines.append(f"  {'sum (unfused estimate)':<24} {total:>12.3e} {total * iters:>24.3f}")
    return lines


def build_batched_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m poisson_tpu solve-batched",
        description="Batched multi-RHS PCG: B Poisson problems in one "
                    "fused device program (solvers.batched).",
    )
    p.add_argument("M", type=int, help="grid cells in x (nodes: M+1)")
    p.add_argument("N", type=int, help="grid cells in y (nodes: N+1)")
    p.add_argument("--batch", type=int, required=True, metavar="B",
                   help="batch size: number of right-hand sides solved "
                        "per dispatch")
    p.add_argument("--bucket", type=int, default=None,
                   help="pad the batch to this executable size (default: "
                        "the power-of-two bucket ladder)")
    p.add_argument("--delta", type=float, default=1e-6,
                   help="convergence threshold on ||w(k+1)-w(k)|| (default 1e-6)")
    p.add_argument("--max-iter", type=int, default=None,
                   help="iteration cap (default (M-1)(N-1))")
    p.add_argument("--dtype", choices=("float32", "float64"), default=None,
                   help="state precision (default: float64 if x64 on, else float32)")
    p.add_argument("--vary-rhs", action="store_true",
                   help="give each member a distinct RHS magnitude "
                        "(gate 1+i/B) so members converge at different "
                        "iterations and the per-member masking is visible")
    p.add_argument("--mesh", type=_parse_mesh, default=None,
                   metavar="PXxPY",
                   help="run the whole bucket as ONE sharded dispatch "
                        "on a PXxPY device mesh (batch×mesh "
                        "composition: vmap outside shard_map — members "
                        "stay whole-grid, the mesh splits the grid, "
                        "halo traffic amortizes over the batch; "
                        "per-member counts/flags reproduce the "
                        "single-device driver; CPU gets real meshes "
                        "via XLA_FLAGS="
                        "--xla_force_host_platform_device_count)")
    p.add_argument("--geometry", metavar="SPEC", action="append",
                   default=None,
                   help="geometry-DSL JSON (inline or @file.json); "
                        "repeatable — members round-robin across the "
                        "specs and DIFFERENT geometries co-batch in the "
                        "one bucket executable (poisson_tpu.geometry)")
    p.add_argument("--verify-every", type=int, default=0, metavar="K",
                   help="per-member in-loop integrity probe "
                        "(poisson_tpu.integrity): a silently corrupted "
                        "member stops alone with an 'integrity' verdict "
                        "while its batchmates solve on; 0 (default) "
                        "keeps the historical executables byte-for-byte")
    p.add_argument("--preconditioner", choices=("jacobi", "mg"),
                   default="jacobi",
                   help="per-member M^-1: jacobi (the historical "
                        "diagonal; default) or mg — one geometric "
                        "V-cycle per iteration (poisson_tpu.mg, "
                        "near-flat iteration counts in resolution; the "
                        "grid must coarsen: even M and N). mg does not "
                        "combine with --geometry yet")
    p.add_argument("--verify-tol", type=float, default=None,
                   help="relative drift tolerance for --verify-every "
                        "(default: dtype-aware)")
    p.add_argument("--repeat", type=int, default=1,
                   help="timed batched-solve repetitions; report the best")
    p.add_argument("--compare-sequential", action="store_true",
                   help="also run the B members as sequential single-RHS "
                        "solves and report throughput speedup + per-member "
                        "iteration-count parity")
    p.add_argument("--trace-dir", metavar="DIR", default=None,
                   help="write unified telemetry here (see the main "
                        "driver's --trace-dir)")
    p.add_argument("--metrics-out", metavar="PATH", default=None,
                   help="write the counters/gauges snapshot here at exit")
    p.add_argument("--json", action="store_true",
                   help="one JSON line instead of a table")
    return p


def _main_solve_batched(argv) -> int:
    args = build_batched_parser().parse_args(argv)
    if args.batch < 1:
        raise SystemExit(f"--batch must be >= 1, got {args.batch}")
    if args.repeat < 1:
        raise SystemExit(f"--repeat must be >= 1, got {args.repeat}")
    from poisson_tpu import obs
    from poisson_tpu.utils import compile_cache

    compile_cache.enable()
    if args.trace_dir or args.metrics_out:
        obs.configure(trace_dir=args.trace_dir,
                      metrics_path=args.metrics_out)
    if args.dtype == "float64":
        import jax

        jax.config.update("jax_enable_x64", True)

    from poisson_tpu.solvers.batched import bucket_size, solve_batched
    from poisson_tpu.solvers.pcg import (
        FLAG_CONVERGED,
        FLAG_NAMES,
        pcg_solve,
        resolve_dtype,
    )

    problem = Problem(M=args.M, N=args.N, delta=args.delta,
                      max_iter=args.max_iter)
    B = args.batch
    gates = ([1.0 + i / B for i in range(B)] if args.vary_rhs
             else [1.0] * B)

    # Env-driven profiler capture (the bench.py convention): the batched
    # driver has the same contract without growing a flag per sink.
    from poisson_tpu.obs import profile as obs_profile

    obs_profile.configure_from_env()

    geometries = None
    if args.geometry:
        specs = [_parse_geometry_arg(s) for s in args.geometry]
        geometries = [specs[i % len(specs)] for i in range(B)]

    if args.verify_every < 0:
        raise SystemExit(f"--verify-every must be >= 0, "
                         f"got {args.verify_every}")
    if args.verify_tol is not None and not args.verify_every:
        raise SystemExit("--verify-tol tunes the integrity probe; pass "
                         "--verify-every K to arm it")
    if args.preconditioner == "mg":
        if geometries is not None:
            raise SystemExit(
                "--preconditioner mg does not co-batch --geometry "
                "members yet (each would need its own level hierarchy); "
                "drop one of the two")
        from poisson_tpu.mg import validate_mg_problem

        try:
            validate_mg_problem(problem)
        except ValueError as e:
            raise SystemExit(f"--preconditioner mg: {e}")
    mesh = None
    if args.mesh is not None:
        import jax

        from poisson_tpu.parallel.mesh import make_solver_mesh

        px, py = args.mesh
        devices = jax.devices()
        if px * py > len(devices):
            raise SystemExit(
                f"--mesh {px}x{py} needs {px * py} devices, found "
                f"{len(devices)} (CPU: set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={px * py})")
        mesh = make_solver_mesh(devices[: px * py], grid=(px, py))
    run = lambda: solve_batched(problem, rhs_gates=gates,
                                dtype=args.dtype, bucket=args.bucket,
                                geometries=geometries,
                                verify_every=args.verify_every,
                                verify_tol=args.verify_tol,
                                preconditioner=args.preconditioner,
                                mesh=mesh)
    timer = PhaseTimer()
    with timer.phase("compile_and_first_solve"):
        result = run()
        fence(result)
    best = None
    with obs.span("timed_batched_solves", repeat=args.repeat):
        for _ in range(args.repeat):
            t0 = time.perf_counter()
            result = run()
            fence(result.iterations)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)

    iters = [int(k) for k in np.asarray(result.iterations)]
    flags = [int(f) for f in np.asarray(result.flag)]
    converged = sum(1 for f in flags if f == FLAG_CONVERGED)
    bucket = args.bucket if args.bucket is not None else bucket_size(B)
    record = {
        "M": problem.M, "N": problem.N, "batch": B, "bucket": bucket,
        "dtype": resolve_dtype(args.dtype),
        "batch_seconds": best,
        "solves_per_sec": B / best,
        "compile_seconds": timer.times["compile_and_first_solve"] - best,
        "max_iterations": int(result.max_iterations),
        "iterations": iters,
        "converged": converged,
        "flags": sorted({FLAG_NAMES.get(f, str(f)) for f in flags}),
    }
    if args.verify_every:
        record["verify_every"] = args.verify_every
    if args.preconditioner != "jacobi":
        record["preconditioner"] = args.preconditioner
    if geometries is not None:
        record["geometry_mix"] = len(args.geometry)
        record["geometries"] = sorted({g.fingerprint for g in geometries})

    if args.compare_sequential:
        import jax

        from poisson_tpu.solvers.batched import uses_fused_kernels
        from poisson_tpu.solvers.pcg import resolve_scaled

        geos = geometries or [None] * B
        seq = lambda g, geo: pcg_solve(problem, dtype=args.dtype,
                                       rhs_gate=g, geometry=geo,
                                       preconditioner=args.preconditioner)
        if uses_fused_kernels(
                jax.devices()[0].platform, record["dtype"],
                resolve_scaled(None, record["dtype"]), mesh=mesh,
                geometries=geometries, mg=args.preconditioner == "mg",
                verify_every=args.verify_every):
            # The batch ran on the member-axis kernels: its one-RHS
            # counterpart is the fused solve on the same canvas.
            from poisson_tpu.ops.pallas_cg import batched_bm, pallas_cg_solve

            seq = lambda g, geo: pallas_cg_solve(
                problem, bm=batched_bm(problem), rhs_gate=g)
        fence(seq(gates[0], geos[0]))  # compile once outside the timing
        with obs.span("timed_sequential_solves", batch=B):
            t0 = time.perf_counter()
            seq_iters = []
            for g, geo in zip(gates, geos):
                r = seq(g, geo)
                fence(r.iterations)    # serialize: no cross-solve overlap
                seq_iters.append(int(r.iterations))
            seq_seconds = time.perf_counter() - t0
        record["sequential_seconds"] = seq_seconds
        record["speedup_vs_sequential"] = seq_seconds / best
        record["iterations_match_sequential"] = seq_iters == iters

    if obs_profile.enabled():
        with obs_profile.capture("solve_batched"):
            fence(run().iterations)

    obs.event("solve_batched.report", **record)
    obs.gauge("batched.solves_per_sec", record["solves_per_sec"])
    obs.finalize()
    if args.json:
        print(json.dumps(record))
        return 0
    lo, hi = min(iters), max(iters)
    print(f"M={problem.M}, N={problem.N} | batch={B} (bucket {bucket}) "
          f"| Time={best:.4f} s | {record['solves_per_sec']:.2f} solves/s")
    print(f"  compile: {record['compile_seconds']:.2f} s   "
          f"dtype: {record['dtype']}   iterations: "
          + (f"{lo}" if lo == hi else f"{lo}..{hi} (max {hi})")
          + f"   converged: {converged}/{B}")
    if args.compare_sequential:
        match = ("identical to sequential"
                 if record["iterations_match_sequential"]
                 else "MISMATCH vs sequential")
        print(f"  vs sequential: {record['speedup_vs_sequential']:.2f}x "
              f"({seq_seconds:.4f} s for {B} solves; per-member "
              f"iteration counts {match})")
    return 0


def build_serve_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m poisson_tpu serve",
        description="Solve-service fire drill (poisson_tpu.serve): admit "
                    "a request load, run the lifecycle loop — bounded "
                    "admission, deadlines, retry/backoff, circuit "
                    "breaking, graceful degradation — and report the "
                    "typed-outcome taxonomy with latency percentiles.",
    )
    p.add_argument("M", type=int, help="grid cells in x (nodes: M+1)")
    p.add_argument("N", type=int, help="grid cells in y (nodes: N+1)")
    p.add_argument("--requests", type=int, default=32, metavar="R",
                   help="requests to submit (default 32)")
    p.add_argument("--capacity", type=int, default=64,
                   help="admission queue bound (default 64; submit more "
                        "than this to watch typed overload shedding)")
    p.add_argument("--max-batch", type=int, default=32,
                   help="members per fused batched dispatch (default 32)")
    p.add_argument("--deadline", type=float, default=None, metavar="S",
                   help="per-request deadline in seconds (chunked "
                        "dispatch; expiry returns a partial result)")
    p.add_argument("--chunk", type=int, default=None,
                   help="iterations between deadline checks on chunked "
                        "dispatches (default 50)")
    p.add_argument("--delta", type=float, default=1e-6,
                   help="convergence threshold (default 1e-6)")
    p.add_argument("--max-iter", type=int, default=None,
                   help="iteration cap (default (M-1)(N-1))")
    p.add_argument("--dtype", choices=("float32", "float64"), default=None,
                   help="state precision (default: float64 if x64 on, "
                        "else float32)")
    p.add_argument("--vary-rhs", action="store_true",
                   help="give each request a distinct RHS magnitude")
    p.add_argument("--geometry", metavar="SPEC", action="append",
                   default=None,
                   help="geometry-DSL JSON (inline or @file.json); "
                        "repeatable — requests round-robin across the "
                        "specs, forming a mixed-geometry load whose "
                        "families co-batch per bucket executable "
                        "(fingerprints ride the flight traces)")
    p.add_argument("--preconditioner", choices=("jacobi", "mg"),
                   default="jacobi",
                   help="service-wide default M^-1 "
                        "(ServicePolicy.preconditioner): mg runs every "
                        "request with the geometric V-cycle "
                        "(poisson_tpu.mg) in its own :mg cohort family "
                        "— separate bucket executables, breakers and "
                        "sentinel baselines; the grid must coarsen "
                        "(even M and N)")
    p.add_argument("--continuous", action="store_true",
                   help="continuous-batching scheduling: a lane table "
                        "steps the fused program chunk by chunk, "
                        "retires converged lanes to their outcomes and "
                        "splices queued RHS into the freed lanes of the "
                        "same executable (default: batch-drain)")
    p.add_argument("--refill-chunk", type=int, default=25,
                   help="iterations per lane-table step in --continuous "
                        "mode (default 25)")
    p.add_argument("--forecast", action="store_true",
                   help="convergence observatory "
                        "(ServicePolicy.forecast): ETA every admission "
                        "from the per-cohort streaming model, shed "
                        "predicted-dead deadlines at submit (typed "
                        "predicted_deadline, zero compute burned), "
                        "re-forecast lane occupants at chunk "
                        "boundaries, and feed every completion back "
                        "into calibration; with --journal the model "
                        "snapshot persists beside it and --recover "
                        "warm-loads it")
    p.add_argument("--workers", type=int, default=1, metavar="W",
                   help="solve-fleet workers pulling from the shared "
                        "admission queue (serve.fleet; default 1 — the "
                        "classic single-worker service). Each worker "
                        "owns sticky bucket executables, its own "
                        "breaker cohort, and a heartbeat watchdog")
    p.add_argument("--devices", type=int, default=None, metavar="D",
                   help="bind the fleet's workers round-robin to D "
                        "device fault-domain slots (serve.placement): "
                        "sticky executables compile ON the bound "
                        "device, breaker/integrity cohorts key on it, "
                        "and a device loss quarantines the whole "
                        "domain (default: one slot on the process "
                        "default device — the pre-placement fleet). "
                        "CPU gets real topologies via XLA_FLAGS="
                        "--xla_force_host_platform_device_count")
    p.add_argument("--journal", metavar="PATH", default=None,
                   help="write-ahead request journal (serve.journal): "
                        "every lifecycle transition is CRC-sealed and "
                        "appended here, so a crashed run can be "
                        "replayed with --recover")
    p.add_argument("--recover", action="store_true",
                   help="replay --journal before serving: requests "
                        "that were queued or in flight when the "
                        "previous process died are re-enqueued "
                        "(recovered taint/backoff path) and drained to "
                        "their one typed outcome (--requests 0 runs "
                        "recovery alone)")
    p.add_argument("--verify-every", type=int, default=0, metavar="K",
                   help="always-on in-loop integrity verification for "
                        "every dispatch (ServicePolicy.integrity): "
                        "silent-data-corruption detections become typed "
                        "'integrity' retries with suspect-cohort taint; "
                        "0 (default) arms the probe only defensively, "
                        "after a first detection taints the hardware "
                        "cohort")
    p.add_argument("--verify-tol", type=float, default=None,
                   help="relative drift tolerance for the integrity "
                        "probe (default: dtype-aware)")
    p.add_argument("--seed", type=int, default=0,
                   help="backoff-jitter / load RNG seed (default 0)")
    p.add_argument("--fault-poison", type=int, default=0, metavar="K",
                   help="fault injection: mark the first K requests as "
                        "batch-killing poison (typed transient errors "
                        "after retry isolation)")
    p.add_argument("--kill-worker-at", type=float, default=None,
                   metavar="T",
                   help="fault injection: kill the next dispatching "
                        "worker once T seconds of serving have passed "
                        "(quarantine + recovery + restart, "
                        "serve.fleet.*)")
    p.add_argument("--kill-after", type=int, default=None, metavar="K",
                   help="fault injection: flush telemetry and die with "
                        "exit 75 (no cleanup) once K outcomes exist — "
                        "the crash half of the journal drill; restart "
                        "with --recover against the same --journal")
    p.add_argument("--metrics-out", metavar="PATH", default=None,
                   help="write the counters/gauges snapshot here at exit")
    p.add_argument("--prom-out", metavar="PATH", default=None,
                   help="write a Prometheus textfile snapshot here at "
                        "exit (serve.* counters included)")
    p.add_argument("--trace-dir", metavar="DIR", default=None,
                   help="write unified telemetry here — including the "
                        "flight recorder's per-request causal traces "
                        "(view one with `python -m poisson_tpu trace "
                        "REQUEST_ID --telemetry DIR`)")
    p.add_argument("--json", action="store_true",
                   help="one JSON line instead of a table")
    return p


def _main_serve(argv) -> int:
    args = build_serve_parser().parse_args(argv)
    if args.requests < (0 if args.recover else 1):
        raise SystemExit(f"--requests must be >= 1, got {args.requests} "
                         "(0 is allowed with --recover: recovery-only)")
    if args.capacity < 1:
        raise SystemExit(f"--capacity must be >= 1, got {args.capacity}")
    if args.workers < 1:
        raise SystemExit(f"--workers must be >= 1, got {args.workers}")
    if args.recover and not args.journal:
        raise SystemExit("--recover needs --journal PATH to replay")
    from poisson_tpu import obs
    from poisson_tpu.utils import compile_cache

    compile_cache.enable()
    if args.metrics_out or args.prom_out or args.trace_dir:
        obs.configure(metrics_path=args.metrics_out,
                      prom_path=args.prom_out,
                      trace_dir=args.trace_dir)
    if args.dtype == "float64":
        import jax

        jax.config.update("jax_enable_x64", True)

    import random as _random

    from poisson_tpu.serve import (
        OUTCOME_ERROR,
        OUTCOME_RESULT,
        OUTCOME_SHED,
        SCHED_CONTINUOUS,
        SCHED_DRAIN,
        FleetPolicy,
        ForecastPolicy,
        ServicePolicy,
        SolveJournal,
        SolveRequest,
        SolveService,
    )

    problem = Problem(M=args.M, N=args.N, delta=args.delta,
                      max_iter=args.max_iter)
    fault = None
    if args.fault_poison:
        from poisson_tpu.testing.faults import poison_batch_fault

        fault = poison_batch_fault(set(range(args.fault_poison)))
    worker_fault = None
    if args.kill_worker_at is not None:
        from poisson_tpu.testing.faults import kill_worker_at

        t_start = time.monotonic()
        worker_fault = kill_worker_at(
            args.kill_worker_at, lambda: time.monotonic() - t_start)
    if args.verify_every < 0:
        raise SystemExit(f"--verify-every must be >= 0, "
                         f"got {args.verify_every}")
    from poisson_tpu.integrity import IntegrityPolicy

    if args.preconditioner == "mg":
        from poisson_tpu.mg import validate_mg_problem

        try:
            validate_mg_problem(problem)
        except ValueError as e:
            raise SystemExit(f"--preconditioner mg: {e}")
    policy = ServicePolicy(
        capacity=args.capacity, max_batch=args.max_batch,
        default_chunk=args.chunk or 50,
        scheduling=(SCHED_CONTINUOUS if args.continuous
                    else SCHED_DRAIN),
        refill_chunk=args.refill_chunk,
        fleet=FleetPolicy(workers=args.workers, devices=args.devices),
        integrity=IntegrityPolicy(verify_every=args.verify_every,
                                  verify_tol=args.verify_tol),
        preconditioner=args.preconditioner,
        forecast=(ForecastPolicy() if args.forecast else None),
    )
    journal = (SolveJournal(args.journal) if args.journal else None)
    if args.recover:
        svc = SolveService.recover(journal, policy, seed=args.seed,
                                   dispatch_fault=fault,
                                   worker_fault=worker_fault)
        rec_report = svc.recovery
        print(f"serve: recovered {len(rec_report.pending)} pending "
              f"request(s) from {args.journal} "
              f"({len(rec_report.outcomes)} prior outcome(s), "
              f"{rec_report.torn_records} torn record(s) skipped)",
              file=sys.stderr)
    else:
        svc = SolveService(policy, seed=args.seed, dispatch_fault=fault,
                           worker_fault=worker_fault, journal=journal)
    geo_specs = ([_parse_geometry_arg(s) for s in args.geometry]
                 if args.geometry else None)
    rng = _random.Random(args.seed)
    t0 = time.perf_counter()
    for i in range(args.requests):
        svc.submit(SolveRequest(
            request_id=i, problem=problem,
            rhs_gate=(1.0 + rng.random() if args.vary_rhs else 1.0),
            dtype=args.dtype, deadline_seconds=args.deadline,
            chunk=args.chunk,
            geometry=(geo_specs[i % len(geo_specs)] if geo_specs
                      else None),
        ))
    if args.kill_after is not None:
        # The crash half of the journal drill: once K outcomes exist,
        # flush telemetry (the metrics snapshot is the accounting
        # evidence) and die like a preemption — exit 75, no cleanup,
        # queue and lane-resident requests abandoned. The journal is
        # what makes the abandonment recoverable.
        import os as _os

        while svc.pump():
            if len(svc.outcomes()) >= args.kill_after:
                obs.finalize()
                _os._exit(75)
    svc.drain()
    wall = time.perf_counter() - t0
    outs = svc.outcomes()
    stats = svc.stats()
    converged = sum(1 for o in outs
                    if o.kind == OUTCOME_RESULT and o.converged)
    partial = sum(1 for o in outs
                  if o.kind == OUTCOME_RESULT and o.partial)
    from poisson_tpu.obs import metrics as _metrics

    record = {
        "M": problem.M, "N": problem.N, "requests": args.requests,
        "scheduling": svc.policy.scheduling,
        "workers": args.workers,
        **({"preconditioner": args.preconditioner}
           if args.preconditioner != "jacobi" else {}),
        **({"geometry_mix": len(geo_specs),
            "geometries": sorted({g.fingerprint for g in geo_specs})}
           if geo_specs else {}),
        "wall_seconds": round(wall, 4),
        "throughput_rps": round(stats["completed"] / wall, 2) if wall
        else None,
        "completed": stats["completed"], "converged": converged,
        "partial": partial, "errors": stats["errors"],
        "shed": stats["shed"], "lost": stats["lost"],
        "recovered": stats["recovered"],
        "shed_rate": round(stats["shed_rate"], 4),
        "latency_seconds": {k: round(v, 4) for k, v in
                            stats["latency_seconds"].items()},
        "breakers": stats["breakers"],
    }
    if args.verify_every or _metrics.get("serve.integrity.detections"):
        record["integrity"] = {
            "verify_every": args.verify_every,
            "detections": _metrics.get("serve.integrity.detections"),
            "retries": _metrics.get("serve.integrity.retries"),
            "suspect_cohorts": _metrics.get(
                "serve.integrity.suspect_cohorts"),
            "errors": _metrics.get("serve.errors.integrity"),
        }
    if args.forecast:
        calib = (svc._forecast.calibration_err_pct()
                 if svc._forecast is not None else None)
        record["forecast"] = {
            "predictions": _metrics.get("obs.forecast.predictions"),
            "predicted_deadline_sheds": _metrics.get(
                "serve.shed.predicted_deadline"),
            "preempted": _metrics.get("serve.forecast.preempted"),
            "calibration_err_pct": (round(calib, 2)
                                    if calib is not None else None),
        }
    if args.workers > 1 or args.kill_worker_at is not None:
        record["fleet"] = {
            "workers": {str(k): v for k, v in stats["workers"].items()},
            "quarantines": _metrics.get("serve.fleet.quarantines"),
            "restarts": _metrics.get("serve.fleet.restarts"),
            "recovered_requests": _metrics.get(
                "serve.fleet.recovered_requests"),
        }
    # Flight-recorder attribution: the p99 is findable, not just a
    # number — its exemplar trace id names the request that paid it,
    # and the slowest requests ride with their latency decompositions.
    from poisson_tpu.serve import p99_exemplar, slowest_requests

    exemplar = p99_exemplar(outs)
    if exemplar is not None:
        record["p99_exemplar"] = exemplar
    record["slowest_requests"] = slowest_requests(outs)
    obs.event("serve.report", **record)
    obs.finalize()
    if args.json:
        print(json.dumps(record))
        return 0 if stats["lost"] == 0 else 1
    lat = record["latency_seconds"]
    print(f"serve: M={problem.M}, N={problem.N} | {args.requests} requests "
          f"in {wall:.2f} s ({record['throughput_rps']} completed/s)")
    print(f"  outcomes: {stats['completed']} results ({converged} "
          f"converged, {partial} partial) | {stats['errors']} typed "
          f"errors | {stats['shed']} shed | lost {stats['lost']}"
          + (f" | recovered {stats['recovered']}"
             if stats["recovered"] else ""))
    print(f"  latency p50/p95/p99: {lat['p50']}/{lat['p95']}/{lat['p99']} "
          f"s | shed rate {record['shed_rate']:.1%}")
    kinds = {}
    for o in outs:
        key = (o.kind if o.kind != OUTCOME_ERROR
               else f"error:{o.error_type}")
        if o.kind == OUTCOME_SHED:
            key = f"shed:{o.shed_reason}"
        kinds[key] = kinds.get(key, 0) + 1
    print("  taxonomy: " + ", ".join(f"{k}={v}"
                                     for k, v in sorted(kinds.items())))
    if exemplar is not None:
        print(f"  p99 exemplar: request {exemplar['request_id']} "
              f"(trace {exemplar['trace_id']}, "
              f"{exemplar['latency_seconds']} s)"
              + (f" — inspect with `python -m poisson_tpu trace "
                 f"{exemplar['request_id']} --telemetry "
                 f"{args.trace_dir}`" if args.trace_dir else ""))
    return 0 if stats["lost"] == 0 else 1


def build_trace_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m poisson_tpu trace",
        description="Flight-recorder viewer (obs.flight): render one "
                    "request's causal timeline — admit, queue wait, "
                    "lane residency with chunk steps, backoff/retries, "
                    "the typed outcome, and the latency decomposition — "
                    "from a telemetry directory's JSONL event log.",
    )
    p.add_argument("request_id",
                   help="request id to trace (the LAST matching trace "
                        "when ids recycled across runs)")
    p.add_argument("--telemetry", required=True, metavar="DIR",
                   help="unified-telemetry directory (--trace-dir "
                        "output; the chaos CLI's out-dir/trace)")
    p.add_argument("--trace-id", default=None,
                   help="disambiguate by exact trace id instead of "
                        "request id")
    p.add_argument("--json", action="store_true",
                   help="emit the trace's raw records as JSON lines")
    return p


def _main_trace(argv) -> int:
    args = build_trace_parser().parse_args(argv)
    import os

    from poisson_tpu.obs import flight
    from poisson_tpu.obs.trace import load_events

    if not os.path.isdir(args.telemetry):
        print(f"no telemetry directory at {args.telemetry}",
              file=sys.stderr)
        return 1
    events = load_events(args.telemetry)
    tid, records = flight.find_trace(
        events, request_id=args.request_id, trace_id=args.trace_id)
    if tid is None:
        print(f"no flight trace for "
              f"{'trace id ' + args.trace_id if args.trace_id else 'request ' + args.request_id}"
              f" in {args.telemetry}", file=sys.stderr)
        return 1
    if args.json:
        for rec in records:
            print(json.dumps(rec, default=str))
    else:
        print(flight.render_timeline(records))
    # Both modes fail on a broken tree: --json exists for automation,
    # which needs the incomplete-trace signal MORE than a human does.
    problems = flight.validate_trace(records)
    if problems:
        print("INCOMPLETE TRACE: " + "; ".join(problems),
              file=sys.stderr)
        return 1
    return 0


def build_top_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m poisson_tpu top",
        description="One-screen fleet scoreboard: queue depth and "
                    "predicted ETA backlog, active lanes, breaker "
                    "states, SLO burn, cache hit rates, placement "
                    "epoch, and forecast calibration — rendered from "
                    "a live Prometheus endpoint, a textfile export, "
                    "or a telemetry snapshot directory (the last one "
                    "works on a dead process's artifacts).",
    )
    p.add_argument("--endpoint", metavar="URL",
                   help="live Prometheus endpoint "
                        "(obs.export.start_http_server), e.g. "
                        "http://127.0.0.1:9464/metrics")
    p.add_argument("--textfile", metavar="PATH",
                   help="Prometheus textfile (POISSON_TPU_PROM / "
                        "obs.export.write_textfile)")
    p.add_argument("--metrics-dir", metavar="DIR",
                   help="telemetry directory with metrics-*.json "
                        "snapshots (obs.metrics.write_snapshot) — "
                        "post-mortem scoreboard for a dead process")
    p.add_argument("--watch", type=float, default=0.0, metavar="N",
                   help="re-render every N seconds until interrupted "
                        "(default: render once)")
    p.add_argument("--json", action="store_true",
                   help="one JSON object per render instead of the "
                        "screen (automation / tests)")
    return p


def _main_top(argv) -> int:
    args = build_top_parser().parse_args(argv)
    sources = [s for s in (args.endpoint, args.textfile,
                           args.metrics_dir) if s]
    if len(sources) != 1:
        print("top needs exactly one of --endpoint / --textfile / "
              "--metrics-dir", file=sys.stderr)
        return 2
    # Scoreboard rendering is pure stdlib over the metrics registry
    # shapes — no jax import, so `top` works on a box that only has
    # the artifacts.
    from poisson_tpu.obs import forecast as _forecast

    def read_metrics() -> dict:
        if args.endpoint:
            import urllib.request

            with urllib.request.urlopen(args.endpoint, timeout=5) as r:
                text = r.read().decode("utf-8", "replace")
            from poisson_tpu.obs import export

            return export.parse_text(text)
        if args.textfile:
            from poisson_tpu.obs import export

            with open(args.textfile, encoding="utf-8") as f:
                return export.parse_text(f.read())
        from poisson_tpu.obs import metrics

        return metrics.load_dir(args.metrics_dir)

    try:
        while True:
            try:
                board = _forecast.build_scoreboard(read_metrics())
            except (OSError, ValueError) as e:
                print(f"scoreboard source unreadable: {e}",
                      file=sys.stderr)
                return 1
            if args.json:
                print(json.dumps(board, sort_keys=True), flush=True)
            else:
                if args.watch:
                    # Home + clear-to-end: repaint in place like top(1).
                    sys.stdout.write("\x1b[H\x1b[J")
                print(_forecast.render_scoreboard(board), flush=True)
            if not args.watch:
                return 0
            time.sleep(args.watch)
    except KeyboardInterrupt:
        return 0


def build_geometry_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m poisson_tpu geometry",
        description="Geometry-spec debugger (poisson_tpu.geometry): "
                    "parse a DSL spec, print its fingerprint and "
                    "canonical form, compile its blend-coefficient "
                    "canvases, and preview the domain as ASCII "
                    "('#' inside, '+' cut faces, '.' outside).",
    )
    p.add_argument("spec", metavar="SPEC",
                   help="geometry-DSL JSON, inline or @file.json "
                        "(README \"Geometry requests\" has the grammar)")
    p.add_argument("--M", type=int, default=64,
                   help="grid cells in x for the canvas preview "
                        "(default 64)")
    p.add_argument("--N", type=int, default=64,
                   help="grid cells in y (default 64)")
    p.add_argument("--render", action="store_true",
                   help="ASCII canvas preview (default unless --json)")
    p.add_argument("--width", type=int, default=64,
                   help="render columns (default 64)")
    p.add_argument("--height", type=int, default=24,
                   help="render rows (default 24)")
    p.add_argument("--json", action="store_true",
                   help="one JSON line (fingerprint, canonical spec, "
                        "canvas stats) instead of the render")
    return p


def _main_geometry(argv) -> int:
    args = build_geometry_parser().parse_args(argv)
    import numpy as _np

    from poisson_tpu.geometry import (build_geometry_fields,
                                      cut_face_mask, render_ascii)

    spec = _parse_geometry_arg(args.spec)
    problem = Problem(M=args.M, N=args.N)
    a64, b64, rhs64 = build_geometry_fields(problem, spec)
    cut = int(cut_face_mask(a64, b64, problem.eps).sum())
    stats = {
        "fingerprint": spec.fingerprint,
        "spec": json.loads(spec.to_json()),
        "M": problem.M, "N": problem.N,
        "inside_nodes": int((rhs64 != 0).sum()),
        "inside_fraction": round(float((rhs64 != 0).mean()), 4),
        "cut_faces": cut,
        "coeff_range": [float(_np.min([a64.min(), b64.min()])),
                        float(_np.max([a64.max(), b64.max()]))],
    }
    if args.json:
        print(json.dumps(stats))
        return 0
    print(f"fingerprint: {stats['fingerprint']}")
    print(f"canonical:   {spec.to_json()}")
    print(f"grid {problem.M}x{problem.N}: "
          f"{stats['inside_nodes']} nodes inside "
          f"({stats['inside_fraction']:.1%}), {cut} cut faces")
    print(render_ascii(problem, spec, width=args.width,
                       height=args.height))
    return 0


def build_chaos_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m poisson_tpu chaos",
        description="Chaos campaign (poisson_tpu.testing.chaos): named, "
                    "seeded, deterministic fault scenarios over the "
                    "solve service and the chunked solvers, asserting "
                    "the no-lost-request invariant from the emitted "
                    "serve.* metrics snapshot. Exit 0 iff every "
                    "scenario's checks hold.",
    )
    p.add_argument("scenarios", nargs="*", metavar="SCENARIO",
                   help="scenario names to run (see --list)")
    p.add_argument("--all", action="store_true",
                   help="run every registered scenario")
    p.add_argument("--seed", type=int, default=0,
                   help="campaign seed (default 0; same seed → same "
                        "outcomes)")
    p.add_argument("--list", action="store_true",
                   help="list scenario names and exit")
    p.add_argument("--out-dir", metavar="DIR", default=None,
                   help="keep per-scenario metrics snapshots (JSON + "
                        "Prometheus text), the campaign report, and the "
                        "flight-recorder JSONL (trace/ subdir) here")
    p.add_argument("--json", action="store_true",
                   help="print the campaign report as JSON")
    return p


def _main_chaos(argv) -> int:
    args = build_chaos_parser().parse_args(argv)
    from poisson_tpu.testing import chaos

    if args.list:
        # Grouped by subsystem: the flat list outgrew readability at
        # ~20 scenarios. Names stay one-per-line (indented) so shell
        # pipelines (grep/awk) keep working on the name column.
        for group, names in chaos.scenario_groups().items():
            print(f"{group}:")
            for name in names:
                print(f"  {name}")
        return 0
    if args.all and args.scenarios:
        raise SystemExit("give scenario names or --all, not both")
    if not args.all and not args.scenarios:
        raise SystemExit("nothing to run: give scenario names or --all "
                         "(--list shows the catalogue)")
    unknown = [n for n in args.scenarios
               if n not in chaos.scenario_names()]
    if unknown:
        raise SystemExit(
            f"unknown scenario(s) {', '.join(unknown)}; known: "
            f"{', '.join(chaos.scenario_names())}"
        )
    import jax

    # The degradation ladder's precision downshift is only observable
    # when the default precision is float64 — pin the campaign's
    # numerical environment so a scenario behaves identically under
    # pytest (x64 on) and from a bare CLI.
    jax.config.update("jax_enable_x64", True)
    # Flight-recorder acceptance rail: the campaign runs with the JSONL
    # recorder on, and afterwards EVERY admitted request's causal trace
    # is validated from the emitted file — one admit root, one typed
    # outcome leaf, no orphan spans, decomposition summing to wall —
    # not from any in-process state. Incomplete traces fail the run.
    import os as _os
    import tempfile as _tempfile

    from poisson_tpu import obs
    from poisson_tpu.obs import flight as _flight
    from poisson_tpu.obs.trace import load_events as _load_events

    tmp_ctx = None
    if args.out_dir:
        flight_dir = _os.path.join(args.out_dir, "trace")
    else:
        tmp_ctx = _tempfile.TemporaryDirectory(
            prefix="poisson-chaos-flight-")
        flight_dir = tmp_ctx.name
    obs.configure(trace_dir=flight_dir)
    try:
        campaign = chaos.run_campaign(
            args.scenarios or None, seed=args.seed, out_dir=args.out_dir)
        obs.finalize()
        flight_events = _load_events(flight_dir)
    finally:
        obs.shutdown()
        if tmp_ctx is not None:
            tmp_ctx.cleanup()
    flight_report = _flight.validate_events(flight_events)
    admitted_total = sum(rep["invariant"]["admitted"]
                         for rep in campaign["scenarios"])
    flight_report["admitted"] = admitted_total
    flight_report["ok"] = (flight_report["complete"]
                           and flight_report["traces"] == admitted_total)
    campaign["flight"] = flight_report
    campaign["ok"] = campaign["ok"] and flight_report["ok"]
    if args.json:
        print(json.dumps(campaign))
        return 0 if campaign["ok"] else 1
    for rep in campaign["scenarios"]:
        mark = "ok " if rep["ok"] else "FAIL"
        inv = rep["invariant"]
        line = (f"{mark} {rep['scenario']:28s} admitted={inv['admitted']:3d}"
                f" lost={inv['lost']}")
        failed = [k for k, v in rep["checks"].items() if not v]
        if failed:
            line += "  failed: " + ", ".join(failed)
        print(line)
    fl = campaign["flight"]
    fl_mark = "ok " if fl["ok"] else "FAIL"
    fl_line = (f"{fl_mark} flight recorder: {fl['traces']} causal "
               f"trace(s) for {fl['admitted']} admitted request(s)")
    if fl["problems"]:
        fl_line += f"  incomplete: {sorted(fl['problems'])}"
    print(fl_line)
    verdict = "ok" if campaign["ok"] else "FAILED"
    print(f"chaos campaign {verdict}: {len(campaign['scenarios'])} "
          f"scenario(s), seed {campaign['seed']}")
    if args.out_dir:
        print(f"per-scenario metrics snapshots in {args.out_dir}",
              file=sys.stderr)
    return 0 if campaign["ok"] else 1


def build_session_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="poisson_tpu session",
        description="Durable solver session (serve.session): an ordered "
                    "stream of dependent solves — a moving-ellipse "
                    "Poisson schedule, or implicit-Euler heat stepping "
                    "with --heat — admitted through the service with "
                    "warm starts, full journaling, and --recover replay "
                    "to the exact step boundary.")
    p.add_argument("M", type=int, help="grid height")
    p.add_argument("N", type=int, help="grid width")
    p.add_argument("--steps", type=int, default=10, metavar="K",
                   help="total steps in the stream (default 10); with "
                        "--recover, the schedule resumes at the "
                        "journal's committed boundary and runs to the "
                        "SAME total")
    p.add_argument("--heat", action="store_true",
                   help="implicit-Euler heat stepping (A + I/dt) "
                        "instead of the moving-domain Poisson schedule")
    p.add_argument("--dt", type=float, default=0.01,
                   help="implicit-Euler time step for --heat "
                        "(mass shift m = 1/dt; default 0.01)")
    p.add_argument("--drift", type=float, default=5e-4, metavar="D",
                   help="per-step ellipse center drift of the moving-"
                        "domain schedule (default 5e-4 — inside the "
                        "warm validity bound, so warm starts hold)")
    p.add_argument("--session-id", default="cli", metavar="SID",
                   help="stream identity (default 'cli') — what the "
                        "journal and the recovery key on")
    p.add_argument("--journal", metavar="PATH", default=None,
                   help="write-ahead journal for the stream AND its "
                        "steps (serve.journal)")
    p.add_argument("--recover", action="store_true",
                   help="replay --journal first: re-open the stream at "
                        "its committed step boundary (mid-step work "
                        "re-enqueued COLD by the service's recovery) "
                        "and finish the schedule")
    p.add_argument("--kill-after", type=int, default=None, metavar="K",
                   help="fault injection: die with exit 75 (no cleanup) "
                        "mid-dispatch of step K — after its submit hit "
                        "the journal, before its outcome; restart with "
                        "--recover against the same --journal")
    p.add_argument("--seed", type=int, default=0,
                   help="service RNG seed (default 0)")
    p.add_argument("--metrics-out", metavar="PATH", default=None,
                   help="write the counters/gauges snapshot here at "
                        "exit (the merged-ledger evidence of the "
                        "kill/recover drill)")
    p.add_argument("--trace-dir", metavar="DIR", default=None,
                   help="unified telemetry incl. the session's flight "
                        "trace (one causal tree spanning the stream)")
    p.add_argument("--json", action="store_true",
                   help="one JSON line instead of a table")
    return p


def _main_session(argv) -> int:
    args = build_session_parser().parse_args(argv)
    if args.steps < 1:
        raise SystemExit(f"--steps must be >= 1, got {args.steps}")
    if args.recover and not args.journal:
        raise SystemExit("--recover needs --journal PATH to replay")
    if args.kill_after is not None and not args.journal:
        raise SystemExit("--kill-after without --journal would lose the "
                         "stream — the drill needs the journal")
    import jax

    jax.config.update("jax_enable_x64", True)
    from poisson_tpu import obs
    from poisson_tpu.utils import compile_cache

    compile_cache.enable()
    if args.metrics_out or args.trace_dir:
        obs.configure(metrics_path=args.metrics_out,
                      trace_dir=args.trace_dir)
    from poisson_tpu.geometry.dsl import Ellipse
    from poisson_tpu.serve import (
        OUTCOME_RESULT,
        SessionHost,
        SolveJournal,
        SolveService,
    )

    problem = Problem(M=args.M, N=args.N)
    m = (1.0 / args.dt) if args.heat else 0.0
    kind = "heat" if args.heat else "poisson"

    def schedule(k: int):
        """Step k's geometry — pure in the step index, so a recovery
        recomputes the schedule from the committed boundary alone."""
        if args.heat:
            return Ellipse()
        return Ellipse(cx=args.drift * k, cy=0.0, rx=1.0, ry=1.0)

    fault = None
    if args.kill_after is not None:
        import os as _os

        kill_at = args.kill_after

        def fault(requests, attempts):
            # Die mid-dispatch of step K: its session_step + submit
            # records are journaled, its outcome is not — the genuine
            # mid-step crash the recovery contract covers.
            for r in requests:
                if (r.session_step is not None
                        and r.session_step >= kill_at):
                    obs.finalize()
                    _os._exit(75)

    journal = SolveJournal(args.journal) if args.journal else None
    t0 = time.perf_counter()
    if args.recover:
        svc = SolveService.recover(journal, seed=args.seed,
                                   dispatch_fault=fault)
        host = SessionHost(svc)
        recovered = host.recover()
        sess = next((s for s in recovered
                     if s.session_id == args.session_id), None)
        if sess is None:
            print(f"session: no open stream {args.session_id!r} in "
                  f"{args.journal} — nothing to recover",
                  file=sys.stderr)
            return 1
        print(f"session: recovered {sess.session_id!r} at step "
              f"boundary {sess.advanced} (generation "
              f"{sess.generation}); continuing cold", file=sys.stderr)
    else:
        svc = SolveService(seed=args.seed, journal=journal,
                           dispatch_fault=fault)
        host = SessionHost(svc)
        sess = host.open(args.session_id, problem, kind=kind,
                         geometry=schedule(0), mass_shift=m,
                         params={"steps": args.steps,
                                 "drift": args.drift})
        if sess is None:
            print("session: open was shed", file=sys.stderr)
            return 1
    outs = []
    while sess.next_step < args.steps:
        outs.append(host.step(sess, geometry=schedule(sess.next_step)))
    summary = host.close(sess)
    obs.finalize()
    wall = time.perf_counter() - t0
    from poisson_tpu.obs import metrics as _metrics

    stats = svc.stats()
    results = sum(1 for o in outs if o.kind == OUTCOME_RESULT)
    record = {
        "M": problem.M, "N": problem.N, "kind": kind,
        "session_id": sess.session_id,
        "steps": summary["steps"], "errors": summary["errors"],
        "steps_run": len(outs), "results": results,
        "slo_good": summary["slo_good"],
        "generation": sess.generation,
        "warm_hits": _metrics.get("session.warm.hits"),
        "warm_fallbacks": _metrics.get("session.warm.fallbacks"),
        "recovered_requests": stats["recovered"],
        "lost": stats["lost"],
        "wall_seconds": round(wall, 4),
        "trace_id": summary["trace_id"],
    }
    if args.json:
        print(json.dumps(record))
    else:
        print(f"session: {kind} stream {sess.session_id!r} | "
              f"{record['steps_run']} step(s) run to "
              f"{summary['steps']} total in {wall:.2f} s")
        print(f"  warm: {record['warm_hits']} hit(s), "
              f"{record['warm_fallbacks']} fallback(s) | errors "
              f"{summary['errors']} | lost {stats['lost']} | "
              f"SLO {'good' if summary['slo_good'] else 'bad'}")
    return 0 if (stats["lost"] == 0 and summary["errors"] == 0) else 1


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "solve-batched":
        return _main_solve_batched(argv[1:])
    if argv and argv[0] == "serve":
        return _main_serve(argv[1:])
    if argv and argv[0] == "session":
        return _main_session(argv[1:])
    if argv and argv[0] == "chaos":
        return _main_chaos(argv[1:])
    if argv and argv[0] == "trace":
        return _main_trace(argv[1:])
    if argv and argv[0] == "top":
        return _main_top(argv[1:])
    if argv and argv[0] == "geometry":
        return _main_geometry(argv[1:])
    args = build_parser().parse_args(argv)
    # Reconcile the positional and flag grid forms: exactly one per axis.
    for axis in ("M", "N"):
        pos, opt = getattr(args, axis), getattr(args, f"{axis}_opt")
        if pos is not None and opt is not None:
            raise SystemExit(f"give {axis} either positionally or as "
                             f"--{axis}, not both")
        if pos is None and opt is None:
            raise SystemExit(f"missing grid size {axis} (positional or "
                             f"--{axis})")
        setattr(args, axis, pos if pos is not None else opt)
    # After parse_args so --help and argv errors stay jax-import-free.
    from poisson_tpu.utils import compile_cache

    compile_cache.enable()
    problem = _problem(args)
    bitflip_at = None
    if args.fault_bitflip_at:
        from poisson_tpu.testing.faults import parse_bitflip_spec

        try:
            bitflip_at, _, _ = parse_bitflip_spec(args.fault_bitflip_at)
        except ValueError as e:
            raise SystemExit(f"--fault-bitflip-at: {e}")
    if args.chunk is None:
        # The NaN/bit-flip drills inject at the first chunk BOUNDARY
        # at/after K; a solve that converges inside chunk one would
        # never reach it, so the default chunk shrinks to make the
        # drill actually fire. An explicit --chunk is always honored
        # (chunking never changes the iterate sequence, only where the
        # boundaries land).
        inject_ats = [k for k in (args.fault_nan_at, bitflip_at)
                      if k is not None]
        args.chunk = (min(200, max(1, min(inject_ats)))
                      if inject_ats else 200)
    elif args.chunk < 1:
        raise SystemExit(f"--chunk must be >= 1, got {args.chunk}")
    if args.verify_every < 0:
        raise SystemExit(f"--verify-every must be >= 0, "
                         f"got {args.verify_every}")
    if args.verify_tol is not None and not args.verify_every:
        raise SystemExit("--verify-tol tunes the integrity probe; pass "
                         "--verify-every K to arm it")
    if args.stream_every < 0:
        raise SystemExit(f"--stream-every must be >= 0, "
                         f"got {args.stream_every}")
    from poisson_tpu import obs

    if (args.trace_dir or args.metrics_out or args.stream_every
            or args.prom_out or args.metrics_port is not None):
        obs.configure(
            trace_dir=args.trace_dir, metrics_path=args.metrics_out,
            stream_every=args.stream_every,
            stream_live=sys.stderr.isatty() and not args.json,
            prom_path=args.prom_out, metrics_port=args.metrics_port,
        )
    # Env-driven profiler capture dir, like bench.py (an explicit
    # --profile DIR below still wins for its own capture).
    from poisson_tpu.obs import profile as _obs_profile

    _obs_profile.configure_from_env()
    if args.categories and args.json:
        raise SystemExit("--categories produces a table; drop --json")
    if args.checkpoint and args.backend == "native":
        raise SystemExit(
            "--checkpoint is supported on the JAX backends, not native"
        )
    if args.checkpoint and args.backend == "xla" and args.mesh is not None:
        raise SystemExit(
            "--backend xla --checkpoint runs single-device; drop --mesh or "
            "use --backend sharded"
        )
    resilience_flags = (
        args.resilient or args.heartbeat
        or args.watchdog_timeout is not None
        or args.stagnation_window is not None or args.keep_last != 2
        or args.fault_nan_at is not None
        or args.fault_preempt_after is not None
        or args.fault_corrupt_checkpoint is not None
        or args.fault_bitflip_at is not None
        or args.verify_every != 0
    )
    if resilience_flags and args.backend == "native":
        raise SystemExit(
            "the resilience/fault-injection flags drive the JAX chunked "
            "solvers; not available with --backend native"
        )
    if args.geometry is not None and args.backend == "native":
        raise SystemExit(
            "--geometry drives the single-device xla solve; the native "
            "C++ path bakes the reference ellipse"
        )
    if args.preconditioner == "mg" and args.backend == "native":
        raise SystemExit(
            "--preconditioner mg drives the JAX xla solve body "
            "(poisson_tpu.mg); not available with --backend native"
        )

    if args.dtype == "float64" and args.backend != "native":
        import jax

        jax.config.update("jax_enable_x64", True)

    if args.backend == "native":
        if args.stream_every:
            raise SystemExit("--stream-every streams from the fused JAX "
                             "loop; not available with --backend native")
        if args.profile:
            raise SystemExit("--profile captures a JAX device trace; "
                             "not available with --backend native")
        if args.categories:
            raise SystemExit("--categories times the JAX ops; "
                             "not available with --backend native")
        if (args.bm is not None or args.bn is not None or args.parallel_grid
                or args.serial_reduce is not None):
            raise SystemExit(
                "--bm/--bn/--parallel-grid/--serial-reduce shape the pallas "
                "kernels; not available with --backend native"
            )
        report, timer, w = _run_native(args, problem)
    else:
        backend = _pick_backend(args)
        # Geometry flags must reach a kernel, not be silently dropped.
        if args.bn is not None and backend != "pallas":
            raise SystemExit(
                f"--bn applies to the single-device pallas backend "
                f"(resolved backend: {backend})"
            )
        if args.parallel_grid and backend not in (
            "pallas", "pallas-ca", "pallas-sharded", "pallas-ca-sharded"
        ):
            raise SystemExit(
                f"--parallel-grid applies to the pallas backends "
                f"(resolved backend: {backend})"
            )
        if args.bm is not None and backend not in (
            "pallas", "pallas-ca", "pallas-sharded", "pallas-ca-sharded"
        ):
            raise SystemExit(
                f"--bm applies to the pallas backends "
                f"(resolved backend: {backend})"
            )
        if args.serial_reduce is not None:
            if backend not in ("pallas", "pallas-ca", "pallas-sharded",
                               "pallas-ca-sharded"):
                raise SystemExit(
                    f"--serial-reduce/--no-serial-reduce applies to the "
                    f"pallas backends (resolved backend: {backend})"
                )
            if args.serial_reduce and args.parallel_grid:
                raise SystemExit(
                    "--serial-reduce accumulates across sequential grid "
                    "steps; it cannot be combined with --parallel-grid"
                )
        if args.geometry is not None:
            if backend != "xla":
                raise SystemExit(
                    f"--geometry drives the single-device xla solve "
                    f"(resolved backend: {backend}); the pallas/sharded/"
                    f"native paths bake the reference ellipse"
                )
            if args.resilient or args.checkpoint:
                raise SystemExit(
                    "--geometry rides the plain xla solve; the "
                    "checkpointed/resilient CLI drivers are ellipse-only "
                    "(geometry-aware chunked dispatch lives in the solve "
                    "service: python -m poisson_tpu serve --geometry)"
                )
        if args.resilient and backend != "xla":
            raise SystemExit(
                f"--resilient drives the single-device xla solve "
                f"(resolved backend: {backend}); the sharded/pallas "
                f"chunked paths take the detection, watchdog and "
                f"checkpoint-hardening flags via --checkpoint"
            )
        if args.preconditioner == "mg":
            if backend not in ("xla", "sharded"):
                raise SystemExit(
                    f"--preconditioner mg drives the xla solve body, on "
                    f"one device or over the mesh (resolved backend: "
                    f"{backend}); the pallas kernels have no MG program "
                    f"— drop the flag or use --backend xla or sharded"
                )
            if backend == "sharded" and (args.checkpoint
                                         or args.setup == "device"):
                raise SystemExit(
                    "--preconditioner mg over a mesh builds its blocks "
                    "on the host and runs in one dispatch; drop "
                    "--checkpoint and --setup device, or use --backend "
                    "xla"
                )
            from poisson_tpu.mg import validate_mg_problem

            try:
                validate_mg_problem(problem)
                if backend == "sharded":
                    import jax

                    from poisson_tpu.parallel.mesh import choose_process_grid
                    from poisson_tpu.parallel.mg_sharded import plan_mesh

                    grid = args.mesh or choose_process_grid(
                        len(jax.devices()))
                    plan_mesh(problem, *grid)
            except ValueError as e:
                raise SystemExit(f"--preconditioner mg: {e}")
        # The chunk-boundary hooks exist on the XLA chunked drivers; a
        # resilience flag that cannot reach one must not be silently
        # dropped (the same no-silent-drop rule the geometry flags follow).
        hookable = args.resilient or (
            args.checkpoint and backend in ("xla", "sharded")
        )
        if (args.fault_nan_at is not None
                or args.fault_preempt_after is not None) and not hookable:
            raise SystemExit(
                "--fault-nan-at/--fault-preempt-after inject at chunk "
                "boundaries; use --resilient, or --checkpoint with "
                f"--backend xla or sharded (resolved backend: {backend})"
            )
        if args.fault_bitflip_at is not None and not (
                args.resilient or (args.checkpoint and backend == "xla")):
            raise SystemExit(
                "--fault-bitflip-at injects at chunk boundaries of the "
                "single-device drivers; use --resilient, or --checkpoint "
                f"with --backend xla (resolved backend: {backend})"
            )
        if args.verify_every and backend != "xla":
            raise SystemExit(
                "--verify-every arms the in-loop integrity probe in the "
                "fused XLA solvers; use --backend xla (resolved "
                f"backend: {backend})"
            )
        if (args.heartbeat or args.watchdog_timeout is not None) \
                and not hookable:
            raise SystemExit(
                "--heartbeat/--watchdog-timeout guard the chunked XLA "
                "drivers; use --resilient, or --checkpoint with "
                f"--backend xla or sharded (resolved backend: {backend})"
            )
        if args.stream_every and backend != "xla":
            raise SystemExit(
                "--stream-every streams (k, ||dw||) from the fused XLA "
                "while_loop; use --backend xla (resolved backend: "
                f"{backend})"
            )
        if args.stagnation_window is not None and not hookable:
            raise SystemExit(
                "--stagnation-window needs an in-loop-detecting driver; "
                "use --resilient, or --checkpoint with --backend xla or "
                f"sharded (resolved backend: {backend})"
            )
        if args.keep_last != 2 and not args.checkpoint:
            raise SystemExit("--keep-last shapes checkpoint retention; "
                             "it needs --checkpoint")
        if args.keep_last < 1:
            raise SystemExit(f"--keep-last must be >= 1, got {args.keep_last}")
        if args.fault_corrupt_checkpoint is not None:
            import os

            if not args.checkpoint:
                raise SystemExit(
                    "--fault-corrupt-checkpoint damages the --checkpoint "
                    "file; pass --checkpoint PATH"
                )
            if not os.path.exists(args.checkpoint):
                raise SystemExit(
                    f"--fault-corrupt-checkpoint: no checkpoint at "
                    f"{args.checkpoint} to corrupt (run once with "
                    f"--checkpoint first)"
                )
            from poisson_tpu.testing.faults import corrupt_file

            corrupt_file(args.checkpoint, args.fault_corrupt_checkpoint)
            print(f"fault injection: corrupted ({args.fault_corrupt_checkpoint}) "
                  f"checkpoint {args.checkpoint}", file=sys.stderr)
        watchdog, on_chunk = _resilience_kit(args)
        try:
            report, timer, w = _run_jax(args, problem, backend,
                                        watchdog=watchdog, on_chunk=on_chunk,
                                        stream_every=args.stream_every)
        except KeyboardInterrupt:
            # The chunked drivers convert a watchdog interrupt into
            # SolveTimeout; an interrupt that still arrives here raw (e.g.
            # mid-compile, outside a driver) gets the same treatment.
            if watchdog is not None and watchdog.fired:
                print("watchdog timeout: solve aborted (diagnostics next "
                      "to the heartbeat file)", file=sys.stderr)
                obs.finalize()
                return 124
            raise
        except Exception as e:
            from poisson_tpu.parallel.watchdog import SolveTimeout

            if isinstance(e, SolveTimeout):
                print(f"{e}", file=sys.stderr)
                obs.finalize()
                return 124
            if on_chunk is not None:
                from poisson_tpu.testing.faults import PreemptionInjected

                if isinstance(e, PreemptionInjected):
                    print(f"{e}; checkpoint retained at {args.checkpoint}"
                          if args.checkpoint else str(e), file=sys.stderr)
                    obs.finalize()
                    return 75   # EX_TEMPFAIL: rerun to resume
            raise

    if args.save_solution:
        np.save(args.save_solution, np.asarray(w, np.float64))
    # The final report is itself a telemetry event, so a trace directory
    # alone reconstructs the run (phases + counters + outcome) without
    # needing the stdout line — what the forensics renderer
    # (benchmarks/summarize_session.py --telemetry) reads.
    import dataclasses as _dc

    obs.event("solve.report", **_dc.asdict(report))
    obs.finalize()
    if args.json:
        print(report.json_line())
        return 0
    print(report.table())
    if args.backend != "native" and args.categories:
        cat_dtype = "float64" if report.dtype == "float64" else "float32"
        print("reconstructed per-op decomposition (production solve is fused):")
        print("\n".join(_categories_table(problem, cat_dtype, report.iterations)))
    if args.profile:
        print(f"profiler trace written to {args.profile}")
    if args.trace_dir:
        print(f"telemetry written to {args.trace_dir} (open the "
              f".trace.json in https://ui.perfetto.dev)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
