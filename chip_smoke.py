"""Chip smoke: drive the main path once on a TPU and check what comes out.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded solve on a 2x2 mesh

One chip runs, in this one process, through the entry points users call:

- the flagship 800x1200 solve and the largest published grid, 2400x3200,
  on the backend ``cli._pick_backend`` picks (the fused Pallas kernel),
  each checked against the golden iteration count, the fp64 native
  oracle's iterate and L2 error, the XLA fp32 solve on the same chip, and
  for a Mosaic kernel in the compiled program;
- ``solve_batched`` at 400x600 with 8 distinct RHS gates against the
  sequential solve of each member;
- 8 requests through ``serve.SolveService``.

``--chips 4`` runs only the sharded solves (``pallas_cg_solve_sharded``
and ``pcg_solve_sharded`` on a 2x2 mesh) against the single-chip fused
solve at 2400x3200, and shows the per-device memory.

Every phase prints one line; times on those lines are smoke timings, not
benchmark numbers. The last line of stdout is the JSON result, printed
only when every phase passed. A non-TPU platform exits non-zero: there is
no fallback. Each phase is a function of its grid so the tests can run it
at a tiny size on the CPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

SINGLE_GRID = (800, 1200)
LARGEST_GRID = (2400, 3200)
BATCH_GRID = (400, 600)
BATCH = 8
# Agreement bounds at the published grids. An fp32 iterate differs from
# the fp64 oracle's by O(1e-5) there (CPU, 800x1200: fused 1.2e-5, XLA
# 3.2e-5, and 2.1e-5 between the two), so the 40x40 test tolerances
# (1e-6, 2e-5) do not carry over; 1e-4 is 0.1% of max|u| = 0.1, far under
# what a wrong kernel produces. The L2 error (a discretisation floor of
# ~2e-4) moves with those iterate errors: fused 2.3%, XLA 7.4% off the
# oracle's at 800x1200 on the CPU.
FP32_ATOL = 1e-4
L2_RTOL = 0.1
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(AssertionError):
    """A phase's result disagrees with its reference."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rhs_gates(batch: int = BATCH) -> list[float]:
    """Distinct RHS multipliers: each member converges in its own count."""
    return [float(g) for g in np.geomspace(0.25, 4.0, batch)]


def _check_golden(name: str, problem, iterations: int) -> None:
    from poisson_tpu.config import GOLDEN_ITERS, golden_tolerance

    golden = GOLDEN_ITERS[(problem.M, problem.N)]
    check(abs(iterations - golden) <= golden_tolerance(golden),
          f"{name}: {iterations} iterations, golden {golden} "
          f"± {golden_tolerance(golden)}")


def _has_kernel(problem) -> bool:
    """True iff the fused solve's compiled program holds a Mosaic kernel
    (``tpu_custom_call``), i.e. it did not run in interpret mode."""
    import jax

    from poisson_tpu.ops import pallas_cg

    cv, cs, cw, g, rhs, sc2, _ = pallas_cg.build_canvases(problem)
    interpret = jax.devices()[0].platform != "tpu"
    serial = pallas_cg._resolve_serial(None, False)
    compiled = pallas_cg._fused_solve.lower(
        problem, cv, interpret, False, serial, cs, cw, g, rhs, sc2
    ).compile()
    return "tpu_custom_call" in compiled.as_text()


def _max_dw(a, b) -> float:
    return float(np.abs(np.asarray(a, np.float64)
                        - np.asarray(b, np.float64)).max())


def phase_solve(M: int, N: int) -> dict:
    """The fused solve (the backend ``cli._pick_backend`` picks on one
    TPU) vs the golden count and the fp64 native oracle's iterate and L2
    error against the analytic solution, and vs the XLA fp32 solve."""
    import jax.numpy as jnp

    from poisson_tpu.analysis import l2_error_host
    from poisson_tpu.config import Problem
    from poisson_tpu.native import native_solve
    from poisson_tpu.ops.pallas_cg import pallas_cg_solve
    from poisson_tpu.solvers.pcg import pcg_solve

    problem = Problem(M=M, N=N)
    fused = pallas_cg_solve(problem)
    iterations = int(fused.iterations)
    _check_golden("fused", problem, iterations)
    w = np.asarray(fused.w, np.float64)
    check(bool(np.isfinite(w).all()), "fused: non-finite iterate")
    oracle = native_solve(problem)
    dw_oracle = _max_dw(w, oracle.w)
    check(dw_oracle <= FP32_ATOL,
          f"fused vs fp64 oracle: max|dw| {dw_oracle!r} > {FP32_ATOL}")
    l2, l2_oracle = l2_error_host(problem, w), l2_error_host(problem, oracle.w)
    check(abs(l2 - l2_oracle) <= L2_RTOL * l2_oracle,
          f"fused L2 {l2!r} vs fp64 oracle L2 {l2_oracle!r}")
    xla = pcg_solve(problem, dtype=jnp.float32)
    dw_xla = _max_dw(w, xla.w)
    check(dw_xla <= FP32_ATOL,
          f"fused vs xla fp32: max|dw| {dw_xla!r} > {FP32_ATOL}")
    return {"iterations": iterations, "xla_iterations": int(xla.iterations),
            "oracle_iterations": int(oracle.iterations), "l2": l2,
            "l2_oracle": l2_oracle, "max_abs_dw_vs_oracle": dw_oracle,
            "max_abs_dw_vs_xla": dw_xla,
            "tpu_custom_call": _has_kernel(problem)}


def phase_batched(M: int, N: int, gates: list[float]) -> dict:
    """``solve_batched`` members vs the sequential solve of each."""
    import jax.numpy as jnp

    from poisson_tpu.config import Problem
    from poisson_tpu.solvers.batched import solve_batched
    from poisson_tpu.solvers.pcg import pcg_solve

    problem = Problem(M=M, N=N)
    batched = [int(k) for k in
               solve_batched(problem, rhs_gates=gates,
                             dtype=jnp.float32).iterations]
    sequential = [int(pcg_solve(problem, dtype=jnp.float32,
                                rhs_gate=g).iterations) for g in gates]
    matched = sum(b == s for b, s in zip(batched, sequential))
    check(matched == len(gates),
          f"batched {batched} vs sequential {sequential}")
    return {"members": len(gates), "matched": matched,
            "iterations": sequential}


def phase_service(M: int, N: int, gates: list[float],
                  expected: list[int]) -> dict:
    """``len(gates)`` requests through ``SolveService``; each must end ok
    with the sequential solve's iteration count."""
    from poisson_tpu.config import Problem
    from poisson_tpu.serve import ServicePolicy, SolveRequest, SolveService
    from poisson_tpu.serve import router

    problem = Problem(M=M, N=N)
    service = SolveService(ServicePolicy())
    for i, g in enumerate(gates):
        shed = service.submit(SolveRequest(request_id=i, problem=problem,
                                           rhs_gate=g, dtype="float32"))
        check(shed is None, f"request {i} shed at admission: {shed}")
    outcomes = {o.request_id: o for o in service.drain()}
    ok = sum(outcomes[i].ok and outcomes[i].iterations == expected[i]
             for i in range(len(gates)))
    check(ok == len(gates), "service outcomes: " + ", ".join(
        f"{i}:{o.kind}/{o.flag}/{o.iterations}" for i, o in
        sorted(outcomes.items())) + f" expected iterations {expected}")
    arms = (router.BACKEND_XLA, router.BACKEND_CA, router.BACKEND_RESIDENT)
    return {"requests": len(gates), "ok": ok, "executor_backends": sorted(
        {router.executor_backend(arm) for arm in arms})}


def _device_memory(devices) -> list[dict]:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append({"id": d.id, "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return out


def phase_sharded(M: int, N: int, devices) -> dict:
    """Both sharded solves on a 2x2 mesh of ``devices`` vs the fused solve
    on one of them."""
    import jax.numpy as jnp

    from poisson_tpu.config import Problem
    from poisson_tpu.ops.pallas_cg import pallas_cg_solve
    from poisson_tpu.parallel import (
        make_solver_mesh,
        pallas_cg_solve_sharded,
        pcg_solve_sharded,
    )

    problem = Problem(M=M, N=N)
    single = pallas_cg_solve(problem)
    out = {"single_iterations": int(single.iterations)}
    _check_golden("single-chip fused", problem, out["single_iterations"])
    w_single = np.asarray(single.w, np.float64)
    del single
    mesh = make_solver_mesh(devices, grid=(2, 2))
    for name, solve in (
        ("pallas_sharded", lambda: pallas_cg_solve_sharded(problem, mesh)),
        ("xla_sharded",
         lambda: pcg_solve_sharded(problem, mesh, dtype=jnp.float32)),
    ):
        result = solve()
        iterations = int(result.iterations)
        _check_golden(name, problem, iterations)
        dw = _max_dw(result.w, w_single)
        check(dw <= FP32_ATOL,
              f"{name} vs single-chip: max|dw| {dw!r} > {FP32_ATOL}")
        out[name] = {"iterations": iterations, "max_abs_dw": dw,
                     "memory": _device_memory(devices)}
    return out


def _spread(memory: list[dict], min_bytes: int) -> bool:
    """Every device peaked at ``min_bytes`` or more: the shards were
    placed on all of them, not gathered on device 0."""
    return all((m["peak_bytes_in_use"] or 0) >= min_bytes for m in memory)


class _Phases:
    """Runs named phases, printing one line each with its smoke timings
    (wall, and the backend-compile seconds JAX reports inside it)."""

    def __init__(self):
        from jax import monitoring

        self.compile_s = 0.0
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == _COMPILE_EVENT:
            self.compile_s += duration

    def run(self, name: str, fn, *args, require=None) -> dict:
        """``fn(*args)``, then ``require(result)``: the checks that hold
        only on the chip (a phase function also runs on the CPU)."""
        compile0, t0 = self.compile_s, time.perf_counter()
        result = fn(*args)
        result["smoke_wall_s"] = time.perf_counter() - t0
        result["smoke_compile_s"] = self.compile_s - compile0
        if require is not None:
            require(result)
        print(f"phase {name}: PASS {json.dumps(result)}", flush=True)
        return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="1: the single-chip phases; 4: the sharded "
                             "solve on a 2x2 mesh and its reference only")
    args = parser.parse_args(argv)

    import jax

    from poisson_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    devices = jax.devices()
    d0 = devices[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)} compile_cache={cache_dir}", flush=True)
    for d in devices:
        print(f"device {d.id}: memory_stats={d.memory_stats()}", flush=True)
    if d0.platform != "tpu":
        print(f"chip_smoke: no TPU (platform {d0.platform!r}); refusing to "
              "run on anything else", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    phases = _Phases()
    if args.chips == 4:
        # Half of one fp32 field's shard: far below what each device holds
        # when the canvases are spread, far above what an idle device holds.
        min_bytes = LARGEST_GRID[0] * LARGEST_GRID[1] * 4 // 4 // 2

        def spread(out):
            for name in ("pallas_sharded", "xla_sharded"):
                check(_spread(out[name]["memory"], min_bytes),
                      f"{name}: a device peaked under {min_bytes} bytes: "
                      f"{out[name]['memory']}")

        phases.run("sharded_2x2", phase_sharded, *LARGEST_GRID,
                   devices[:4], require=spread)
    else:
        from poisson_tpu import cli

        picked = cli._pick_backend(
            cli.build_parser().parse_args([str(n) for n in SINGLE_GRID]))
        check(picked == "pallas",
              f"cli._pick_backend chose {picked!r} on one TPU, not 'pallas'")
        def kernel(out):
            check(out["tpu_custom_call"], "fused solve compiled without a "
                  "tpu_custom_call (interpret mode?)")

        for grid in (SINGLE_GRID, LARGEST_GRID):
            phases.run("solve_{}x{}".format(*grid), phase_solve, *grid,
                       require=kernel)
        gates = rhs_gates()
        batched = phases.run("batched_400x600_b8", phase_batched,
                             *BATCH_GRID, gates)
        service = phases.run("service_400x600_r8", phase_service,
                             *BATCH_GRID, gates, batched["iterations"])
        print("service: its router arms execute on "
              f"{service['executor_backends']} (serve.router."
              "executor_backend)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
