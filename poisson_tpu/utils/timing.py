"""Instrumentation: phase timing and the solve report.

TPU-native equivalent of stage4's manual ``MPI_Wtime`` bracketing
(``stage4-mpi+cuda/poisson_mpi_cuda_f.cu:696-701,956-980``: five accumulators
gpu/copy/comm/precond/dot, MPI_Reduce(MAX), rank-0 table; plus the
init/solver/finalize phase split in ``main``, ``…cu:1010-1034``).

Under XLA there is no per-op host bracketing — the whole solve is one fused
device program, which is the point (stage4 lost 20%+ to per-op sync, BASELINE
Table 2). What remains meaningful on the host side:

- phase wall-clock (trace/compile vs execute, init vs solve), via
  :class:`PhaseTimer` with explicit ``block_until_ready`` fencing — the
  ``MPI_Barrier``+``MPI_Wtime`` pattern of ``stage2:…cpp:483-490``;
- derived throughput (MLUPS = interior points × iterations / second — the
  BASELINE.json metric);
- for intra-program category breakdown, ``jax.profiler.trace`` captures a
  device timeline (stage4's per-category table, done by the profiler instead
  of hand-inserted timers).
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Optional

import jax

from poisson_tpu.config import Problem


def fence(tree) -> None:
    """Wait until every array in ``tree`` is computed: JAX dispatch is
    asynchronous, so a timing that ends without this measures the
    enqueue."""
    jax.block_until_ready(tree)


class PhaseTimer:
    """Named wall-clock phases with device fencing.

    Thin compatibility shim over the unified span API
    (``poisson_tpu.obs``): each phase is an ``obs`` span (fenced at exit
    — the MPI_Barrier+Wtime idiom, stage2:…cpp:483-490), so when
    telemetry is configured the phase lands on the Perfetto timeline and
    in the event log; the accumulated ``times`` dict keeps the historical
    interface either way.

    >>> t = PhaseTimer()
    >>> with t.phase("solve"):
    ...     result = pcg_solve(problem)   # doctest: +SKIP
    >>> t.times["solve"]                  # doctest: +SKIP
    """

    def __init__(self) -> None:
        self.times: dict[str, float] = {}

    def phase(self, name: str):
        timer = self

        class _Ctx:
            def __enter__(self):
                from poisson_tpu import obs

                self._span = obs.span(name)
                self._span.__enter__()
                self._t0 = time.perf_counter()
                return self

            def __exit__(self, *exc):
                # Fence outstanding device work so the phase boundary is
                # real (the MPI_Barrier+Wtime idiom, stage2:…cpp:483-490)
                # — done here, before the span closes, so both the span's
                # recorded duration and ``times`` include the fence, and
                # the fence still runs when telemetry is unconfigured.
                try:
                    jax.effects_barrier()
                except Exception:
                    pass
                self._span.__exit__(*exc)
                timer.times[name] = timer.times.get(name, 0.0) + (
                    time.perf_counter() - self._t0
                )

        return _Ctx()


def mlups(problem: Problem, iterations: int, seconds: float) -> float:
    """Million lattice-site updates per second: interior·iters/time/1e6 —
    the BASELINE.json throughput metric."""
    return problem.interior_points * iterations / seconds / 1e6


@dataclasses.dataclass
class SolveReport:
    """Stage4-style result report (``…cu:969-980`` and the rank-0 result
    line ``stage2:…cpp:493-498``), as structured data."""

    M: int
    N: int
    iterations: int
    solve_seconds: float
    compile_seconds: float
    mlups: float
    final_diff: float
    dtype: str
    devices: int
    mesh: Optional[tuple[int, int]] = None
    l2_error: Optional[float] = None
    # Termination verdict name (solvers.pcg.FLAG_NAMES) when the solver
    # stopped for a reason other than convergence; None otherwise.
    stopped: Optional[str] = None
    # Which solve path ran, and on what silicon — makes CLI records
    # joinable with bench session records (which already log both).
    backend: Optional[str] = None
    device_kind: Optional[str] = None
    # Recovery provenance (resilient solves): attempts taken and the
    # (iteration, verdict, action) history — surfaced on SUCCESS too,
    # not only inside DivergenceError.
    restarts: Optional[int] = None
    recovery: Optional[tuple] = None
    # Batched solves: batch size and the per-member iteration vector
    # (``iterations`` above then holds the scalar max the fused loop ran).
    batch: Optional[int] = None
    iterations_per_member: Optional[list] = None
    # Performance attribution (obs.costs): the backend's effective
    # bytes/iteration model, the HBM bandwidth this run achieved, and
    # the fraction of the platform ceiling that represents (None when
    # the backend has no pass model or the ceiling is unknown — an
    # honest gap, never a made-up number).
    bytes_per_iter_model: Optional[float] = None
    achieved_gbps: Optional[float] = None
    roofline_fraction: Optional[float] = None

    def json_line(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    def table(self) -> str:
        rows = [
            f"M={self.M}, N={self.N} | Iter={self.iterations} "
            + (f"(max of {self.batch} members) " if self.batch else "")
            + f"| Time={self.solve_seconds:.4f} s",
            f"  compile: {self.compile_seconds:.2f} s   dtype: {self.dtype}"
            f"   devices: {self.devices}"
            + (f"   mesh: {self.mesh[0]}x{self.mesh[1]}" if self.mesh else "")
            + (f"   backend: {self.backend}" if self.backend else "")
            + (f" [{self.device_kind}]" if self.device_kind else ""),
            f"  throughput: {self.mlups:.0f} MLUPS   final ||dw||: "
            f"{self.final_diff:.3e}"
            + (
                f"   L2 err vs analytic: {self.l2_error:.3e}"
                if self.l2_error is not None
                else ""
            ),
        ]
        if self.achieved_gbps is not None:
            rows.append(
                f"  attribution: {self.achieved_gbps:.1f} GB/s effective"
                + (
                    f" = {self.roofline_fraction:.0%} of roofline"
                    if self.roofline_fraction is not None
                    else " (no bandwidth ceiling on file for this "
                         "device; set POISSON_TPU_PEAK_GBPS)"
                )
            )
        if self.restarts:
            detail = "; ".join(
                f"iter {k}: {verdict} -> {action}"
                for k, verdict, action in (self.recovery or ())
            )
            rows.append(
                f"  recovered: {self.restarts} restart(s)"
                + (f" ({detail})" if detail else "")
            )
        if self.stopped is not None:
            rows.append(f"  WARNING: solve stopped without converging "
                        f"({self.stopped})")
        return "\n".join(rows)


def solve_report(
    problem: Problem,
    result,
    solve_seconds: float,
    compile_seconds: float,
    dtype: str,
    devices: int = 1,
    mesh: Optional[tuple[int, int]] = None,
    l2_error: Optional[float] = None,
    backend: Optional[str] = None,
    device_kind: Optional[str] = None,
) -> SolveReport:
    import numpy as np

    from poisson_tpu import obs
    from poisson_tpu.solvers.pcg import iterations_scalar

    # Batched results carry per-member vectors; the report's scalar slots
    # hold the honest wall-clock values (the fused loop's max) and the
    # per-member vector rides alongside.
    iters_arr = np.asarray(result.iterations)
    batched = iters_arr.ndim > 0
    iters = iterations_scalar(result.iterations)
    # Verdict-tracking solvers (PCGResult.flag) surface abnormal stops in
    # the report; converged/untracked results stay quiet.
    stopped = None
    flag = getattr(result, "flag", None)
    flag_name = "untracked"
    if flag is not None:
        from poisson_tpu.solvers.pcg import FLAG_CONVERGED, FLAG_NAMES, \
            FLAG_NONE

        # Vector flags: the worst member wins, by severity — failure
        # verdicts (breakdown/nonfinite/stagnated) first, then
        # done-without-verdict (FLAG_NONE, e.g. a budget-exhausted
        # member), then converged. A plain max() would rank FLAG_NONE (0)
        # below FLAG_CONVERGED (1) and report a cap-hit batch as
        # converged.
        flags = np.asarray(flag).ravel()
        failures = flags[(flags != FLAG_NONE) & (flags != FLAG_CONVERGED)]
        if failures.size:
            flag = int(failures.max())
        elif (flags == FLAG_NONE).any():
            flag = FLAG_NONE
        else:
            flag = int(flags.max()) if flags.size else FLAG_NONE
        flag_name = FLAG_NAMES.get(flag, str(flag))
        if flag == FLAG_NONE:
            # done-without-verdict (cap hit, or a verdict-less solver
            # path): count it as what the historical reading was.
            flag_name = "running"
        if flag not in (FLAG_NONE, FLAG_CONVERGED):
            stopped = FLAG_NAMES.get(flag, str(flag))
    # Solve-level counters: solves and iterations by stop verdict, plus
    # compile vs execute seconds (accumulating float counters).
    obs.inc(f"pcg.solves.{flag_name}")
    obs.inc(f"pcg.iterations.{flag_name}", iters)
    obs.inc("time.compile_seconds", max(0.0, compile_seconds))
    obs.inc("time.execute_seconds", max(0.0, solve_seconds))
    restarts = getattr(result, "restarts", None)
    recovery = getattr(result, "recovery_history", None)
    # Roofline attribution (obs.costs): achieved bandwidth against the
    # backend's pass model and the platform ceiling. Advisory — any
    # failure (exotic dtype name, no pass model for this backend) leaves
    # the fields None rather than touching the report's core job.
    useful_iters = int(iters_arr.sum()) if batched else iters
    bytes_per_iter = achieved_gbps = fraction = None
    try:
        from poisson_tpu.obs.costs import roofline_summary

        rl = roofline_summary(
            problem, backend, np.dtype(dtype).itemsize, useful_iters,
            solve_seconds, device_kind=device_kind, devices=max(1, devices),
        )
        bytes_per_iter = rl["bytes_per_iter_model"]
        achieved_gbps = rl["achieved_gbps"]
        fraction = rl["fraction"]
    except Exception:
        pass
    return SolveReport(
        M=problem.M,
        N=problem.N,
        iterations=iters,
        solve_seconds=solve_seconds,
        compile_seconds=compile_seconds,
        # Batched: throughput counts every member's useful updates
        # (Σ member iterations, same numerator the roofline attribution
        # above uses), not just the slowest member's — a B=64 batch's
        # MLUPS must be comparable with B=64 sequential reports, not
        # ~64× under them.
        mlups=mlups(problem, useful_iters, solve_seconds),
        final_diff=float(np.max(np.asarray(result.diff))),
        batch=(int(iters_arr.shape[0]) if batched else None),
        iterations_per_member=(
            [int(k) for k in iters_arr] if batched else None
        ),
        dtype=dtype,
        devices=devices,
        mesh=mesh,
        l2_error=l2_error,
        stopped=stopped,
        backend=backend,
        device_kind=device_kind,
        restarts=(int(restarts) if restarts else None),
        recovery=(tuple(recovery) if restarts and recovery else None),
        bytes_per_iter_model=bytes_per_iter,
        achieved_gbps=achieved_gbps,
        roofline_fraction=fraction,
    )
