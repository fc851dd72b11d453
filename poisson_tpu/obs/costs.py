"""Performance attribution: what a solve *should* cost, and what it does.

The third observability pillar, after spans (where time went) and
counters (what happened): a closed-form cost model of the 5-point-stencil
PCG iteration checked against XLA's own accounting of the compiled
program, plus a roofline attribution of measured throughput — the
Williams/Waterman/Patterson methodology (PAPERS.md) applied to real
compiled executables instead of paper napkins.

Three layers, deliberately kept distinct because they answer different
questions:

- **HLO operand traffic** (:func:`measured_iteration_cost`) — what
  ``lowered.compile().cost_analysis()`` counts for ONE compiled PCG
  iteration body: every fused kernel's operand+result bytes and FLOPs.
  This is the compiler's truth about the program it built. Counts each
  *use* (the five shifted stencil reads of ``p`` are five operands), so
  it over-states DRAM traffic where tiles stay cache-resident — which is
  exactly why it pairs with the analytic model rather than the roofline.
- **the analytic stencil model** (:func:`analytic_iteration_cost`) — the
  same quantity derived by hand from the iteration's dataflow as a
  closed form in grid shape and dtype. Measured-vs-model agreement
  within ±25% (pinned by ``tests/test_perf_obs.py``) is the invariant:
  drift means either the solver's per-iteration work changed or the
  compiler started building a different program — both worth an alarm
  before any wall-clock regression shows up.
- **roofline attribution** (:func:`roofline_summary`) — *effective* HBM
  traffic per iteration (each backend's canvas-pass model, the numbers
  ``benchmarks/roofline.py`` and BENCH.md's sanity rule already use)
  times measured iterations over measured seconds, as a fraction of the
  platform's bandwidth ceiling. This is the "how fast *should* this
  be" number that bench records and SolveReports now carry.

Everything here degrades to None-valued fields rather than raising:
cost introspection is advisory, and a backend whose runtime does not
implement ``cost_analysis`` (some PJRT plugins) must not take the solve
or the bench down with it.
"""

from __future__ import annotations

import os
from typing import Optional

from poisson_tpu.obs import metrics

# -- the analytic model -------------------------------------------------
#
# Units: one "pass" = (M+1)·(N+1)·dtype_bytes — one full-grid array read
# or written once. The tallies below count HLO operand+result traffic of
# the fused loop body the way XLA's cost analysis does (each operand use
# counts, including the five shifted stencil slices of p and the
# while-loop keep/candidate selects that fusion cannot eliminate), so
# the model and cost_analysis() measure the same quantity. The per-term
# integers are exact dataflow counts; the trailing ``loop_overhead``
# term absorbs what XLA's fusion keeps of the state-select/copy traffic
# and is calibrated once against jax 0.4.37 HLO (the ±25% agreement
# test in tests/test_perf_obs.py pins it against drift).
#
# Scaled body (the production fp32 path: Ã = D^-1/2 A D^-1/2, z ≡ r):
_SCALED_BYTES_TERMS = {
    # Ap = sc·A(sc·p): p as five shifted slices, a and b twice each,
    # sc twice (pre- and post-multiply), one result write.
    "stencil_apply": 5 + 2 + 2 + 2 + 1,
    "denominator_dot": 2,           # (Ap, p)
    # w' = w + αp, r' = r − αAp fused with the ‖Δw‖ and ζ reductions:
    # reads p, w, r, Ap, sc; writes w', r'.
    "state_update": 5 + 2,
    "z_propagation": 2,             # z' = r' through the keep-select
    "p_update": 3,                  # p' = r' + βp
    "loop_overhead": 7,             # keep/candidate selects XLA retains
}
# Unscaled body (fp64 oracle parity: explicit Jacobi apply_Dinv with its
# division and D==0 guards, which XLA fuses less aggressively):
_UNSCALED_BYTES_TERMS = {
    "stencil_apply": 5 + 2 + 2 + 1,     # no sc multiplies
    "denominator_dot": 2,
    "state_update": 4 + 2,              # reads p, w, r, Ap; writes w', r'
    "preconditioner": 4,                # z' = D⁻¹r': reads r', d twice; writes z'
    "zeta_dot": 2,                      # (z', r')
    "p_update": 3,
    "loop_overhead": 20,                # guarded division breaks fusion:
    # z and the where-masks materialize instead of staying in-register
}
# FLOPs per grid point, same convention (XLA counts compares/selects):
_SCALED_FLOPS_PER_POINT = 34.0
_UNSCALED_FLOPS_PER_POINT = 54.0


def grid_points(M: int, N: int) -> int:
    """Full-grid points (M+1)·(N+1) — the array footprint every pass
    model is quoted against."""
    return (M + 1) * (N + 1)


def analytic_iteration_cost(M: int, N: int, dtype_bytes: int = 4,
                            scaled: bool = True) -> dict:
    """Closed-form bytes and FLOPs of ONE PCG iteration on an (M, N)
    grid — the 5-point-stencil model described in the module docstring.

    Returns ``{"flops", "bytes", "passes", "flops_per_point", "terms"}``;
    ``terms`` is the per-term pass tally so a drifted agreement check can
    say *which* part of the model went stale.
    """
    terms = dict(_SCALED_BYTES_TERMS if scaled else _UNSCALED_BYTES_TERMS)
    passes = float(sum(terms.values()))
    fpp = _SCALED_FLOPS_PER_POINT if scaled else _UNSCALED_FLOPS_PER_POINT
    pts = grid_points(M, N)
    return {
        "flops": fpp * pts,
        "bytes": passes * pts * dtype_bytes,
        "passes": passes,
        "flops_per_point": fpp,
        "terms": terms,
    }


def apportion_compute(span_seconds: float,
                      member_iterations: dict) -> dict:
    """Split one shared dispatch span's measured wall across its members
    by iteration count — the flight recorder's compute attribution.

    A fused batched dispatch (or lane chunk step) advances every member
    inside ONE measured span; the per-iteration cost of the program is
    the same for every lane (identical vmapped body — the quantity the
    analytic model above prices), so a member's share of the span is
    ``span_seconds × own_iterations / Σ iterations``. Members that
    advanced zero iterations (frozen, done, evicted) get 0.0 — their
    residency is lane-wait, not compute. The shares sum to
    ``span_seconds`` exactly (up to float rounding), which is what lets
    a request's latency decomposition sum to its measured wall.
    """
    total = sum(max(0, int(k)) for k in member_iterations.values())
    if total <= 0:
        return {mid: 0.0 for mid in member_iterations}
    return {mid: span_seconds * max(0, int(k)) / total
            for mid, k in member_iterations.items()}


def mg_vcycle_cost(M: int, N: int, dtype_bytes: int = 4,
                   config=None, scaled: bool = True) -> dict:
    """Analytic HLO-operand traffic of ONE geometric V-cycle
    (:mod:`poisson_tpu.mg`) — the per-iteration surcharge an
    MG-preconditioned CG iteration pays over the Jacobi body, in the
    same operand-pass units as :func:`analytic_iteration_cost`.

    Per non-coarsest level (area 4^-l of the fine grid): the
    first pre-smoothing sweep from zero is the closed form ω·D⁻¹r
    (3 passes: read r, dinv, write x); every further damped-Jacobi
    sweep is one stencil application (10: five shifted reads of x, a
    and b twice each, one write) plus the fused update (5: read x, r,
    dinv, Ax; write x) = 15; the residual costs 12 (stencil + fused
    subtract); restriction 1.25 (read fine, write quarter-size
    coarse); prolongation+correction 2.25. The coarsest level is
    either the dense-inverse matvec — n² matrix reads, the constant
    term that dominates small grids and vanishes relative to fine work
    at scale — or ``coarse_sweeps`` smoother sweeps. Scaled solves add
    the √d congruence wrap (4 fine passes).

    Returns ``{"bytes", "flops", "passes_fine_equivalent", "levels",
    "coarse_dense", "terms"}`` and sets the ``cost.mg.*`` gauges.
    ``passes_fine_equivalent`` is total bytes over one fine-grid array
    pass — the number roofline attribution adds to the CG body's pass
    model so MG records cohort separately.
    """
    from poisson_tpu.mg.hierarchy import DEFAULT_MG, plan_levels

    cfg = config or DEFAULT_MG
    dims = plan_levels(M, N, cfg)
    pts0 = grid_points(M, N)
    sweep, first_sweep, residual, restrict, prolong = 15.0, 3.0, 12.0, 1.25, 2.25
    per_level = (first_sweep + (cfg.pre_smooth - 1) * sweep
                 if cfg.pre_smooth > 0 else 0.0)
    per_level += residual + restrict + prolong + cfg.post_smooth * sweep
    bytes_total = 0.0
    flops_total = 0.0
    for lvl, (m, n) in enumerate(dims[:-1]):
        pts = grid_points(m, n)
        bytes_total += per_level * pts * dtype_bytes
        sweeps = cfg.pre_smooth + cfg.post_smooth
        flops_total += (13.0 * sweeps + 12.0) * pts
    mc, nc = dims[-1]
    n_int = (mc - 1) * (nc - 1)
    coarse_dense = n_int <= cfg.coarse_dense_limit
    if coarse_dense:
        bytes_total += float(n_int) * n_int * dtype_bytes
        flops_total += 2.0 * n_int * n_int
    else:
        pts = grid_points(mc, nc)
        bytes_total += cfg.coarse_sweeps * sweep * pts * dtype_bytes
        flops_total += 13.0 * cfg.coarse_sweeps * pts
    if scaled:
        bytes_total += 4.0 * pts0 * dtype_bytes   # √d congruence wrap
    report = {
        "bytes": bytes_total,
        "flops": flops_total,
        "passes_fine_equivalent": bytes_total / (pts0 * dtype_bytes),
        "levels": len(dims),
        "coarse_dense": coarse_dense,
        "terms": {
            "per_level_passes": per_level,
            "coarsest": f"{mc}x{nc}",
            "coarse_dense_bytes": (float(n_int) * n_int * dtype_bytes
                                   if coarse_dense else 0.0),
        },
    }
    metrics.gauge("cost.mg.bytes_per_cycle", bytes_total)
    metrics.gauge("cost.mg.flops_per_cycle", flops_total)
    metrics.gauge("cost.mg.passes", report["passes_fine_equivalent"])
    return report


def krylov_block_cost(M: int, N: int, B: int, dtype_bytes: int = 4,
                      scaled: bool = True) -> dict:
    """Analytic HLO-operand traffic of ONE block-CG iteration over B
    right-hand sides (:mod:`poisson_tpu.krylov.block`), in the same
    operand-pass units as :func:`analytic_iteration_cost` — the model
    that lets the sentinel and roofline attribution cohort ``:blk``
    records separately from the independent-mode family.

    Per iteration the block pays B member-iterations of stencil/update
    traffic (the vmapped body's per-member passes, with the coefficient
    canvases read ONCE per stencil application and amortized across
    the B members — the hardware-batching win the independent mode
    already has) plus the block coupling: three B×B Gram matrices
    (PᵀQ, PᵀR, QᵀZ — 2 array passes each over B stacks = 6·B passes),
    two (n × B)·(B × B) recombinations (X/R updates and the direction
    update, 2 passes each = 6·B counting the orthonormalization's
    P-recombination), and the B×B eigendecompositions (O(B³) FLOPs,
    byte-negligible). Fewer block iterations buying more bytes per
    iteration is exactly the trade the sentinel must see cohorted, not
    averaged away.

    Returns ``{"bytes", "flops", "bytes_per_member_iteration",
    "passes_per_member", "coupling_passes"}`` and sets the
    ``cost.krylov.block_*`` gauges.
    """
    base = analytic_iteration_cost(M, N, dtype_bytes, scaled)
    pts = grid_points(M, N)
    # Coupling traffic per block iteration, in fine-grid passes: 6B for
    # the three Gram products + 6B for the three stack recombinations.
    coupling_passes = 12.0 * B
    bytes_total = B * base["bytes"] + coupling_passes * pts * dtype_bytes
    flops = (B * base["flops"]
             + 3.0 * (2.0 * B * B) * pts       # Gram products
             + 3.0 * (2.0 * B * B) * pts       # recombinations
             + 30.0 * B ** 3)                  # B×B eigh/solves
    report = {
        "bytes": bytes_total,
        "flops": flops,
        "bytes_per_member_iteration": bytes_total / B,
        "passes_per_member": bytes_total / (B * pts * dtype_bytes),
        "coupling_passes": coupling_passes,
    }
    metrics.gauge("cost.krylov.block_bytes_per_iter", bytes_total)
    metrics.gauge("cost.krylov.block_flops_per_iter", flops)
    metrics.gauge("cost.krylov.block_passes_per_member",
                  report["passes_per_member"])
    return report


def krylov_deflated_cost(M: int, N: int, k: int, dtype_bytes: int = 4,
                         scaled: bool = True) -> dict:
    """Analytic HLO-operand traffic of ONE deflated-CG iteration with a
    k-vector basis (:mod:`poisson_tpu.krylov.recycle`) — the
    per-iteration surcharge the warm path pays over the plain body,
    in the same operand-pass units as :func:`analytic_iteration_cost`.

    The deflation projector costs two k-stack passes per iteration
    (read AW for the k weighted dots, read W for the correction; the
    k-vector coefficient solve is byte-negligible), so the warm
    iteration moves ``base + 2k`` passes — fewer iterations buying
    more bytes per iteration, the trade the sentinel must see
    cohorted. Returns ``{"bytes", "flops", "passes", "basis_passes"}``
    and sets the ``cost.krylov.deflated_*`` gauges.
    """
    base = analytic_iteration_cost(M, N, dtype_bytes, scaled)
    pts = grid_points(M, N)
    basis_passes = 2.0 * k
    bytes_total = base["bytes"] + basis_passes * pts * dtype_bytes
    flops = base["flops"] + 2.0 * (2.0 * k) * pts + 2.0 * k * k
    report = {
        "bytes": bytes_total,
        "flops": flops,
        "passes": bytes_total / (pts * dtype_bytes),
        "basis_passes": basis_passes,
    }
    metrics.gauge("cost.krylov.deflated_bytes_per_iter", bytes_total)
    metrics.gauge("cost.krylov.deflated_flops_per_iter", flops)
    metrics.gauge("cost.krylov.deflated_passes", report["passes"])
    return report


# -- compiled-executable introspection ----------------------------------


def program_costs(compiled) -> dict:
    """``{"flops", "bytes_accessed"}`` from a compiled executable's
    ``cost_analysis()`` (None values when the runtime does not implement
    it — cost introspection is advisory, never fatal)."""
    flops = bytes_accessed = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            flops = ca.get("flops")
            bytes_accessed = ca.get("bytes accessed")
    except Exception:
        pass
    return {"flops": flops, "bytes_accessed": bytes_accessed}


def program_memory(compiled) -> dict:
    """Peak-memory view of a compiled executable via
    ``memory_analysis()``: argument/output/temp sizes plus their sum as
    ``peak_bytes`` — the live-buffer upper bound the program needs."""
    out = {"argument_bytes": None, "output_bytes": None,
           "temp_bytes": None, "generated_code_bytes": None,
           "peak_bytes": None}
    try:
        ma = compiled.memory_analysis()
        out["argument_bytes"] = int(ma.argument_size_in_bytes)
        out["output_bytes"] = int(ma.output_size_in_bytes)
        out["temp_bytes"] = int(ma.temp_size_in_bytes)
        out["generated_code_bytes"] = int(ma.generated_code_size_in_bytes)
        out["peak_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                             + out["temp_bytes"])
    except Exception:
        pass
    return out


def measured_iteration_cost(problem, dtype=None, scaled=None) -> dict:
    """Compile ONE PCG iteration body for ``problem`` and report what
    XLA's cost analysis counted, next to the analytic model.

    The body program is the attribution anchor: the solve's
    ``while_loop`` body is counted once by HLO cost analysis regardless
    of trip count, so compiling the body alone is the only way to read
    per-iteration cost off a real executable. Sets the ``cost.hlo.*``
    and ``cost.model.*`` gauges; returns the combined dict. Compilation
    of the body is small (one fused elementwise/stencil program), but
    not free — call this once per (problem, dtype) from harness code,
    not per solve.
    """
    import jax
    import jax.numpy as jnp

    from poisson_tpu.solvers.pcg import (
        iteration_program,
        resolve_dtype,
        resolve_scaled,
    )

    dtype_name = resolve_dtype(dtype)
    use_scaled = resolve_scaled(scaled, dtype_name)
    body, state = iteration_program(problem, dtype=dtype_name,
                                    scaled=use_scaled)
    compiled = jax.jit(body).lower(state).compile()
    cost = program_costs(compiled)
    mem = program_memory(compiled)
    model = analytic_iteration_cost(
        problem.M, problem.N, jnp.dtype(dtype_name).itemsize, use_scaled
    )
    agreement = None
    if cost["bytes_accessed"]:
        agreement = cost["bytes_accessed"] / model["bytes"]
    report = {
        "program": "xla_iteration_body",
        "grid": [problem.M, problem.N],
        "dtype": dtype_name,
        "scaled": use_scaled,
        "hlo_flops_per_iter": cost["flops"],
        "hlo_bytes_per_iter": cost["bytes_accessed"],
        "model_flops_per_iter": model["flops"],
        "model_bytes_per_iter": model["bytes"],
        "model_passes": model["passes"],
        # hlo/model bytes ratio; 1.0 = perfect agreement, the ±25% band
        # is the pinned invariant (tests/test_perf_obs.py).
        "model_agreement": agreement,
        "peak_memory_bytes": mem["peak_bytes"],
    }
    for key in ("hlo_flops_per_iter", "hlo_bytes_per_iter",
                "model_flops_per_iter", "model_bytes_per_iter",
                "model_agreement", "peak_memory_bytes"):
        if report[key] is not None:
            metrics.gauge(f"cost.{key}", report[key])
    return report


def solve_program_costs(problem, dtype=None, scaled=None,
                        stream_every: int = 0) -> dict:
    """Whole-solve-program introspection: FLOPs, bytes, and peak memory
    of the actual jitted ``_solve`` executable (setup + fused loop +
    epilogue; the loop body counted once — per-iteration attribution is
    :func:`measured_iteration_cost`'s job). Costs a compile of the full
    program, so it is harness-level (bench.py), not per-solve. Sets the
    ``cost.solve.*`` gauges."""
    import jax.numpy as jnp

    from poisson_tpu.solvers.pcg import (
        _solve,
        host_setup,
        resolve_dtype,
        resolve_scaled,
    )

    dtype_name = resolve_dtype(dtype)
    use_scaled = resolve_scaled(scaled, dtype_name)
    a, b, rhs, aux = host_setup(problem, dtype_name, use_scaled)
    compiled = _solve.lower(problem, use_scaled, int(stream_every),
                            0, 0.0, False, 0, a, b, rhs, aux).compile()
    cost = program_costs(compiled)
    mem = program_memory(compiled)
    report = {
        "program": "xla_solve",
        "flops": cost["flops"],
        "bytes_accessed": cost["bytes_accessed"],
        "peak_memory_bytes": mem["peak_bytes"],
        "argument_bytes": mem["argument_bytes"],
        "temp_bytes": mem["temp_bytes"],
    }
    for key in ("flops", "bytes_accessed", "peak_memory_bytes"):
        if report[key] is not None:
            metrics.gauge(f"cost.solve.{key}", report[key])
    return report


# -- roofline attribution -----------------------------------------------

# Effective HBM array passes per iteration by backend — how many times
# the working set actually crosses the memory system once fusion and
# cache residency are accounted for. These are the SAME constants
# BENCH.md's physical-consistency rule and summarize_session's
# passes-at-ceiling column use: the pallas numbers from the kernels'
# strip pass models (benchmarks/roofline.py), the xla number from the
# measured fusion break-even documented in BENCH.md. Distinct from the
# HLO operand model above on purpose: operand counting double-counts
# cache-resident reuse, so it must never be fed into a bandwidth
# fraction.
EFFECTIVE_PASSES = {
    "xla": 8.0,
    "sharded": 8.0,
    "xla_batched": 8.0,
    "pallas": 14.7,
    "pallas_fused": 14.7,
    "pallas-sharded": 14.7,
    "pallas_sharded": 14.7,
    "pallas-ca": 10.1,
    "pallas_ca": 10.1,
    "pallas-ca-sharded": 10.1,
}

# Published peak HBM bandwidth per chip, GB/s, matched by substring
# against device_kind (libtpu strings: 'TPU v5 lite', 'TPU v5e',
# 'TPU v4', ...). v5e aligned with the 0.82 TB/s measured stream ceiling
# BENCH.md already standardizes on. POISSON_TPU_PEAK_GBPS overrides —
# the knob for CPU hosts or unlisted parts.
PEAK_GBPS_BY_DEVICE = (
    ("v5 lite", 820.0),
    ("v5litepod", 820.0),
    ("v5e", 820.0),
    ("v5p", 2765.0),
    ("v6e", 1640.0),
    ("v6 lite", 1640.0),
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
)


def platform_peak_gbps(device_kind: Optional[str]) -> Optional[float]:
    """Bandwidth ceiling for a device_kind string (None when unknown).
    ``POISSON_TPU_PEAK_GBPS`` wins when set — e.g. a CPU host whose
    stream ceiling was measured once with ``benchmarks/roofline.py``."""
    env = os.environ.get("POISSON_TPU_PEAK_GBPS")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    if not device_kind:
        return None
    kind = str(device_kind).lower()
    for sub, gbps in PEAK_GBPS_BY_DEVICE:
        if sub in kind:
            return gbps
    return None


def roofline_summary(problem, backend: Optional[str], dtype_bytes: int,
                     iterations: int, solve_seconds: float,
                     device_kind: Optional[str] = None,
                     devices: int = 1,
                     passes_override: Optional[float] = None) -> dict:
    """Achieved-vs-roofline attribution of one measured solve.

    ``achieved_gbps`` = effective bytes/iteration (backend pass model ×
    grid bytes) × iterations / seconds, per device; ``fraction`` divides
    by the platform ceiling when one is known (None otherwise — an
    honest "no ceiling on file" beats a made-up one). Sets the
    ``roofline.*`` gauges. ``passes_override`` replaces the static
    backend pass model for program families whose traffic is
    config-dependent — the MG-preconditioned iteration's passes are the
    CG body's plus :func:`mg_vcycle_cost`'s fine-equivalent, so MG
    records never borrow the plain-CG model (and regress.py cohorts
    them separately by ``detail.preconditioner`` anyway).
    """
    passes = (passes_override if passes_override is not None
              else EFFECTIVE_PASSES.get(backend or ""))
    peak = platform_peak_gbps(device_kind)
    achieved = None
    if passes and solve_seconds and solve_seconds > 0 and iterations:
        grid_bytes = grid_points(problem.M, problem.N) * dtype_bytes
        achieved = (passes * grid_bytes * iterations
                    / solve_seconds / max(1, devices) / 1e9)
    fraction = (achieved / peak) if (achieved and peak) else None
    report = {
        "passes_model": passes,
        "bytes_per_iter_model": (
            passes * grid_points(problem.M, problem.N) * dtype_bytes
            if passes else None
        ),
        # Four significant figures, not fixed decimals: a tiny or slow
        # solve's few MB/s would otherwise round to a 0.0 GB/s reading.
        "achieved_gbps": float(f"{achieved:.4g}") if achieved else None,
        "peak_gbps": peak,
        "fraction": float(f"{fraction:.4g}") if fraction else None,
    }
    for key in ("achieved_gbps", "peak_gbps", "fraction"):
        if report[key] is not None:
            metrics.gauge(f"roofline.{key}", report[key])
    return report


def bench_costs(problem, dtype=None, backend: Optional[str] = None,
                iterations: Optional[int] = None,
                solve_seconds: Optional[float] = None,
                device_kind: Optional[str] = None, devices: int = 1,
                full_program: bool = False) -> Optional[dict]:
    """The cost block bench records embed: per-iteration HLO-vs-model
    attribution plus the roofline fraction of the measured run.

    The attribution anchor is always the XLA iteration body (the
    reference program every backend is golden-checked against); pallas
    executables are not introspectable through ``cost_analysis`` and the
    block says so via ``program``. ``full_program=True`` additionally
    compiles and introspects the whole jitted solve (``cost.solve.*``).
    ``POISSON_TPU_COST_ANALYSIS=0`` disables the whole block; any
    internal failure returns None rather than raising.
    """
    if os.environ.get("POISSON_TPU_COST_ANALYSIS", "1") == "0":
        return None
    try:
        import jax.numpy as jnp

        from poisson_tpu.solvers.pcg import resolve_dtype

        dtype_name = resolve_dtype(dtype)
        block = measured_iteration_cost(problem, dtype=dtype_name)
        if full_program:
            block["solve_program"] = solve_program_costs(
                problem, dtype=dtype_name
            )
        if iterations and solve_seconds:
            block["roofline"] = roofline_summary(
                problem, backend, jnp.dtype(dtype_name).itemsize,
                iterations, solve_seconds, device_kind, devices,
            )
        return block
    except Exception:
        return None
