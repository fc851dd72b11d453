"""Batched multi-RHS solves: one traced program, many Poisson problems.

Every path in the framework — like all five reference implementations
(SURVEY §0) — solved exactly one right-hand side per dispatch. This driver
applies the block-CG insight (O'Leary 1980, PAPERS.md) as a *hardware
batching* transform rather than a Krylov-subspace change: the operator is
identical across members, so B right-hand sides stack on a leading batch
axis and the shared PCG body (``solvers.pcg.make_pcg_body``) is ``vmap``-ed
over it. One compile, one ``lax.while_loop``, one kernel launch sequence —
compile time, dispatch overhead, and coefficient-field memory traffic are
paid once for the whole batch, the same throughput move every inference
serving stack makes (Orca, PAPERS.md).

Per-member convergence masking keeps the iterate sequences honest: each
member carries its own ``flag``/``k``, a member that stops (converged,
breakdown, non-finite, budget) is *frozen* — the vmapped body still computes
its would-be update, a per-member select discards it — and the fused loop
exits when every member has stopped. A member's iterates, flags, and
iteration counts therefore match the sequential ``pcg_loop`` bit-for-bit
(tests/test_batched.py asserts exactly this, f32 and f64).

Ragged request sets are padded to a bucket size so one compiled executable
serves many batch sizes: a zero RHS converges degenerately at iteration 1
(ζ₀ = 0 trips the |（Ap,p)| guard), so padding members cost one masked
iteration and are sliced off before returning. Bucket-cache reuse is
surfaced via ``obs.metrics`` (``batched.bucket_cache.hits``/``.misses``).

Composition with the sharded path (``mesh=``): the batch axis is vmapped
*outside* ``shard_map`` — members stay whole-grid, the mesh splits the
grid, not the batch. One dispatch then solves B right-hand sides on an
N-device mesh (``parallel.pcg_sharded.solve_batched_sharded``): the
vmapped body runs per shard over the local block stack, every
per-member reduction is a ``psum``-replicated mesh scalar, and the halo
exchange + coefficient traffic of each iteration are paid once for the
whole batch. ``mesh=None`` (the default) keeps the single-device
programs byte-for-byte. Executable families that have no sharded
program yet (per-member geometries, MG, the in-loop integrity probe)
are rejected loudly when combined with ``mesh=``.

On a TPU, a plain batch on one operator (fp32 on the scaled system; no
mesh, geometries, MG, block mode or integrity probe — see
:func:`uses_fused_kernels`) runs instead on the member-axis Pallas
kernels (``ops.pallas_cg._fused_solve_batched``): one ``while_loop`` of
two fused sweeps over the (B, R, C) canvas stack, a stopped member
frozen by α = β = 0 rather than by selects over its canvases. Member i
is then the one-RHS fused solve (``pallas_cg_solve``) bit for bit, and
the ``batched.fused.*`` counters say how often the path engages. Every
other request, and every request off a TPU, keeps the XLA programs.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from poisson_tpu import obs
from poisson_tpu.config import Problem
from poisson_tpu.solvers.pcg import (
    PCGOps,
    PCGResult,
    PCGState,
    host_setup,
    init_state,
    make_pcg_body,
    resolve_dtype,
    resolve_scaled,
    scaled_single_device_ops,
    single_device_ops,
    solve_setup,
)

# Bucket ladder for padding ragged batch sizes onto a small set of compiled
# executables. Powers of two up to 256: request sets beyond the top bucket
# compile at their exact size (a deliberate escape hatch, not an error).
DEFAULT_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)

# Shapes this process has already traced, keyed like the jit cache
# ((bucket, M, N, dtype, scaled, weighted, delta, cap)). Mirrors XLA's own
# compile cache so the hit/miss counters in obs.metrics tell the serving
# story (a ragged arrival pattern that buckets well shows hits >> misses).
_TRACED: set = set()


def reset_bucket_cache() -> None:
    """Forget which bucket shapes this process has traced (tests; a
    library user pairing it with ``obs.metrics.reset()`` — the counters
    and this set must move together or hit/miss arithmetic goes stale)."""
    _TRACED.clear()


def bucket_size(n: int, buckets: Sequence[int] = DEFAULT_BUCKETS) -> int:
    """Smallest bucket ≥ n (n itself beyond the ladder)."""
    if n < 1:
        raise ValueError(f"batch size must be >= 1, got {n}")
    for b in buckets:
        if n <= b:
            return int(b)
    return int(n)


def pcg_loop_batched(ops: PCGOps, rhs_stack, *, delta: float, max_iter: int,
                     weighted_norm: bool, h1: float, h2: float,
                     stagnation_window: int = 0, verify_every: int = 0,
                     verify_tol: float = 0.0,
                     preconditioner: str = "jacobi") -> PCGState:
    """Run the shared PCG body over a (B, M+1, N+1) RHS stack in ONE fused
    ``while_loop`` with per-member convergence masking.

    The body is the exact sequential body (``make_pcg_body``) vmapped over
    the batch axis; each iteration then freezes every member whose previous
    state was already stopped (done, or at the iteration cap) by selecting
    its old state over the computed update — so a member's trajectory is
    identical to what ``pcg_loop`` would have produced, including its
    final ``k`` and ``flag``. The loop exits when no member can advance.

    Streaming (``stream_every``) is deliberately not plumbed here: the
    host callback is per-iteration scalar telemetry and has no meaningful
    vmapped form; the batched path reports per-member outcomes instead.

    ``verify_every`` > 0 arms the in-loop integrity probe PER MEMBER
    (``poisson_tpu.integrity``): the body's pair form
    (``make_pcg_member_body``) is vmapped with the RHS stack so every
    member's true residual is checked against its OWN right-hand side —
    a flipped bit stops only the corrupted member with FLAG_INTEGRITY;
    its batchmates' trajectories are untouched (masked, like every
    other per-member stop). At 0 the program is the exact historical
    one.
    """
    if verify_every > 0:
        from poisson_tpu.solvers.pcg import make_pcg_member_body

        member = make_pcg_member_body(
            ops, delta=delta, weighted_norm=weighted_norm, h1=h1, h2=h2,
            stagnation_window=stagnation_window,
            verify_every=verify_every, verify_tol=verify_tol,
            preconditioner=preconditioner,
        )
        vpair = jax.vmap(member, in_axes=(0, 0))
        vbody = lambda s: vpair(s, rhs_stack)
    else:
        body = make_pcg_body(
            ops, delta=delta, weighted_norm=weighted_norm, h1=h1, h2=h2,
            stagnation_window=stagnation_window,
        )
        vbody = jax.vmap(body)
    init = jax.vmap(functools.partial(init_state, ops))(rhs_stack)

    def masked_body(s: PCGState) -> PCGState:
        stepped = vbody(s)
        frozen = s.done | (s.k >= max_iter)

        def keep(old, new):
            pred = frozen.reshape(frozen.shape + (1,) * (new.ndim - 1))
            return jnp.where(pred, old, new)

        return jax.tree_util.tree_map(keep, s, stepped)

    def cond(s: PCGState):
        return jnp.any((~s.done) & (s.k < max_iter))

    return lax.while_loop(cond, masked_body, init)


def member_field_ops(problem: Problem, scaled: bool):
    """Per-member ops factory for stacked-canvas programs. ONE
    construction shared by the fused batched solve and the lane stepping
    engine — their bit-parity contract rests on it."""

    def member_ops(a, b, aux):
        return (
            scaled_single_device_ops(problem, a, b, aux)
            if scaled
            else single_device_ops(problem, a, b, aux)
        )

    return member_ops


def pcg_step_batched_fields(problem: Problem, scaled: bool, a_stack,
                            b_stack, aux_stack, state: PCGState,
                            stop_at, *, delta: float,
                            weighted_norm: bool, h1: float,
                            h2: float, verify_every: int = 0,
                            verify_tol: float = 0.0,
                            rhs_stack=None) -> PCGState:
    """Masked vmapped stepping over PER-MEMBER coefficient canvases:
    every member solves its OWN fictitious domain with the shared PCG
    body until it reaches ``stop_at`` — a scalar cap for the fused
    solve, a per-member stop line for the lane engine
    (:mod:`poisson_tpu.solvers.lanes`). Stopped/frozen members keep
    their state via per-member select, exactly like
    :func:`pcg_loop_batched`. ``verify_every`` > 0 arms the per-member
    integrity probe (``rhs_stack`` — each member's OWN RHS — is then
    required and vmapped alongside its canvases)."""
    member_ops = member_field_ops(problem, scaled)

    if verify_every > 0:
        from poisson_tpu.solvers.pcg import make_pcg_member_body

        if rhs_stack is None:
            raise ValueError("verify_every > 0 needs rhs_stack — the "
                             "per-member probe checks each member's own "
                             "true residual")

        def member_body_v(s: PCGState, a, b, aux, rhs) -> PCGState:
            body = make_pcg_member_body(
                member_ops(a, b, aux), delta=delta,
                weighted_norm=weighted_norm, h1=h1, h2=h2,
                verify_every=verify_every, verify_tol=verify_tol,
            )
            return body(s, rhs)

        vbody_v = jax.vmap(member_body_v)
        step = lambda s: vbody_v(s, a_stack, b_stack, aux_stack,
                                 rhs_stack)
    else:
        def member_body(s: PCGState, a, b, aux) -> PCGState:
            body = make_pcg_body(
                member_ops(a, b, aux), delta=delta,
                weighted_norm=weighted_norm, h1=h1, h2=h2,
            )
            return body(s)

        vbody = jax.vmap(member_body)
        step = lambda s: vbody(s, a_stack, b_stack, aux_stack)

    def masked_body(s: PCGState) -> PCGState:
        stepped = step(s)
        frozen = s.done | (s.k >= stop_at)

        def keep(old, new):
            pred = frozen.reshape(frozen.shape + (1,) * (new.ndim - 1))
            return jnp.where(pred, old, new)

        return jax.tree_util.tree_map(keep, s, stepped)

    def cond(s: PCGState):
        return jnp.any((~s.done) & (s.k < stop_at))

    return lax.while_loop(cond, masked_body, state)


def pcg_loop_batched_fields(problem: Problem, scaled: bool, a_stack,
                            b_stack, aux_stack, rhs_stack, *,
                            delta: float, max_iter: int,
                            weighted_norm: bool, h1: float,
                            h2: float, verify_every: int = 0,
                            verify_tol: float = 0.0) -> PCGState:
    """:func:`pcg_loop_batched` with PER-MEMBER coefficient canvases:
    a/b/aux carry a leading (B, …) axis and are vmapped alongside the
    state, so every member solves its OWN fictitious domain inside the
    one fused ``while_loop`` (mixed-geometry co-batching,
    ``poisson_tpu.geometry``). Member *i*'s arithmetic is the exact
    sequential solve of its canvases — per-member reductions make lane
    trajectories independent — so iterates/flags/counts match
    ``pcg_solve(problem, geometry=g_i)`` bit-for-bit (asserted in
    tests)."""
    member_ops = member_field_ops(problem, scaled)
    init = jax.vmap(
        lambda rhs, a, b, aux: init_state(member_ops(a, b, aux), rhs)
    )(rhs_stack, a_stack, b_stack, aux_stack)
    return pcg_step_batched_fields(
        problem, scaled, a_stack, b_stack, aux_stack, init, max_iter,
        delta=delta, weighted_norm=weighted_norm, h1=h1, h2=h2,
        verify_every=verify_every, verify_tol=verify_tol,
        rhs_stack=(rhs_stack if verify_every > 0 else None))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _solve_batched_geo(problem: Problem, scaled: bool, verify_every: int,
                       verify_tol: float, a_stack, b_stack,
                       rhs_stack, aux_stack) -> PCGResult:
    """jitted mixed-geometry batched solve: one executable per
    (bucket, grid, dtype, scaled) — the SAME executable no matter which
    geometries occupy the members (canvases are operands, never part of
    the jit key), which is what lets a second geometry family land as a
    bucket-cache hit with zero recompiles. ``verify_every``/``verify_tol``
    are the static per-member integrity-probe knobs (0 = the exact
    historical program)."""
    s = pcg_loop_batched_fields(
        problem, scaled, a_stack, b_stack, aux_stack, rhs_stack,
        delta=problem.delta, max_iter=problem.iteration_cap,
        weighted_norm=problem.weighted_norm,
        h1=problem.h1, h2=problem.h2,
        verify_every=verify_every, verify_tol=verify_tol,
    )
    w = s.w * aux_stack if scaled else s.w   # per-member unscale
    return PCGResult(w=w, iterations=s.k, diff=s.diff, residual_dot=s.zr,
                     flag=s.flag, max_iterations=jnp.max(s.k))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _solve_batched(problem: Problem, scaled: bool, verify_every: int,
                   verify_tol: float, a, b, rhs_stack,
                   aux) -> PCGResult:
    """jitted batched solve over a (B, M+1, N+1) RHS stack; compiled once
    per (bucket, grid, dtype, scaled) — the executable every padded
    request set of the same bucket reuses. ``verify_every``/``verify_tol``
    are the static per-member integrity-probe knobs (0 = the exact
    historical program)."""
    ops = (
        scaled_single_device_ops(problem, a, b, aux)
        if scaled
        else single_device_ops(problem, a, b, aux)
    )
    s = pcg_loop_batched(
        ops, rhs_stack,
        delta=problem.delta, max_iter=problem.iteration_cap,
        weighted_norm=problem.weighted_norm,
        h1=problem.h1, h2=problem.h2,
        verify_every=verify_every, verify_tol=verify_tol,
    )
    w = s.w * aux if scaled else s.w   # aux broadcasts over the batch axis
    return PCGResult(w=w, iterations=s.k, diff=s.diff, residual_dot=s.zr,
                     flag=s.flag, max_iterations=jnp.max(s.k))


def _shared_base(problems: Sequence[Problem]) -> Problem:
    """Validate that every member shares the operator (everything except
    the RHS magnitude ``f_val``) and return the shared base problem."""
    if not problems:
        raise ValueError("solve_batched needs at least one problem")
    base = problems[0]
    for i, p in enumerate(problems[1:], start=1):
        if p.with_(f_val=base.f_val) != base:
            raise ValueError(
                "batched members must share the operator — every Problem "
                "field except f_val must match member 0; member "
                f"{i} differs: {p} vs {base}"
            )
    return base


def _count_bucket(key: tuple, batch: int, bucket: int) -> None:
    if key in _TRACED:
        obs.inc("batched.bucket_cache.hits")
    else:
        _TRACED.add(key)
        obs.inc("batched.bucket_cache.misses")
    obs.inc("batched.solves", batch)
    obs.inc("batched.padding_members", bucket - batch)
    obs.gauge("batched.last_bucket", bucket)


def solve_batched(problems=None, *, rhs_stack=None, rhs_gates=None,
                  dtype=None, scaled=None, mesh=None,
                  buckets: Sequence[int] = DEFAULT_BUCKETS,
                  bucket: Optional[int] = None,
                  member_ids: Optional[Sequence] = None,
                  geometries: Optional[Sequence] = None,
                  verify_every: int = 0,
                  verify_tol=None,
                  preconditioner: str = "jacobi",
                  mg_config=None,
                  mode: str = "independent") -> PCGResult:
    """Solve a batch of Poisson problems in one fused device program.

    Input forms (exactly one):

    - ``solve_batched([p0, p1, …])`` — a sequence of :class:`Problem`
      sharing everything but ``f_val`` (the operator must be shared; the
      RHS may differ member-to-member). Each member's RHS is built by the
      same fp64 host setup the sequential solver uses, so member ``i``
      reproduces ``pcg_solve(p_i)`` bit-for-bit.
    - ``solve_batched(p, rhs_gates=[g0, g1, …])`` — one problem, B scalar
      RHS multipliers (the batched mirror of ``pcg_solve``'s ``rhs_gate``;
      also the bench/CLI chaining hook — gates may be traced scalars).
    - ``solve_batched(p, rhs_stack=B_array)`` — one problem, an explicit
      (B, M+1, N+1) stack of physical right-hand sides (zero Dirichlet
      ring; internally mapped to the scaled system when ``scaled``).

    The batch is zero-padded to :func:`bucket_size` (``bucket`` pins an
    explicit size ≥ B) so ragged request sets reuse one compiled
    executable per bucket; padding members stop degenerately at iteration
    1 and are sliced off before returning. Returns a :class:`PCGResult`
    whose ``w``/``iterations``/``diff``/``residual_dot``/``flag`` carry a
    leading batch axis (``iterations`` is the per-member truth) plus the
    scalar ``max_iterations`` the fused loop actually ran.

    ``dtype``/``scaled`` follow ``pcg_solve``'s precision policy. On a
    TPU the plain fp32 scaled forms run on the member-axis Pallas
    kernels (see the module docstring); members then reproduce
    ``pallas_cg_solve`` rather than ``pcg_solve``.

    ``mesh`` (a :class:`jax.sharding.Mesh` from
    ``parallel.mesh.make_solver_mesh``) runs the whole bucket as ONE
    sharded dispatch — vmap outside ``shard_map``: members stay
    whole-grid, the mesh splits the grid, halo exchange amortizes over
    the batch. Per-member iteration counts and stop flags reproduce the
    unsharded batched driver (iterates agree to reduction-order ULPs —
    ``psum`` of shard-local sums associates differently than one
    full-grid sum; pinned by tests/test_placement.py). ``mesh=None``
    keeps the historical single-device executables byte-for-byte.
    Combinations without a sharded program (``geometries``, MG,
    ``verify_every`` > 0) are rejected loudly.

    ``member_ids`` (optional, one hashable id per member) rides through
    padding and slicing onto ``PCGResult.origin``, so position ``i`` of
    every returned per-member field is attributable to ``origin[i]`` no
    matter how the batch was padded or re-formed. Default: ``(0, …, B−1)``.
    This is the requeue seam the solve service (``poisson_tpu.serve``)
    needs — a member re-enqueued into a *different* bucket after a fault
    keeps its request identity — and is useful standalone (aggregate
    bucket stats are no longer the only per-dispatch record).

    ``geometries`` (optional, one :mod:`poisson_tpu.geometry` spec or
    None per member) gives each member its OWN fictitious domain:
    coefficient canvases stack on a leading batch axis and the shared
    body is vmapped over them too, so *different geometries on the same
    grid co-batch in one bucket executable* — the executable is keyed by
    shapes alone, never by which domains occupy it (a second geometry
    family is a ``geom.cache.miss`` + ``batched.bucket_cache.hit``, zero
    recompiles). A None entry is the problem's default (the reference
    ellipse); member *i* reproduces
    ``pcg_solve(problem, geometry=g_i, rhs_gate=…)`` bit-for-bit.
    Padding members reuse member 0's canvases with a zero RHS (they
    stop degenerately at iteration 1 as before).

    ``verify_every`` > 0 arms the PER-MEMBER in-loop integrity probe
    (``poisson_tpu.integrity``; ``verify_tol`` defaults dtype-aware):
    a silently corrupted member stops alone with FLAG_INTEGRITY while
    its batchmates solve on untouched — the masking that already
    isolates per-member convergence isolates per-member corruption
    verdicts too. The stride is part of the executable identity, so
    verified buckets form their own bucket-cache key family and
    ``verify_every=0`` keeps the historical executables byte-for-byte.

    ``mode`` selects the batched recurrence (``poisson_tpu.krylov``):
    ``"independent"`` (the default) is the historical vmapped-member
    program — byte-identical executables, golden counts bit-for-bit;
    ``"block"`` carries the (n × B) block iterate with B×B recurrences
    (:mod:`poisson_tpu.krylov.block` — breakdown-free block CG), so
    members share spectral information and total iterations drop on
    clustered RHS batches. Block mode requires ONE shared operator:
    ``geometries`` entries, if given, must all carry the same
    fingerprint (the single shared domain); ``mesh``/MG/``verify_every``
    have no block program yet and are rejected loudly. Block dispatches
    compile at the EXACT batch size (no zero-RHS padding — a zero
    column is pure rank deficiency, wasted width by construction) and
    their bucket-cache keys carry a ``("block",)`` marker so block
    executables never claim reuse of the independent family. Block
    iteration counts are per-member first-δ-crossings of a coupled
    recurrence — NOT comparable to the independent mode's — so block
    mode is gated by the manufactured-solution L2 oracle
    (``geometry.manufactured.manufactured_error(krylov=…)``), not by
    golden-count parity. ``PCGResult.deficient`` reports whether the
    B×B solves truncated a rank-deficient direction (graceful
    degradation — the ``krylov.block.rank_deficient`` counter).

    ``preconditioner="mg"`` runs every member with the geometric
    V-cycle preconditioner (:mod:`poisson_tpu.mg`): the shared member
    body — V-cycle inside ``apply_Dinv`` — is vmapped exactly like the
    Jacobi body and the hierarchy canvases broadcast across the batch
    (one coefficient load for B members). Parity contract: the MG
    *apply* (one V-cycle) is bit-identical under ``vmap`` and member
    *i* reproduces ``pcg_solve(..., preconditioner="mg")``'s iteration
    count and stop flag exactly, with iterates agreeing to a few ULPs —
    XLA's FMA-contraction choices inside the deep fused cycle+body
    program differ between the solo and vmapped layouts, which the
    elementwise Jacobi body never exposed (both pinned by
    tests/test_mg.py). MG buckets are their own executable family (the
    bucket-cache key carries the cycle config); mixed per-member
    ``geometries`` do not co-batch with MG yet — each member would
    need its own level hierarchy — and are rejected loudly (the solve
    service dispatches geometry+MG requests solo). The counters
    ``batched.mg.dispatches`` and ``batched.mg.members`` count MG
    dispatches and their members.

    Each call runs under the span ``solve_batched`` with the children
    ``solve_batched.prepare`` (argument checks, gate upload, set-up
    lookup and, for MG, the level hierarchy lookup, padding),
    ``solve_batched.launch`` (the dispatch) and
    ``solve_batched.finish`` (slicing the padding off): see
    :func:`poisson_tpu.obs.span`.
    """
    with obs.span("solve_batched"):
        with obs.span("solve_batched.prepare"):
            prep = _prepare_batch(
                problems, rhs_stack=rhs_stack, rhs_gates=rhs_gates,
                dtype=dtype, scaled=scaled, mesh=mesh, buckets=buckets,
                bucket=bucket, member_ids=member_ids, geometries=geometries,
                verify_every=verify_every, verify_tol=verify_tol,
                preconditioner=preconditioner, mg_config=mg_config,
                mode=mode)
        with obs.span("solve_batched.launch"):
            result = _launch_batch(prep, mesh)
        with obs.span("solve_batched.finish"):
            return _finish_batch(prep, result)


class _Prepared(NamedTuple):
    """What ``solve_batched``'s host preparation hands its dispatch."""

    problem: Problem
    jit_problem: Problem      # f_val normalized away (see _prepare_batch)
    dtype_name: str
    use_scaled: bool
    use_block: bool
    mg: Optional[tuple]       # (cycle config, device hierarchy) for MG
    geo: Optional[list]       # parsed per-member geometries, or None
    setups: Optional[list]    # per-member (a, b, rhs, aux) on that path
    a: Any
    b: Any
    aux: Any
    rhs_stack: Any            # padded to ``size`` members
    batch: int
    size: int
    origin: tuple
    verify_every: int
    v_tol: float
    verify_key: Optional[tuple]
    # The fused path's (cv, cs, cw, g, sc2, sc_int); rhs_stack then holds
    # (size, R, C) canvases. None on every XLA family.
    canvases: Optional[tuple]


def uses_fused_kernels(platform: str, dtype_name: str, scaled: bool, *,
                       mesh=None, geometries=None, mg: bool = False,
                       block: bool = False, verify_every: int = 0) -> bool:
    """Whether a batch runs on the member-axis Pallas kernels
    (``ops.pallas_cg._fused_solve_batched``) rather than the vmapped XLA
    loop: on a TPU, in fp32 on the scaled system, with no mesh,
    per-member geometries, MG preconditioner, block mode or in-loop
    integrity probe — ``cli._pick_backend``'s rule for one solve. Every
    input form (gates, a stack, a list of problems) qualifies."""
    return (platform == "tpu" and dtype_name == "float32" and scaled
            and mesh is None and geometries is None and not mg
            and not block and verify_every == 0)


def _platform() -> str:
    return jax.devices()[0].platform


def _fused_operands(problem: Problem, rhs_stack, gates):
    """The fused path's coefficient canvases and its (B, R, C) right-hand
    side canvases: the gated RHS canvas, exactly ``pallas_cg_solve
    (rhs_gate=)``'s multiply, or a full-grid stack laid onto canvases."""
    from poisson_tpu.ops.pallas_cg import (
        batched_bm,
        build_canvases,
        grid_to_canvas,
    )

    cv, cs, cw, g, rhs, sc2, sc_int = build_canvases(problem,
                                                     batched_bm(problem))
    if gates is not None:
        stack = rhs[None] * gates[:, None, None]
    else:
        stack = grid_to_canvas(problem, cv, rhs_stack)
    return (cv, cs, cw, g, sc2, sc_int), stack


def _prepare_batch(problems, *, rhs_stack, rhs_gates, dtype, scaled, mesh,
                   buckets, bucket, member_ids, geometries, verify_every,
                   verify_tol, preconditioner, mg_config,
                   mode) -> _Prepared:
    """``solve_batched``'s host preparation: every argument check, the
    gate upload, the ``host_setup`` lookup, the MG level hierarchy lookup
    and the padded ``rhs_stack`` (see :func:`solve_batched` for the
    arguments)."""
    from poisson_tpu.krylov import KRYLOV_BLOCK, KRYLOV_MODES

    if mode not in KRYLOV_MODES:
        raise ValueError(
            f"unknown mode {mode!r} — expected one of {KRYLOV_MODES}")
    use_block = mode == KRYLOV_BLOCK
    if use_block:
        # The block recurrence couples members through B×B solves, so
        # it is only defined for ONE shared operator; the orthogonal
        # executable families have no block program yet:
        if mesh is not None:
            raise ValueError(
                "mode='block' has no sharded program yet; drop mesh= "
                "or use mode='independent'")
        if preconditioner not in (None, "jacobi"):
            raise ValueError(
                "mode='block' composes with the jacobi (symmetric-"
                f"scaling) body only; preconditioner={preconditioner!r} "
                "has no block program — use mode='independent'")
        if int(verify_every) > 0:
            raise ValueError(
                "mode='block' does not trace the per-member integrity "
                "probe yet; run verify_every=0 or mode='independent'")
    if mesh is not None:
        # The batch×mesh composition (vmap outside shard_map — members
        # stay whole-grid, the mesh splits the grid) is wired for the
        # plain multi-RHS forms. The orthogonal executable families are
        # rejected loudly until each grows its own sharded program:
        if geometries is not None and any(g is not None
                                          for g in geometries):
            raise ValueError(
                "solve_batched(mesh=) does not carry per-member "
                "geometries yet (stacked canvases need sharded blocks "
                "per member); drop geometries= or dispatch on a single "
                "device")
        if preconditioner not in (None, "jacobi"):
            raise ValueError(
                "solve_batched(mesh=) composes with the Jacobi "
                "(symmetric-scaling) body only; preconditioner="
                f"{preconditioner!r} needs a sharded hierarchy — "
                "dispatch MG batches on a single device")
        if int(verify_every) > 0:
            raise ValueError(
                "solve_batched(mesh=) does not trace the per-member "
                "integrity probe yet; run verify_every=0 on the mesh "
                "or verified buckets on a single device")
    forms = sum(x is not None for x in (rhs_stack, rhs_gates))
    if problems is None:
        raise ValueError("solve_batched needs problems (a Problem or a "
                         "sequence of Problems)")
    if isinstance(problems, Problem):
        problem = problems
        if forms != 1:
            raise ValueError(
                "with a single Problem, pass exactly one of rhs_gates or "
                "rhs_stack (a sequence of Problems is the third form)"
            )
        member_problems = None
    else:
        if forms != 0:
            raise ValueError(
                "rhs_gates/rhs_stack apply to the single-Problem form; a "
                "sequence of Problems already defines every member's RHS"
            )
        member_problems = list(problems)
        problem = _shared_base(member_problems)

    dtype_name = resolve_dtype(dtype)
    use_scaled = resolve_scaled(scaled, dtype_name)

    # f_val never enters the traced program (the RHS arrives as a traced
    # array; the jitted solve reads only delta/cap/norm/h1/h2), so the jit
    # static key — and the bucket-cache key that mirrors it — normalizes
    # it away: batches differing only in RHS magnitude share one compiled
    # executable per bucket.
    jit_problem = problem.with_(f_val=1.0)
    mg_cfg = None
    if preconditioner not in (None, "jacobi"):
        from poisson_tpu.mg import (
            DEFAULT_MG,
            resolve_preconditioner,
            validate_mg_problem,
        )

        resolve_preconditioner(preconditioner)   # raises on unknown
        if geometries is not None:
            if any(g is not None for g in geometries):
                raise ValueError(
                    "preconditioner='mg' does not co-batch per-member "
                    "geometries yet (each member would need its own "
                    "level hierarchy); dispatch geometry+MG requests "
                    "solo via pcg_solve(geometry=..., "
                    "preconditioner='mg')"
                )
            geometries = None   # all-None entries: the default domain
        mg_cfg = mg_config or DEFAULT_MG
        validate_mg_problem(problem, mg_cfg)
    fused = uses_fused_kernels(
        _platform(), dtype_name, use_scaled, mesh=mesh,
        geometries=geometries, mg=mg_cfg is not None, block=use_block,
        verify_every=int(verify_every))
    geo = setups = None
    a = b = aux = None
    gates = None
    if geometries is not None:
        from poisson_tpu.geometry.dsl import parse_geometry

        geo = [None if g is None else parse_geometry(g)
               for g in geometries]
        if use_block:
            from poisson_tpu.geometry.dsl import fingerprint_of

            fps = {fingerprint_of(g) for g in geo}
            if len(fps) != 1:
                raise ValueError(
                    "mode='block' needs ONE shared operator: every "
                    "geometries entry must carry the same fingerprint "
                    f"(got {len(fps)} distinct domains) — mixed-domain "
                    "batches use mode='independent'")

    def _geo_setups(base_problem, n, per_member_problems=None):
        """One (a, b, rhs, aux) per member — fingerprint-cached device
        canvases (``geometry.canvas``); None entries are the problem's
        default ellipse via the exact host_setup arrays."""
        if len(geo) != n:
            raise ValueError(
                f"geometries must have one entry per member: got "
                f"{len(geo)} specs for batch {n}")
        probs = per_member_problems or [base_problem] * n
        return [solve_setup(p, dtype_name, use_scaled, geometry=g)
                for p, g in zip(probs, geo)]

    if member_problems is not None:
        if geo is not None:
            # Per-member setup (each member's canvases AND f_val-scaled
            # RHS come from its own spec/problem — bit-parity with
            # pcg_solve(p_i, geometry=g_i)).
            setups = _geo_setups(problem, len(member_problems),
                                 member_problems)
            rhs_stack = jnp.stack([s[2] for s in setups])
            batch = len(member_problems)
        else:
            from poisson_tpu.solvers.pcg import host_fields64

            # One shared setup (a/b/aux are f_val-independent) plus
            # per-member RHS by exact fp64 scaling of the unit-f_val
            # base — NOT B full host setups (which would also thrash
            # host_setup's small LRU). Bit-exactness vs host_setup(p_i):
            # the indicator is 0/1 and the scaling is a single fp64
            # product either way (f·1[D]·D^{-1/2} associates without
            # extra roundings), then the same cast.
            a, b, _, aux = host_setup(jit_problem, dtype_name, use_scaled)
            base64 = host_fields64(jit_problem, use_scaled)[2]
            dt = jnp.dtype(dtype_name)
            rhs_stack = jnp.stack([jnp.asarray(base64 * p.f_val, dt)
                                   for p in member_problems])
            batch = len(member_problems)
    elif rhs_gates is not None:
        if geo is None and not fused:
            a, b, rhs, aux = host_setup(problem, dtype_name, use_scaled)
        gate_dt = jnp.dtype(dtype_name)
        if hasattr(rhs_gates, "ndim"):
            # An existing (B,) array — possibly data-dependent on a prior
            # result (the bench's chaining trick: gates of exactly 1.0
            # computed from the previous solve serialize back-to-back
            # batched solves without changing any bit).
            gates = jnp.asarray(rhs_gates, gate_dt).reshape(-1)
        else:
            gates = jnp.stack([jnp.asarray(g, gate_dt).reshape(())
                               for g in rhs_gates])
        batch = gates.shape[0]
        if batch < 1:
            raise ValueError("rhs_gates must have at least one member")
        if geo is not None:
            # Per-member unit canvases × the member's gate — exactly
            # pcg_solve(problem, geometry=g, rhs_gate=gate)'s multiply.
            setups = _geo_setups(problem, batch)
            rhs_stack = jnp.stack([s[2] for s in setups]
                                  ) * gates[:, None, None]
        elif not fused:
            # Per-member rhs * gate — elementwise, exactly pcg_solve's
            # rhs_gate multiply, so gated members stay bit-identical to
            # the sequential gated solve.
            rhs_stack = rhs[None] * gates[:, None, None]
    else:
        a, b, _, aux = host_setup(jit_problem, dtype_name, use_scaled)
        rhs_stack = jnp.asarray(rhs_stack, jnp.dtype(dtype_name))
        if rhs_stack.ndim != 3 or rhs_stack.shape[1:] != problem.grid_shape:
            raise ValueError(
                f"rhs_stack must be (B, {problem.grid_shape[0]}, "
                f"{problem.grid_shape[1]}), got {rhs_stack.shape}"
            )
        batch = rhs_stack.shape[0]
        if geo is not None:
            setups = _geo_setups(jit_problem, batch)
            if use_scaled:
                # Physical B_i → member-scaled b̃_i = D_i^{-1/2}·B_i.
                rhs_stack = rhs_stack * jnp.stack([s[3] for s in setups])
        elif use_scaled:
            # Physical B → scaled b̃ = D^{-1/2}·B; aux IS D^{-1/2} on the
            # full grid (zero ring), so one broadcast multiply.
            rhs_stack = rhs_stack * aux

    if member_ids is not None:
        origin = tuple(member_ids)
        if len(origin) != batch:
            raise ValueError(
                f"member_ids must have one id per member: got "
                f"{len(origin)} ids for batch {batch}"
            )
    else:
        origin = tuple(range(batch))

    canvases = None
    if fused:
        # The gate form multiplies the problem's own RHS canvas; the other
        # forms carry their RHS already, so only the operator is looked up.
        canvases, rhs_stack = _fused_operands(
            problem if gates is not None else jit_problem, rhs_stack, gates)

    if use_block:
        # Block dispatches compile at the EXACT batch size: a zero-RHS
        # padding column is pure rank deficiency — width the coupled
        # recurrence would pay for and truncate every iteration.
        if bucket is not None and int(bucket) != batch:
            raise ValueError(
                f"mode='block' dispatches exact-size blocks; bucket="
                f"{bucket} cannot pad a batch of {batch}")
        size = batch
    else:
        size = (bucket_size(batch, buckets) if bucket is None
                else int(bucket))
    if size < batch:
        raise ValueError(f"bucket {size} smaller than batch {batch}")
    if size > batch:
        pad = jnp.zeros((size - batch,) + tuple(rhs_stack.shape[1:]),
                        rhs_stack.dtype)
        rhs_stack = jnp.concatenate([rhs_stack, pad])

    # Keyed exactly like the jit call below ((static problem, scaled) +
    # the shapes/dtype the stacked operands carry), so the hit/miss
    # counters report real executable reuse, not an approximation of it.
    # The geometry path adds one marker — stacked canvases are a
    # different operand signature, hence a different executable family —
    # but NEVER the fingerprints: every geometry mix of a bucket shares
    # one executable, which is the whole point of co-batching.
    from poisson_tpu.solvers.pcg import resolve_verify_tol

    verify_every = int(verify_every)
    v_tol = (resolve_verify_tol(verify_tol, dtype_name)
             if verify_every > 0 else 0.0)
    # The verify stride is executable identity (a static jit arg), so
    # the bucket-cache key mirrors it — but ONLY when verifying: the
    # flag-off key keeps its historical shape and counter arithmetic.
    verify_key = (("verify", verify_every, v_tol)
                  if verify_every > 0 else None)
    mg = None
    if mg_cfg is not None:
        from poisson_tpu.mg.hierarchy import device_hierarchy

        mg = (mg_cfg, device_hierarchy(problem, dtype_name, use_scaled,
                                       config=mg_cfg))
    return _Prepared(problem, jit_problem, dtype_name, use_scaled, use_block,
                     mg, geo, setups, a, b, aux, rhs_stack, batch, size,
                     origin, verify_every, v_tol, verify_key, canvases)


def _launch_batch(prep: _Prepared, mesh) -> PCGResult:
    """``solve_batched``'s dispatch: the bucket-cache bookkeeping and the
    jitted call of whichever executable family runs (the mesh family also
    looks up its shard blocks here)."""
    (problem, jit_problem, dtype_name, use_scaled, use_block, mg, geo,
     setups, a, b, aux, rhs_stack, batch, size, _, verify_every, v_tol,
     verify_key, canvases) = prep
    if canvases is not None:
        from poisson_tpu.ops.pallas_cg import _fused_solve_batched

        _count_bucket((size, jit_problem, dtype_name, use_scaled,
                       ("fused",)), batch, size)
        obs.inc("batched.fused.dispatches")
        obs.inc("batched.fused.members", batch)
        cv, cs, cw, g, sc2, sc_int = canvases
        interpret = jax.devices()[0].platform != "tpu"
        return _fused_solve_batched(jit_problem, cv, interpret, cs, cw, g,
                                    rhs_stack, sc2, sc_int)
    if use_block:
        from poisson_tpu.krylov.block import _solve_block

        if geo is not None:
            # One shared domain (fingerprint-uniform, validated above):
            # the block runs on its canvases, unbatched — the shared
            # operator is the whole point.
            a, b, aux = setups[0][0], setups[0][1], setups[0][3]
        key = (size, jit_problem, dtype_name, use_scaled, ("block",))
        if geo is not None:
            key = key + ("geo",)
        _count_bucket(key, batch, size)
        obs.inc("krylov.block.solves", batch)
        return _solve_block(jit_problem, use_scaled, a, b, rhs_stack,
                            aux)
    if mesh is not None:
        from poisson_tpu.parallel.mesh import X_AXIS, Y_AXIS, block_size
        from poisson_tpu.parallel.pcg_sharded import (
            _host_shard_blocks,
            shard_rhs_stack,
            solve_batched_sharded,
        )

        px_size = mesh.shape[X_AXIS]
        py_size = mesh.shape[Y_AXIS]
        m_blk = block_size(problem.M - 1, px_size)
        n_blk = block_size(problem.N - 1, py_size)
        # The mesh shape is executable identity (the shard program is
        # compiled per topology), so sharded buckets form their own
        # bucket-cache key family — a mesh dispatch never claims to
        # reuse a single-device executable, and vice versa.
        key = (size, jit_problem, dtype_name, use_scaled,
               ("mesh", px_size, py_size))
        _count_bucket(key, batch, size)
        a_blk, b_blk, _, aux_blk = _host_shard_blocks(
            jit_problem, px_size, py_size, m_blk, n_blk, dtype_name,
            use_scaled)
        rhs_blk = shard_rhs_stack(rhs_stack, px_size, py_size, m_blk,
                                  n_blk)
        result = solve_batched_sharded(jit_problem, mesh, dtype_name,
                                       use_scaled, a_blk, b_blk,
                                       rhs_blk, aux_blk)
    elif geo is not None:
        def stack_pad(idx):
            stack = jnp.stack([s[idx] for s in setups])
            if size > batch:
                # Padding members reuse member 0's canvases (any valid
                # operator works: their RHS is zero, they stop at k=1).
                stack = jnp.concatenate(
                    [stack, jnp.broadcast_to(
                        stack[:1], (size - batch,) + stack.shape[1:])])
            return stack

        key = (size, jit_problem, dtype_name, use_scaled, "geo")
        if verify_key:
            key = key + (verify_key,)
        _count_bucket(key, batch, size)
        result = _solve_batched_geo(jit_problem, use_scaled,
                                    verify_every, v_tol,
                                    stack_pad(0), stack_pad(1),
                                    rhs_stack, stack_pad(3))
    elif mg is not None:
        from poisson_tpu.mg.preconditioner import _solve_batched_mg

        cfg, hier = mg
        # MG buckets are their own executable family: the cycle config
        # is operand/static identity exactly like the verify stride.
        key = (size, jit_problem, dtype_name, use_scaled, ("mg", cfg))
        if verify_key:
            key = key + (verify_key,)
        _count_bucket(key, batch, size)
        obs.inc("batched.mg.dispatches")
        obs.inc("batched.mg.members", batch)
        obs.inc("mg.solves", batch)
        result = _solve_batched_mg(jit_problem, use_scaled, cfg,
                                   verify_every, v_tol,
                                   a, b, rhs_stack, aux, hier)
    else:
        key = (size, jit_problem, dtype_name, use_scaled)
        if verify_key:
            key = key + (verify_key,)
        _count_bucket(key, batch, size)
        result = _solve_batched(jit_problem, use_scaled, verify_every,
                                v_tol, a, b, rhs_stack, aux)
    return result


def _finish_batch(prep: _Prepared, result: PCGResult) -> PCGResult:
    """The dispatch's result with its padding members sliced off and
    ``origin`` attached."""
    batch, size, origin = prep.batch, prep.size, prep.origin
    if size == batch:
        return result._replace(origin=origin)
    # Slice padding members off every batched field; max_iterations is
    # recomputed over the real members (padding stops at k=1, so the
    # fused-loop max is unchanged unless every member was padding).
    # ``origin`` was never padded — position i stays member_ids[i].
    return PCGResult(
        w=result.w[:batch],
        iterations=result.iterations[:batch],
        diff=result.diff[:batch],
        residual_dot=result.residual_dot[:batch],
        flag=result.flag[:batch],
        max_iterations=jnp.max(result.iterations[:batch]),
        origin=origin,
    )
