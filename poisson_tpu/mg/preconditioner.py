"""MG-preconditioned PCG drivers: the ops bundle and the jitted solves.

The preconditioner seam of the whole framework is ``PCGOps.apply_Dinv``
— the shared PCG body (``solvers.pcg.make_pcg_body``) only ever sees
``z = M⁻¹r`` through it. Plugging multigrid in is therefore an ops
construction, never a body change: the default ``"jacobi"`` programs are
the byte-identical historical executables (pinned by tests/test_mg.py),
and ``"mg"`` swaps one V-cycle per iteration in their place.

Scaled-system wrap: the fp32 production path runs CG on
Ã = D^{-1/2}·A·D^{-1/2} (``scaled_single_device_ops``). The V-cycle
works in w-space on the *unscaled* operator at every level, so the
scaled preconditioner is the congruence transform
``z̃ = √d · V(√d · r̃)`` — SPD whenever V is, and exactly equivalent to
MG-preconditioned CG on the unscaled system under y = D^{1/2}w.

Every jitted driver here is the MG twin of an existing flag-off program
(``_solve``, ``_solve_batched``, ``_run_chunk``, ``_member_init``,
``_step_lanes``) with the hierarchy riding as one extra pytree operand
and the cycle config as one extra static arg — separate executables by
construction, so flag-off callers keep their compile-cache identity.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from poisson_tpu.config import Problem
from poisson_tpu.mg.cycle import v_cycle
from poisson_tpu.mg.hierarchy import (
    DEFAULT_MG,
    MGConfig,
    MGLevels,
    device_hierarchy,
)
from poisson_tpu.solvers.pcg import (
    PCGOps,
    PCGResult,
    PCGState,
    init_state,
    make_pcg_body,
    make_pcg_member_body,
    pcg_loop,
    scaled_single_device_ops,
    single_device_ops,
)


def mg_ops(problem: Problem, a, b, aux, hier: MGLevels,
           config: MGConfig = DEFAULT_MG, scaled: bool = True,
           kernel_levels: int = 0, interpret: bool = False) -> PCGOps:
    """The MG-preconditioned ops bundle: the standard backend bundle
    with ``apply_Dinv`` replaced by one V-cycle (scaled solves get the
    √d congruence wrap — ``hier.scinv``). Everything else — operator,
    dots, norms — is untouched, so the outer CG recurrence is exactly
    the historical one with a stronger M⁻¹. ``kernel_levels`` and
    ``interpret`` go to :func:`~poisson_tpu.mg.cycle.v_cycle`."""
    base = (
        scaled_single_device_ops(problem, a, b, aux)
        if scaled
        else single_device_ops(problem, a, b, aux)
    )
    h1, h2 = problem.h1, problem.h2
    if scaled:
        scinv = hier.scinv

        def precond(rt):
            return scinv * v_cycle(hier, scinv * rt, h1, h2, config,
                                   kernel_levels, interpret)
    else:
        def precond(r):
            return v_cycle(hier, r, h1, h2, config, kernel_levels,
                           interpret)

    return base._replace(apply_Dinv=precond)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5),
                   static_argnames=("interpret",))
def _solve_mg(problem: Problem, scaled: bool, config: MGConfig,
              stream_every: int, verify_every: int, verify_tol: float,
              a, b, rhs, aux, hier: MGLevels,
              interpret: bool = False) -> PCGResult:
    """The MG twin of ``solvers.pcg._solve``: same loop, same flags,
    same result contract — the hierarchy is an operand, the cycle
    config a static arg. ``verify_every`` arms the same in-loop
    integrity probe (drift is preconditioner-independent; the
    update-norm guards use the MG-calibrated collapse ratio —
    ``integrity.probe.default_verify_collapse``).

    The one MG program whose cycle takes the Pallas strip kernels: on
    the levels ``hier.strips`` holds (``mg.hierarchy.kernel_levels``),
    in the interpreter when ``interpret``. Its twins below keep the XLA
    cycle, so the V-cycle's bit parity under ``vmap`` holds there."""
    ops = mg_ops(problem, a, b, aux, hier, config, scaled,
                 len(hier.strips), interpret)
    s = pcg_loop(
        ops, rhs,
        delta=problem.delta, max_iter=problem.iteration_cap,
        weighted_norm=problem.weighted_norm,
        h1=problem.h1, h2=problem.h2,
        stream_every=stream_every,
        verify_every=verify_every, verify_tol=verify_tol,
        preconditioner="mg",
    )
    w = s.w * aux if scaled else s.w
    return PCGResult(w=w, iterations=s.k, diff=s.diff, residual_dot=s.zr,
                     flag=s.flag)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _solve_batched_mg(problem: Problem, scaled: bool, config: MGConfig,
                      verify_every: int, verify_tol: float,
                      a, b, rhs_stack, aux, hier: MGLevels) -> PCGResult:
    """The MG twin of ``solvers.batched._solve_batched``: the shared
    member body (with the V-cycle inside ``apply_Dinv``) vmapped over a
    (B, M+1, N+1) RHS stack with the same per-member convergence
    masking — the hierarchy closes over the body and broadcasts, one
    coefficient load for the whole batch."""
    from poisson_tpu.solvers.batched import pcg_loop_batched

    ops = mg_ops(problem, a, b, aux, hier, config, scaled)
    s = pcg_loop_batched(
        ops, rhs_stack,
        delta=problem.delta, max_iter=problem.iteration_cap,
        weighted_norm=problem.weighted_norm,
        h1=problem.h1, h2=problem.h2,
        verify_every=verify_every, verify_tol=verify_tol,
        preconditioner="mg",
    )
    w = s.w * aux if scaled else s.w
    return PCGResult(w=w, iterations=s.k, diff=s.diff, residual_dot=s.zr,
                     flag=s.flag, max_iterations=jnp.max(s.k))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _member_init_mg(problem: Problem, scaled: bool, config: MGConfig,
                    a, b, aux, hier: MGLevels, rhs) -> PCGState:
    """One member's ``init_state`` with the MG preconditioner (z₀ is a
    V-cycle of r₀) — the lane splice twin of ``lanes._member_init``."""
    return init_state(mg_ops(problem, a, b, aux, hier, config, scaled),
                      rhs)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _step_lanes_mg(problem: Problem, scaled: bool, chunk: int,
                   config: MGConfig, verify_every: int, verify_tol: float,
                   a, b, aux, hier: MGLevels, rhs_stack,
                   state: PCGState) -> PCGState:
    """The MG twin of ``lanes._step_lanes`` (and, with
    ``verify_every`` > 0, of ``_step_lanes_verify``): advance every lane
    by at most ``chunk`` of its own iterations against the shared
    hierarchy. ``rhs_stack`` is only read when verifying (each lane's
    probe checks its OWN right-hand side); flag-off callers pass None —
    an empty pytree, so the operand signature stays honest."""
    ops = mg_ops(problem, a, b, aux, hier, config, scaled)
    if verify_every > 0:
        member = make_pcg_member_body(
            ops, delta=problem.delta, weighted_norm=problem.weighted_norm,
            h1=problem.h1, h2=problem.h2,
            verify_every=verify_every, verify_tol=verify_tol,
            preconditioner="mg",
        )
        vbody = jax.vmap(member, in_axes=(0, 0))
        step = lambda s: vbody(s, rhs_stack)
    else:
        body = make_pcg_body(
            ops, delta=problem.delta, weighted_norm=problem.weighted_norm,
            h1=problem.h1, h2=problem.h2,
        )
        vb = jax.vmap(body)
        step = lambda s: vb(s)
    stop_at = jnp.minimum(state.k + chunk, problem.iteration_cap)

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


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6, 7))
def _run_chunk_mg(problem: Problem, scaled: bool, chunk: int,
                  config: MGConfig, stagnation_window: int,
                  stream_every: int, verify_every: int, verify_tol: float,
                  a, b, aux, rhs, hier: MGLevels,
                  state: PCGState) -> PCGState:
    """The MG twin of ``checkpoint._run_chunk``: advance a chunked solve
    by at most ``chunk`` iterations. Drives the checkpointed, chunked
    (deadline-carrying) and resilient single-request paths."""
    ops = mg_ops(problem, a, b, aux, hier, config, scaled)
    body = make_pcg_body(
        ops, delta=problem.delta, weighted_norm=problem.weighted_norm,
        h1=problem.h1, h2=problem.h2,
        stagnation_window=stagnation_window, stream_every=stream_every,
        verify_every=verify_every, verify_tol=verify_tol,
        verify_rhs=rhs, preconditioner="mg",
    )
    stop_at = jnp.minimum(state.k + chunk, problem.iteration_cap)

    def cond(s: PCGState):
        return (~s.done) & (s.k < stop_at)

    return lax.while_loop(cond, body, state)


def mg_solve_setup(problem: Problem, dtype_name: str, scaled: bool,
                   geometry=None,
                   config: MGConfig = DEFAULT_MG):
    """(a, b, rhs, aux, hierarchy) for an MG solve — ``solve_setup``
    plus the fingerprint-cached device hierarchy."""
    from poisson_tpu.solvers.pcg import solve_setup

    a, b, rhs, aux = solve_setup(problem, dtype_name, scaled,
                                 geometry=geometry)
    hier = device_hierarchy(problem, dtype_name, scaled,
                            geometry=geometry, config=config)
    return a, b, rhs, aux, hier
