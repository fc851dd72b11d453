"""Diagonally-preconditioned conjugate gradients as a ``lax.while_loop``.

TPU-native re-design of the reference's host-driven iteration
(``stage0/Withoutopenmp1.cpp:106-172`` ``solve``;
``stage2-mpi/poisson_mpi_decomp.cpp:356-460`` ``solve_mpi``;
``stage4-mpi+cuda/poisson_mpi_cuda_f.cu:688-983`` ``gradient_solver_mpi``):
the whole solve — setup, iteration, convergence test — is one traced program.
Unlike stage4, which synchronises the host after every kernel and round-trips
partial sums over PCIe for each dot product (SURVEY §3.3), nothing here leaves
the device until the loop exits.

The reference implements this loop five separate times (serial, OpenMP, MPI,
hybrid, CUDA). Here the loop skeleton exists once, parameterised by a
:class:`PCGOps` bundle: the single-device bundle has a no-op halo exchange and
plain sums; the sharded bundle (``parallel.pcg_sharded``) plugs in ``ppermute``
halo exchange and ``psum`` reductions. Same controller, different backend —
the factoring the reference never did.

Iteration structure (exactly the reference's, ``stage2:…cpp:400-457``):
    w0 = 0;  r0 = B;  z0 = D⁻¹r0;  p0 = z0;  ζ0 = (z0,r0)
    repeat k = 1, 2, …:
        Ap   = A p                      (halo exchange first, when sharded)
        den  = (Ap, p);  stop if |den| < 1e-15 (degenerate, state kept)
        α    = ζ/den
        w   += αp;  r −= αAp;  diff = ‖αp‖  (weighted or not, Problem.weighted_norm)
        z    = D⁻¹r;  ζ' = (z, r)
        stop if diff < δ  (this iteration counts, updates kept)
        β    = ζ'/ζ;  p = z + βp
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from poisson_tpu import obs
from poisson_tpu.config import Problem
from poisson_tpu.models.fictitious_domain import build_fields
from poisson_tpu.ops.stencil import (
    apply_A,
    apply_Dinv,
    diag_D,
    dot_weighted,
)

_DENOM_TOL = 1e-15  # degenerate-direction guard (stage2:…cpp:414)

# Termination verdicts recorded in PCGState.flag / PCGResult.flag. The
# reference's loop knows only "converged or budget" — at production scale a
# solve must also say *why* it stopped (NaN blow-up, Krylov breakdown,
# stagnation) so the recovery driver (solvers.resilient) can decide between
# restart, precision escalation, and failing loudly.
FLAG_NONE = 0        # still running, or a solver that does not track verdicts
FLAG_CONVERGED = 1   # ‖Δw‖ < δ
FLAG_BREAKDOWN = 2   # |（Ap, p)| below the degenerate-direction guard
FLAG_NONFINITE = 3   # NaN/Inf reached the residual or update norm
FLAG_STAGNATED = 4   # no best-‖Δw‖ improvement for a full stagnation window
# Host-stamped only, never set inside the fused loop: the chunked drivers
# (solvers.checkpoint / solvers.resilient) stamp it on the RESULT when a
# per-request deadline expired at a chunk boundary before convergence —
# the partial-result-with-flag contract of the solve service
# (poisson_tpu.serve). The persisted PCGState never carries it, so a
# deadline-stopped solve resumes cleanly with a larger budget.
FLAG_DEADLINE = 5    # deadline expired mid-solve; w is the partial iterate
# In-loop integrity verdict (poisson_tpu.integrity): the verification
# probe (verify_every > 0) found the recurrence residual drifting from
# the true residual, or a convergence event that jumped implausibly —
# the silent-data-corruption fingerprint (a flipped bit in w/r/p, or a
# corrupted stencil application). The iterate is SUSPECT, not NaN: the
# recovery driver restarts from the last *verified-good* snapshot
# instead of escalating precision, and the solve service types it as an
# ``integrity`` error class with suspect-cohort taint.
FLAG_INTEGRITY = 6   # verification probe detected silent corruption

FLAG_NAMES = {
    FLAG_NONE: "running",
    FLAG_CONVERGED: "converged",
    FLAG_BREAKDOWN: "breakdown",
    FLAG_NONFINITE: "nonfinite",
    FLAG_STAGNATED: "stagnated",
    FLAG_DEADLINE: "deadline",
    FLAG_INTEGRITY: "integrity",
}


class PCGOps(NamedTuple):
    """Backend bundle consumed by the shared PCG loop.

    apply_A:   p (halo-fresh) → Ap, zero outside owned interior
    apply_Dinv: r → D⁻¹r, zero outside owned interior
    dot:       (u, v) → *global* weighted inner product h1·h2·Σ u·v
    sqnorm:    u → *global* Σ_interior u², unweighted (the convergence sum;
               weighting applied by the loop per Problem.weighted_norm)
    exchange:  p → p with refreshed halos (identity on a single device)
    """

    apply_A: Callable
    apply_Dinv: Callable
    dot: Callable
    sqnorm: Callable
    exchange: Callable


class PCGState(NamedTuple):
    """Loop state. The trailing three fields default so solvers that carry
    their own state types (the fused pallas paths) can build the portable
    checkpoint state without tracking them."""

    k: jnp.ndarray        # iterations completed (reference's `iter`)
    done: jnp.ndarray     # converged, degenerate, or diverged
    w: jnp.ndarray
    r: jnp.ndarray
    z: jnp.ndarray
    p: jnp.ndarray
    zr: jnp.ndarray       # ζ = (z, r)
    diff: jnp.ndarray     # last ‖w(k+1)−w(k)‖
    flag: jnp.ndarray = np.int32(FLAG_NONE)   # termination verdict
    best: jnp.ndarray = np.inf                # best ‖Δw‖ seen so far
    stall: jnp.ndarray = np.int32(0)          # iterations since best improved


class PCGResult(NamedTuple):
    """Solve result. Scalar solves fill the historical scalar fields; the
    batched driver (``solvers.batched``) returns the SAME type with a
    leading batch axis on ``w``/``iterations``/``diff``/``residual_dot``/
    ``flag`` — ``iterations`` is then the per-member truth (a vector), and
    ``max_iterations`` carries the scalar the wall clock actually paid for
    (the fused loop runs until the slowest member stops)."""

    w: jnp.ndarray           # full (…, M+1, N+1) solution grid(s)
    iterations: jnp.ndarray  # per-solve count; vector on batched results
    diff: jnp.ndarray        # final update norm
    residual_dot: jnp.ndarray  # final ζ = (D⁻¹r, r)
    flag: jnp.ndarray = np.int32(FLAG_NONE)  # termination verdict (FLAG_*)
    # Recovery provenance, set by the resilient driver on host-side
    # results only (None/() are empty pytree nodes, so jitted solvers
    # returning the defaults stay valid jit outputs). A solve that
    # recovered and then converged is no longer silent about it.
    restarts: object = None            # int: recovery attempts taken
    recovery_history: tuple = ()       # ((iteration, verdict, action), …)
    # Batched solves only: scalar max over the member iteration vector
    # (None on scalar solves, an empty pytree node under jit).
    max_iterations: object = None
    # Batched solves only: per-member origin identities (a tuple aligned
    # with the leading batch axis, padding members already sliced off).
    # Defaults to (0, 1, …, B−1); the solve service passes request ids so
    # a member re-enqueued into a different bucket keeps its identity.
    # Host-side metadata (ints/strings, not traced arrays).
    origin: object = None
    # Block-mode solves only (poisson_tpu.krylov.block): scalar bool —
    # the B×B coefficient solves truncated a rank-deficient direction
    # at some iteration (graceful degradation, not a failure; the
    # service counts it as ``krylov.block.rank_deficient``). None (an
    # empty pytree node) on every other solver's results.
    deficient: object = None


def iterations_scalar(iterations) -> int:
    """Collapse an ``iterations`` field to one honest scalar: the value
    itself for scalar solves, the max over members for batched vectors —
    the iteration count the fused loop actually ran (and the wall clock
    paid for), which is what every report line historically meant."""
    arr = np.asarray(iterations)
    return int(arr.max()) if arr.ndim else int(arr)


def _select(pred, new, old):
    return jax.tree_util.tree_map(
        lambda n, o: lax.select(jnp.broadcast_to(pred, n.shape), n, o), new, old
    )


def init_state(ops: PCGOps, rhs) -> PCGState:
    """w=0, r=B, z=D⁻¹r, p=z, ζ=(z,r)  (stage2:…cpp:384-396)."""
    w = jnp.zeros_like(rhs)
    r = rhs
    z = ops.apply_Dinv(r)
    p = z
    zr = ops.dot(z, r)
    return PCGState(
        k=jnp.zeros((), jnp.int32),
        done=jnp.asarray(False),
        w=w, r=r, z=z, p=p, zr=zr,
        diff=jnp.asarray(jnp.inf, rhs.dtype),
        flag=jnp.asarray(FLAG_NONE, jnp.int32),
        best=jnp.asarray(jnp.inf, rhs.dtype),
        stall=jnp.zeros((), jnp.int32),
    )


def restart_state(ops: PCGOps, rhs, w) -> PCGState:
    """Fresh CG restart from an existing iterate: r = B − Aw, z = M⁻¹r,
    p = z. The recovery driver (``solvers.resilient``) uses this to resume
    from the last good iterate after a divergence — the Krylov history is
    discarded (it is what went bad), the accumulated solution is kept.

    Constructed directly rather than via ``init_state(ops, rhs)``: the
    init's own ``z = M⁻¹·rhs`` would be computed only to be thrown away
    by the restart's replacements — harmless when M⁻¹ is the elementwise
    Jacobi diagonal, a full wasted (and eagerly dispatched) V-cycle when
    it is the MG preconditioner (``poisson_tpu.mg``)."""
    r = rhs - ops.apply_A(ops.exchange(w))
    z = ops.apply_Dinv(r)
    zr = ops.dot(z, r)
    return PCGState(
        k=jnp.zeros((), jnp.int32),
        done=jnp.asarray(False),
        w=w, r=r, z=z, p=z, zr=zr,
        diff=jnp.asarray(jnp.inf, rhs.dtype),
        flag=jnp.asarray(FLAG_NONE, jnp.int32),
        best=jnp.asarray(jnp.inf, rhs.dtype),
        stall=jnp.zeros((), jnp.int32),
    )


def make_pcg_member_body(ops: PCGOps, *, delta: float, weighted_norm: bool,
                         h1: float, h2: float, stagnation_window: int = 0,
                         stream_every: int = 0, verify_every: int = 0,
                         verify_tol: float = 0.0,
                         verify_jump: Optional[float] = None,
                         verify_colsum=None,
                         preconditioner: str = "jacobi",
                         history_every: int = 0):
    """The PCG iteration as a ``body(state, rhs) -> state`` pair-form —
    the verification-capable core :func:`make_pcg_body` wraps. The
    second argument is ONLY read when ``verify_every > 0`` (the in-loop
    integrity probe needs the RHS to recompute the true residual); the
    batched/lane drivers vmap this form with ``in_axes=(0, 0)`` so each
    member's probe checks its OWN right-hand side and only the
    corrupted member trips FLAG_INTEGRITY.

    With ``verify_every == 0`` (the default) no probe is traced and the
    body is the exact historical iteration — byte-identical HLO, golden
    iteration counts bit-for-bit (pinned by tests/test_integrity.py).

    When verifying, every ``verify_every``-th iteration AND every
    convergence event runs the residual-drift invariant
    (``poisson_tpu.integrity.probe``): ``‖(b − Aw) − r‖`` beyond
    ``verify_tol`` relative to the residual/RHS scale stamps
    FLAG_INTEGRITY and stops the member. A convergence whose previous
    best ‖Δw‖ sat more than ``verify_jump`` (default
    ``integrity.DEFAULT_VERIFY_JUMP``) above this step's own ‖Δw‖ is
    classified corrupt too, as is a one-step ‖Δw‖ collapse beyond
    ``integrity.DEFAULT_VERIFY_COLLAPSE`` without converging — the two
    faces of a flipped search direction, which keeps the recurrence
    consistent and is invisible to the drift check. ``verify_colsum``
    (the precomputed ``A·𝟙``) additionally enables the checksum-row
    ABFT identity on the stencil application at each probe.
    """
    if verify_every > 0:
        from poisson_tpu.integrity.probe import (
            default_verify_collapse,
            default_verify_jump,
        )

        # The update-norm guard ratios are PRECONDITIONER-specific:
        # MG-preconditioned CG legitimately contracts ‖Δw‖ several-fold
        # per iteration, so the Jacobi-calibrated ratios would false-
        # alarm on clean MG solves (measured — see integrity.probe).
        if verify_jump is None:
            verify_jump = default_verify_jump(preconditioner)
        verify_collapse = default_verify_collapse(preconditioner)

    def body(s: PCGState, vrhs=None) -> PCGState:
        p = ops.exchange(s.p)
        Ap = ops.apply_A(p)
        denom = ops.dot(Ap, p)
        degenerate = jnp.abs(denom) < _DENOM_TOL
        alpha = s.zr / jnp.where(degenerate, 1.0, denom)

        dw = alpha * p
        w_new = s.w + dw
        r_new = s.r - alpha * Ap
        sq = ops.sqnorm(dw)
        diff = jnp.sqrt(sq * (h1 * h2)) if weighted_norm else jnp.sqrt(sq)

        z_new = ops.apply_Dinv(r_new)
        zr_new = ops.dot(z_new, r_new)
        converged = diff < delta

        if stream_every > 0:
            from poisson_tpu.obs.stream import emit_every

            emit_every(stream_every, s.k + 1, diff)

        if history_every > 0:
            from poisson_tpu.obs.forecast import emit_history

            emit_history(history_every, s.k + 1, diff)

        beta = zr_new / jnp.where(s.zr == 0.0, 1.0, s.zr)
        p_new = z_new + beta * p

        # In-loop health classification. NaN/Inf anywhere in the scalars
        # poisons every later iterate, so stopping is strictly better than
        # looping to the cap; a converged verdict requires finite scalars
        # (NaN < δ is False anyway, but be explicit about precedence).
        nonfinite = ~(jnp.isfinite(diff) & jnp.isfinite(zr_new))
        improved = diff < s.best
        best_new = jnp.minimum(s.best, diff)
        stall_new = jnp.where(improved, 0, s.stall + 1).astype(jnp.int32)
        if stagnation_window > 0:
            stagnated = (~converged) & (stall_new >= stagnation_window)
        else:
            stagnated = jnp.asarray(False)
        if verify_every > 0:
            # The integrity probe: due every verify_every iterations and
            # on every convergence event (a corrupted solve must never
            # hand out a "converged" iterate unverified). lax.cond keeps
            # the extra stencil application off the non-probe
            # iterations; the probe only READS — clean solves keep
            # their golden iteration counts (iterates agree with the
            # unverified program to round-off: the probe's presence can
            # shift XLA's fusion choices by an ULP).
            from poisson_tpu.integrity.probe import (
                abft_drift_exceeds,
                drift_exceeds,
            )

            due = (((s.k + 1) % verify_every) == 0) | converged

            def _probe():
                bad = drift_exceeds(ops, w_new, r_new, vrhs, verify_tol)
                if verify_colsum is not None:
                    bad = bad | abft_drift_exceeds(verify_colsum, p, Ap,
                                                   verify_tol)
                return bad

            corrupt = lax.cond(due, _probe,
                               lambda: jnp.zeros_like(converged))
            # The false-convergence jump guard: genuine update-norm
            # convergence is gradual (the best ‖Δw‖ approaches δ before
            # crossing it, so the final step's ratio is single digits);
            # a convergence whose previous best sat ``verify_jump``
            # times above THIS step's ‖Δw‖ is a collapsed α from a
            # corrupted search direction. Ratio against diff, not δ: a
            # flip late in the solve collapses from wherever best was,
            # which an absolute δ-multiple would miss. isfinite(best)
            # exempts a legitimate first-iteration convergence (best
            # still ∞).
            suspicious = (converged & jnp.isfinite(s.best)
                          & (s.best > verify_jump * diff))
            # The mid-solve collapse guard: the SAME flipped-direction
            # physics when the collapsed ‖Δw‖ lands ABOVE δ — no
            # convergence event, so the jump guard never looks, and the
            # recurrence stays consistent, so the drift probe is blind
            # in principle. A one-step drop beyond verify_collapse
            # (clean CG measures ≤ 1.4×; the flip's gain factor is
            # ×2¹⁶ and up) is corruption. isfinite(s.diff) exempts the
            # first iteration after init/restart (diff starts at ∞).
            collapsed = ((~converged) & jnp.isfinite(s.diff)
                         & (s.diff > verify_collapse * diff))
            corrupt = (corrupt | suspicious | collapsed) & ~nonfinite
            # A corrupt verdict freezes the member; keep the PRE-flip
            # best so the recovery driver's recheck can reproduce the
            # jump condition (the collapsed diff would otherwise have
            # just overwritten its own evidence) and so a false-alarm
            # resume keeps the honest progress floor.
            best_new = jnp.where(corrupt, s.best, best_new)
            flag = jnp.where(
                nonfinite, FLAG_NONFINITE,
                jnp.where(corrupt, FLAG_INTEGRITY,
                          jnp.where(converged, FLAG_CONVERGED,
                                    jnp.where(stagnated, FLAG_STAGNATED,
                                              FLAG_NONE))),
            ).astype(jnp.int32)
            stop = (degenerate | converged | nonfinite | stagnated
                    | corrupt)
        else:
            flag = jnp.where(
                nonfinite, FLAG_NONFINITE,
                jnp.where(converged, FLAG_CONVERGED,
                          jnp.where(stagnated, FLAG_STAGNATED, FLAG_NONE)),
            ).astype(jnp.int32)
            stop = degenerate | converged | nonfinite | stagnated

        # Degenerate break happens before any update (stage2:…cpp:410-415):
        # keep the old state entirely. Convergence break keeps this
        # iteration's w/r/z updates (p is then irrelevant).
        candidate = PCGState(
            k=s.k + 1,
            done=stop,
            w=w_new, r=r_new, z=z_new, p=p_new,
            zr=zr_new, diff=diff,
            flag=flag, best=best_new, stall=stall_new,
        )
        kept = s._replace(
            k=s.k + 1, done=jnp.asarray(True),
            flag=jnp.asarray(FLAG_BREAKDOWN, jnp.int32),
        )
        return _select(degenerate, kept, candidate)

    return body


def make_pcg_body(ops: PCGOps, *, delta: float, weighted_norm: bool,
                  h1: float, h2: float, stagnation_window: int = 0,
                  stream_every: int = 0, verify_every: int = 0,
                  verify_tol: float = 0.0,
                  verify_jump: Optional[float] = None,
                  verify_rhs=None, verify_colsum=None,
                  preconditioner: str = "jacobi",
                  history_every: int = 0):
    """One PCG iteration as a pure state→state function — shared by the
    convergence ``while_loop`` (:func:`pcg_loop`) and the fixed-budget
    diagnostic ``scan`` (``solvers.history``).

    Every iteration classifies its own outcome into ``flag`` so a failing
    solve stops at the iteration that went bad instead of burning the rest
    of its budget on NaNs: a non-finite residual/update norm sets
    FLAG_NONFINITE, the degenerate-direction break FLAG_BREAKDOWN, and —
    when ``stagnation_window`` > 0 — ``stagnation_window`` consecutive
    iterations without a new best ‖Δw‖ set FLAG_STAGNATED. The checks only
    ever stop iterations that could no longer converge, so converging
    solves keep their golden iteration counts bit-for-bit.

    ``stream_every`` > 0 additionally ships (k, ‖Δw‖) to the host-side
    telemetry sink every that many iterations (``obs.stream``) via an
    unordered ``jax.debug.callback`` — progress visibility out of the
    fused loop. It is a trace-time constant: at the default 0 no
    callback exists in the program and the iterations are untouched.

    ``verify_every`` > 0 threads the in-loop integrity probe
    (``poisson_tpu.integrity``) into the body against ``verify_rhs``
    (the RHS this state's true residual is checked against — required
    when verifying); a detected drift stamps FLAG_INTEGRITY. Like
    ``stream_every`` it is a trace-time constant: at the default 0 the
    body is the exact historical program, byte-identical HLO. See
    :func:`make_pcg_member_body` for the semantics (and for the
    ``body(state, rhs)`` pair form the batched drivers vmap).

    ``history_every`` > 0 ships (k, ‖Δw‖) to the forecast history sink
    (``obs.forecast``) every that many iterations — the mid-flight
    convergence-rate seam. Identical trace-time-constant contract:
    at the default 0 no callback is traced and the program is
    byte-identical."""
    if verify_every > 0 and verify_rhs is None:
        raise ValueError(
            "verify_every > 0 needs verify_rhs — the in-loop integrity "
            "probe recomputes the true residual b - Aw against it"
        )
    member = make_pcg_member_body(
        ops, delta=delta, weighted_norm=weighted_norm, h1=h1, h2=h2,
        stagnation_window=stagnation_window, stream_every=stream_every,
        verify_every=verify_every, verify_tol=verify_tol,
        verify_jump=verify_jump, verify_colsum=verify_colsum,
        preconditioner=preconditioner, history_every=history_every,
    )
    if verify_every == 0:
        return member     # vrhs defaults to None and is never read
    return lambda s: member(s, verify_rhs)


def pcg_loop(ops: PCGOps, rhs, *, delta: float, max_iter: int,
             weighted_norm: bool, h1: float, h2: float,
             stagnation_window: int = 0, stream_every: int = 0,
             verify_every: int = 0, verify_tol: float = 0.0,
             verify_abft: bool = False,
             preconditioner: str = "jacobi",
             history_every: int = 0) -> PCGState:
    """Run the PCG while_loop to convergence; backend-agnostic.
    ``verify_every``/``verify_tol`` arm the in-loop integrity probe
    against this solve's own RHS; ``verify_abft`` additionally traces
    the checksum-row ABFT identity (the column-sum vector is computed
    once here, outside the loop)."""
    colsum = None
    if verify_every > 0 and verify_abft:
        from poisson_tpu.integrity.probe import abft_colsum

        colsum = abft_colsum(ops, rhs)
    body = make_pcg_body(
        ops, delta=delta, weighted_norm=weighted_norm, h1=h1, h2=h2,
        stagnation_window=stagnation_window, stream_every=stream_every,
        verify_every=verify_every, verify_tol=verify_tol,
        verify_rhs=(rhs if verify_every > 0 else None),
        verify_colsum=colsum, preconditioner=preconditioner,
        history_every=history_every,
    )

    def cond(s: PCGState):
        return (~s.done) & (s.k < max_iter)

    return lax.while_loop(cond, body, init_state(ops, rhs))


def single_device_ops(problem: Problem, a, b, aux) -> PCGOps:
    """Stage0/stage1-equivalent backend: whole grid on one device.

    ``aux`` is the Jacobi diagonal embedded in the full grid's zero ring —
    the same full-grid layout ``scaled_single_device_ops`` takes, so both
    backends consume :func:`host_setup`'s aux unchanged.

    Every op accepts leading batch axes (the ``ops.stencil`` convention):
    reductions sum only the trailing grid axes, so a (B, M+1, N+1) state
    stack gets per-member dots/norms — usable either directly or under
    ``vmap`` (the batched driver, ``solvers.batched``). a/b/aux may
    themselves carry leading batch axes (per-member geometry canvases,
    ``poisson_tpu.geometry``)."""
    h1, h2 = problem.h1, problem.h2
    # ndim dispatch like ops.stencil._cslice: 2D aux keeps the literal
    # historical slice (unbatched jaxpr unchanged); stacked aux
    # (per-member geometry diagonals) slices under an Ellipsis.
    d = aux[1:-1, 1:-1] if aux.ndim == 2 else aux[..., 1:-1, 1:-1]
    return PCGOps(
        apply_A=lambda p: apply_A(p, a, b, h1, h2),
        apply_Dinv=lambda r: apply_Dinv(r, d),
        dot=lambda u, v: dot_weighted(u, v, h1, h2),
        sqnorm=lambda u: jnp.sum(
            u[..., 1:-1, 1:-1] * u[..., 1:-1, 1:-1], axis=(-2, -1)
        ),
        exchange=lambda p: p,
    )


def scaled_single_device_ops(problem: Problem, a, b, sc) -> PCGOps:
    """Symmetrically-scaled backend: plain CG on Ã = D^{-1/2} A D^{-1/2}.

    Mathematically identical to Jacobi-PCG on A (same iterates under the
    substitution y = D^{1/2}w, z = D⁻¹r ↔ r̃, (z,r) = (r̃,r̃)), but the scaled
    operator has unit diagonal and O(1) entries, collapsing the ~1/ε·h⁻²
    dynamic range of the fictitious-domain matrix. This is what makes fp32
    viable on TPU: unscaled fp32 diverges at 800×1200 (κ ~ 1e11), scaled
    fp32 reproduces the fp64 golden iteration counts exactly.

    ``sc`` is D^{-1/2} on the full grid (zero ring). The preconditioner
    becomes the identity; the convergence norm is mapped back to w-space via
    ‖Δw‖ = ‖sc·Δy‖; the caller maps the solution back with w = sc·y.
    Batch-polymorphic like :func:`single_device_ops` (sc broadcasts over
    leading axes; reductions are per-member).
    """
    h1, h2 = problem.h1, problem.h2
    return PCGOps(
        apply_A=lambda p: apply_A(p * sc, a, b, h1, h2) * sc,
        apply_Dinv=lambda r: r,
        dot=lambda u, v: dot_weighted(u, v, h1, h2),
        sqnorm=lambda u: jnp.sum((u * sc)[..., 1:-1, 1:-1] ** 2,
                                 axis=(-2, -1)),
        exchange=lambda p: p,
    )


@functools.lru_cache(maxsize=8)
def host_fields64(problem: Problem, scaled: bool):
    """Build the problem fields on the host in fp64 (numpy) — the single
    source of the precision policy's setup derivation, shared by the
    single-device and sharded solvers.

    The reference also runs setup on the CPU (even in the CUDA stage,
    ``stage4:…cu:717``). Doing it in numpy fp64 keeps setup precision
    independent of the device's x64 support: on TPU the solver state may be
    fp32 while coefficients, the Jacobi diagonal, and the scaling vector are
    derived in fp64 and cast once.

    Returns (a, b, rhs_use, aux) as fp64 numpy arrays on the full (M+1,N+1)
    grid; ``aux`` is the zero-ring embedding of D (unscaled) or of
    D^{-1/2} (scaled), and ``rhs_use`` is B or the scaled b̃ = D^{-1/2}B.
    """
    import numpy as np

    a64, b64, rhs64 = build_fields(problem, dtype=np.float64, xp=np)
    d64 = diag_D(a64, b64, problem.h1, problem.h2)
    if not scaled:
        return a64, b64, rhs64, np.pad(d64, 1)
    inv_sqrt_d = 1.0 / np.sqrt(d64)
    return a64, b64, np.pad(rhs64[1:-1, 1:-1] * inv_sqrt_d, 1), np.pad(
        inv_sqrt_d, 1
    )


@functools.lru_cache(maxsize=8)
def host_setup(problem: Problem, dtype_name: str, scaled: bool):
    """Device-resident fields cast from :func:`host_fields64`. Cached so
    repeated solves of the same problem (e.g. a benchmark's timed loop) pay
    for setup and transfer once."""
    dtype = jnp.dtype(dtype_name)
    a64, b64, rhs64, aux64 = host_fields64(problem, scaled)
    return (
        jnp.asarray(a64, dtype),
        jnp.asarray(b64, dtype),
        jnp.asarray(rhs64, dtype),
        jnp.asarray(aux64, dtype),
    )


def solve_setup(problem: Problem, dtype_name: str, scaled: bool,
                geometry=None):
    """The one setup seam every solver entry point routes through:
    ``geometry=None`` is :func:`host_setup` (the reference ellipse,
    byte-identical arrays to every prior release); a geometry spec swaps
    in the fingerprint-cached canvases of ``geometry.canvas`` — same
    shapes, same dtype, same (a, b, rhs, aux) contract, so the jitted
    solve programs are shared across domains (the canvases are operands,
    never part of the jit key)."""
    if geometry is None:
        return host_setup(problem, dtype_name, scaled)
    from poisson_tpu.geometry.canvas import geometry_setup

    return geometry_setup(problem, geometry, dtype_name, scaled)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _solve(problem: Problem, scaled: bool, stream_every: int,
           verify_every: int, verify_tol: float, verify_abft: bool,
           history_every: int,
           a, b, rhs, aux) -> PCGResult:
    """jitted solve; ``aux`` is the zero-ring-embedded D (unscaled) or
    D^{-1/2} (scaled) on the full grid. ``stream_every`` is the static
    telemetry stride (0 = no callback traced in — see ``obs.stream``);
    ``verify_every``/``verify_tol``/``verify_abft`` are the static
    integrity-probe knobs (0 = no probe traced in — see
    ``poisson_tpu.integrity``); ``history_every`` is the static
    forecast-history stride (0 = no callback traced in — see
    ``obs.forecast``). All strides are part of the compile cache key,
    so flag-off programs are the exact historical executables."""
    ops = (
        scaled_single_device_ops(problem, a, b, aux)
        if scaled
        else single_device_ops(problem, a, b, aux)
    )
    s = pcg_loop(
        ops, rhs,
        delta=problem.delta, max_iter=problem.iteration_cap,
        weighted_norm=problem.weighted_norm,
        h1=problem.h1, h2=problem.h2,
        stream_every=stream_every,
        verify_every=verify_every, verify_tol=verify_tol,
        verify_abft=verify_abft,
        history_every=history_every,
    )
    w = s.w * aux if scaled else s.w
    return PCGResult(w=w, iterations=s.k, diff=s.diff, residual_dot=s.zr,
                     flag=s.flag)


def resolve_dtype(dtype) -> str:
    """Resolve the requested precision, refusing a silent fp64→fp32 downcast.

    JAX downcasts float64 arrays to float32 unless ``jax_enable_x64`` is on;
    an explicit fp64 request would then quietly run fp32 against δ=1e-6 and
    miss the golden iteration counts. ``None`` picks the best available.
    """
    if dtype is None:
        return "float64" if jax.config.jax_enable_x64 else "float32"
    name = jnp.dtype(dtype).name
    if name == "float64" and not jax.config.jax_enable_x64:
        raise ValueError(
            "float64 requested but jax_enable_x64 is off — the solve would "
            "silently run in float32. Call "
            "jax.config.update('jax_enable_x64', True) first, or pass an "
            "explicit 32-bit dtype."
        )
    return name


def resolve_scaled(scaled, dtype_name: str) -> bool:
    """Default precision policy: sub-64-bit state uses the symmetrically
    scaled system (required for correctness at fine grids); fp64 runs the
    reference's literal Jacobi-PCG for oracle parity."""
    if scaled is None:
        return dtype_name != "float64"
    return bool(scaled)


def resolve_verify_tol(verify_tol, dtype_name: str) -> float:
    """The integrity probe's relative drift tolerance: the caller's
    explicit value, else the dtype-aware default
    (``integrity.probe.default_verify_tol`` — sized for zero false
    alarms on clean golden solves while exponent-class corruption lands
    orders of magnitude above the line)."""
    if verify_tol is not None:
        return float(verify_tol)
    from poisson_tpu.integrity.probe import default_verify_tol

    return default_verify_tol(dtype_name)


def pcg_solve(problem: Problem, dtype=None, scaled=None,
              rhs_gate=None, stream_every: int = 0,
              geometry=None, verify_every: int = 0,
              verify_tol=None, verify_abft: bool = False,
              preconditioner: str = "jacobi",
              mg_config=None, history_every: int = 0,
              mesh=None) -> PCGResult:
    """Single-device solve (the stage0/stage1 workload, SURVEY §3.1), or
    the MG solve split over a device mesh.

    The iteration is jit-compiled end to end; setup runs on the host in fp64
    (see :func:`host_setup`). ``dtype`` selects the state precision (fp64 for
    oracle parity on CPU, fp32 for TPU throughput; default: fp64 when x64 is
    enabled, else fp32). ``scaled`` selects symmetric diagonal scaling
    (default: on for sub-64-bit dtypes — see :func:`scaled_single_device_ops`).
    ``rhs_gate``, if given, is a traced scalar the RHS is multiplied by —
    pass exactly 1.0 to chain benchmark solves with a data dependency
    (serialized, bit-identical result). ``stream_every`` > 0 streams
    (k, ‖Δw‖) to the telemetry sink every that many iterations
    (``obs.stream``; 0 = off, the program is byte-identical).
    ``geometry`` swaps the reference ellipse for any
    :mod:`poisson_tpu.geometry` spec (same grid, same compiled program —
    only the coefficient canvases change; fingerprint-cached, see
    ``geom.cache.*``). Omitted, the solve is byte-identical to every
    prior release.

    ``verify_every`` > 0 arms the in-loop integrity probe
    (``poisson_tpu.integrity``): every that many iterations (and on
    every convergence event) the loop recomputes the true residual and
    stops the solve with ``flag == FLAG_INTEGRITY`` when it drifts from
    the recurrence beyond ``verify_tol`` (default: dtype-aware) —
    silent-data-corruption detection for one extra stencil application
    per check. ``verify_abft`` adds the checksum-row ABFT identity on
    the stencil application. At 0 (the default) no probe is traced:
    byte-identical program, bit-for-bit golden counts.

    ``preconditioner`` selects the M⁻¹ the CG recurrence runs with:
    ``"jacobi"`` (the default) is the historical diagonal path —
    byte-identical executables, golden counts bit-for-bit;
    ``"mg"`` swaps in one geometric V-cycle per iteration
    (:mod:`poisson_tpu.mg` — near-flat iteration counts in resolution;
    the grid must coarsen, see ``mg.validate_mg_problem``).
    ``mg_config`` tunes the cycle (``mg.MGConfig``; None = defaults).

    ``history_every`` > 0 ships (k, ‖Δw‖) to the forecast history sink
    (``obs.forecast``) every that many iterations — the mid-flight
    convergence-rate seam the ETA estimator reads. Same trace-time
    contract as ``stream_every``: 0 (the default) traces no callback
    and the program is byte-identical.

    An MG solve sets the gauge ``mg.pallas_levels``: how many levels of
    its cycle run on the Pallas strip kernels (``ops.pallas_mg``), on
    one device or, over a mesh, on the shards' blocks.

    ``mesh`` (a ``parallel.make_solver_mesh`` mesh; MG only) splits the
    MG solve over its devices: one ``shard_map`` program whose cycle is
    sharded down to its replication level and whole on every device
    below (``parallel.mg_sharded``; the gauge ``mg.replicated_from``
    holds that level). Its set-up builds each shard's fields on the host
    in fp64 and places them on their own device; the grid must split
    into even blocks (``parallel.mg_sharded.plan_mesh``). Streaming,
    verification, history and geometries are not wired there.

    Runs under the span ``pcg_solve`` with the children ``.prepare``
    (checks, set-up and hierarchy cache lookups, gate multiply),
    ``.launch`` (the jitted call) and ``.finish`` (the ``mg.solves``
    count): see :func:`poisson_tpu.obs.span`.
    """
    dtype_name = resolve_dtype(dtype)
    use_scaled = resolve_scaled(scaled, dtype_name)
    verify_every = int(verify_every)
    history_every = int(history_every)
    tol = (resolve_verify_tol(verify_tol, dtype_name)
           if verify_every > 0 else 0.0)
    use_mg = preconditioner not in (None, "jacobi")
    if mesh is not None and not use_mg:
        raise ValueError(
            "pcg_solve(mesh=...) runs the MG solve over a mesh; the "
            "Jacobi solve over a mesh is parallel.pcg_solve_sharded")
    with obs.span("pcg_solve"):
        with obs.span("pcg_solve.prepare"):
            if mesh is not None:
                cfg, plan, (hier, rhs, aux) = _mg_mesh_prepare(
                    problem, dtype_name, use_scaled, mesh, geometry,
                    preconditioner, mg_config, stream_every, verify_every,
                    history_every)
                obs.gauge("mg.pallas_levels", len(hier.strips))
                obs.gauge("mg.replicated_from", plan.replicated_from)
            elif use_mg:
                cfg, (a, b, rhs, aux, hier) = _mg_prepare(
                    problem, dtype_name, use_scaled, geometry,
                    preconditioner, mg_config, verify_abft, history_every)
                obs.gauge("mg.pallas_levels", len(hier.strips))
            else:
                a, b, rhs, aux = solve_setup(problem, dtype_name,
                                             use_scaled, geometry=geometry)
            if rhs_gate is not None:
                rhs = rhs * jnp.asarray(rhs_gate, rhs.dtype)
        with obs.span("pcg_solve.launch"):
            if mesh is not None:
                from poisson_tpu.parallel.mg_sharded import (
                    _solve_mg_sharded,
                )

                result = _solve_mg_sharded(
                    problem, mesh, plan, cfg, use_scaled, hier, rhs, aux,
                    interpret=jax.devices()[0].platform != "tpu")
            elif use_mg:
                from poisson_tpu.mg.preconditioner import _solve_mg

                result = _solve_mg(
                    problem, use_scaled, cfg, int(stream_every),
                    verify_every, tol, a, b, rhs, aux, hier,
                    interpret=jax.devices()[0].platform != "tpu")
            else:
                result = _solve(problem, use_scaled, int(stream_every),
                                verify_every, tol,
                                bool(verify_abft and verify_every > 0),
                                history_every, a, b, rhs, aux)
        with obs.span("pcg_solve.finish"):
            if use_mg:
                obs.inc("mg.solves")
    return result


def _mg_prepare(problem: Problem, dtype_name: str, use_scaled: bool,
                geometry, preconditioner, mg_config, verify_abft: bool,
                history_every: int):
    """(cycle config, (a, b, rhs, aux, hierarchy)) of an MG solve, after
    the checks that refuse what the MG path does not wire."""
    from poisson_tpu.mg import (
        DEFAULT_MG,
        resolve_preconditioner,
        validate_mg_problem,
    )
    from poisson_tpu.mg.preconditioner import mg_solve_setup

    resolve_preconditioner(preconditioner)   # raises on unknown
    cfg = mg_config or DEFAULT_MG
    validate_mg_problem(problem, cfg)
    if verify_abft:
        raise ValueError(
            "verify_abft is wired for the jacobi path only; drop it "
            "or use preconditioner='jacobi'"
        )
    if history_every > 0:
        raise ValueError(
            "history_every is wired for the jacobi path only; drop "
            "it or use preconditioner='jacobi'"
        )
    return cfg, mg_solve_setup(problem, dtype_name, use_scaled,
                               geometry=geometry, config=cfg)


def _mg_mesh_prepare(problem: Problem, dtype_name: str, use_scaled: bool,
                     mesh, geometry, preconditioner, mg_config,
                     stream_every: int, verify_every: int,
                     history_every: int):
    """(cycle config, mesh plan, (hierarchy, rhs, aux)) of an MG solve
    over ``mesh``, after the checks that refuse what that path does not
    wire."""
    from poisson_tpu.mg import DEFAULT_MG, resolve_preconditioner
    from poisson_tpu.parallel.mg_sharded import mesh_setup

    resolve_preconditioner(preconditioner)   # raises on unknown
    unwired = [name for name, on in (
        ("geometry", geometry is not None),
        ("stream_every", stream_every), ("verify_every", verify_every),
        ("history_every", history_every)) if on]
    if unwired:
        raise ValueError(
            f"{', '.join(unwired)} is not wired for the MG solve over a "
            "mesh; drop it or solve on one device")
    cfg = mg_config or DEFAULT_MG
    plan, fields = mesh_setup(problem, dtype_name, use_scaled, mesh, cfg)
    return cfg, plan, fields


def iteration_program(problem: Problem, dtype=None, scaled=None,
                      preconditioner: str = "jacobi"):
    """The one-iteration PCG body as a (jittable fn, example state) pair
    — the per-iteration cost-attribution anchor (``obs.costs``).

    XLA's HLO cost analysis counts a ``while_loop`` body once regardless
    of trip count, so per-iteration FLOPs/bytes can only be read off a
    compiled executable by compiling the body alone; this packages
    exactly the body :func:`pcg_loop` runs (same ops bundle, same
    coefficient closure, so the compiled program's operand traffic is
    the solve's per-iteration truth). Precision/scaling policy matches
    :func:`pcg_solve`.
    """
    dtype_name = resolve_dtype(dtype)
    use_scaled = resolve_scaled(scaled, dtype_name)
    a, b, rhs, aux = host_setup(problem, dtype_name, use_scaled)
    if preconditioner not in (None, "jacobi"):
        # The MG iteration body: the same loop body with one V-cycle in
        # apply_Dinv — the per-iteration cost anchor the bytes/iter
        # model for MG cohorts (obs.costs.mg_vcycle_cost) is checked
        # against.
        from poisson_tpu.mg import (
            DEFAULT_MG,
            device_hierarchy,
            resolve_preconditioner,
        )
        from poisson_tpu.mg.preconditioner import mg_ops

        resolve_preconditioner(preconditioner)
        hier = device_hierarchy(problem, dtype_name, use_scaled)
        ops = mg_ops(problem, a, b, aux, hier, DEFAULT_MG, use_scaled)
    else:
        ops = (
            scaled_single_device_ops(problem, a, b, aux)
            if use_scaled
            else single_device_ops(problem, a, b, aux)
        )
    body = make_pcg_body(
        ops, delta=problem.delta, weighted_norm=problem.weighted_norm,
        h1=problem.h1, h2=problem.h2,
    )
    return body, init_state(ops, rhs)


def pcg_step_fn(problem: Problem, scaled: bool = True):
    """One fused PCG iteration for the flagship single-device problem —
    the jittable 'forward step' exposed to the harness (__graft_entry__).
    ``aux`` is D (unscaled) or D^{-1/2} on the grid (scaled), matching
    :func:`host_setup`. Assumes a non-degenerate search direction (driven
    pre-convergence; the full loop adds the |denom| guard)."""

    def step(w, r, z, p, zr, a, b, aux):
        ops = (
            scaled_single_device_ops(problem, a, b, aux)
            if scaled
            else single_device_ops(problem, a, b, aux)
        )
        Ap = ops.apply_A(p)
        denom = ops.dot(Ap, p)
        alpha = zr / denom
        w = w + alpha * p
        r = r - alpha * Ap
        z = ops.apply_Dinv(r)
        zr_new = ops.dot(z, r)
        beta = zr_new / zr
        p = z + beta * p
        return w, r, z, p, zr_new

    return step
