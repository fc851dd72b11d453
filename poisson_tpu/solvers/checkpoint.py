"""Checkpoint/resume for long solves.

The reference has no checkpointing (SURVEY §5: a solve runs to convergence
in one shot and the solution never touches disk). At pod scale a preempted
job restarts from iteration zero, so this framework adds the missing
subsystem: the solve runs as fixed-size chunks of the shared PCG body, and
after each chunk the five-array CG state (w, r, z, p, ζ) plus iteration
counter is persisted. A restart with the same problem resumes from the
last chunk boundary and converges to the same answer — CG's iterate
sequence is a pure function of its state, so chunked and one-shot solves
are identical to round-off.

Format: a single ``.npz`` (numpy, host-side) with a problem fingerprint;
a mismatched fingerprint refuses to resume rather than silently solving a
different problem.

Hardening (this layer is the recovery path, so it must survive the same
faults it exists for):

- writes are atomic (tmp + ``os.replace``) and CRC-sealed — a payload
  checksum over every array is stored in the file and verified on load, so
  a truncated or bit-flipped checkpoint is *detected*, never resumed;
- the previous ``keep_last − 1`` generations are retained as
  ``<path>.1 ≥ <path>.2 ≥ …`` (newest first) and ``load_state`` falls back
  through them when the newest generation is corrupt or was written for a
  different problem;
- a state whose in-loop verdict is FLAG_NONFINITE is never persisted —
  the last good generation survives a divergence for the recovery driver
  (``solvers.resilient``) to restart from.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import warnings
import zlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from poisson_tpu.config import Problem
from poisson_tpu.solvers.pcg import (
    FLAG_CONVERGED,
    FLAG_DEADLINE,
    FLAG_INTEGRITY,
    FLAG_NONE,
    FLAG_NONFINITE,
    PCGResult,
    PCGState,
    host_setup,
    init_state,
    make_pcg_body,
    resolve_dtype,
    resolve_scaled,
    resolve_verify_tol,
    scaled_single_device_ops,
    single_device_ops,
)

_STATE_KEYS = ("k", "done", "w", "r", "z", "p", "zr", "diff",
               "flag", "best", "stall")
# Verdict fields are absent in checkpoints written before hardening (and
# in portable states produced by the fused solvers); they resume as a
# clean slate rather than failing the load.
_OPTIONAL_DEFAULTS = {"flag": np.int32(0), "best": np.inf,
                      "stall": np.int32(0)}


class CorruptCheckpointError(RuntimeError):
    """The checkpoint file exists but cannot be trusted: unreadable npz,
    missing payload keys, or CRC mismatch."""


def _fingerprint(problem: Problem, dtype_name: str, scaled: bool,
                 preconditioner: str = "jacobi", mg_config=None) -> str:
    # Bind problem identity, not the stopping budget: max_iter is excluded
    # so a run capped by --max-iter (or preempted) can resume with a larger
    # budget — the natural recovery workflow.
    fields = {
        f.name: getattr(problem, f.name)
        for f in dataclasses.fields(problem)
        if f.name != "max_iter"
    }
    if preconditioner not in (None, "jacobi"):
        # The preconditioner is solve identity: z/p in a persisted state
        # are M⁻¹-derived, so resuming a Jacobi-written state under MG
        # (or vice versa) would splice two different Krylov recurrences —
        # and so would resuming one MGConfig's state under another (the
        # cycle config IS the M⁻¹), so the config joins the tuple too.
        # Appended only for non-default preconditioners — historical
        # Jacobi fingerprints stay byte-identical and keep resuming.
        from poisson_tpu.mg import DEFAULT_MG

        return repr((sorted(fields.items()), dtype_name, scaled,
                     preconditioner, mg_config or DEFAULT_MG))
    return repr((sorted(fields.items()), dtype_name, scaled))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _run_chunk(problem: Problem, scaled: bool, chunk: int,
               stagnation_window: int, stream_every: int,
               verify_every: int, verify_tol: float,
               a, b, aux, rhs, state: PCGState) -> PCGState:
    """Advance the solve by at most ``chunk`` iterations
    (device-resident). ``verify_every``/``verify_tol`` are the static
    integrity-probe knobs (``poisson_tpu.integrity``); ``rhs`` is the
    probe's true-residual reference — callers pass None when the probe
    is off, so flag-off programs keep their historical operand
    signature (and HLO) exactly."""
    ops = (
        scaled_single_device_ops(problem, a, b, aux)
        if scaled
        else single_device_ops(problem, a, b, aux)
    )
    body = make_pcg_body(
        ops, delta=problem.delta, weighted_norm=problem.weighted_norm,
        h1=problem.h1, h2=problem.h2,
        stagnation_window=stagnation_window, stream_every=stream_every,
        verify_every=verify_every, verify_tol=verify_tol,
        verify_rhs=rhs,
    )
    stop_at = jnp.minimum(state.k + chunk, problem.iteration_cap)

    def cond(s: PCGState):
        return (~s.done) & (s.k < stop_at)

    return lax.while_loop(cond, body, state)


def _chunk_ops_advance(problem: Problem, dtype_name: str, scaled: bool,
                       a, b, aux, rhs, chunk: int,
                       stagnation_window: int, stream_every: int,
                       verify_every: int, verify_tol: float,
                       preconditioner: str = "jacobi", mg_config=None,
                       geometry=None):
    """The (ops, advance, init) triple every chunked driver loops on:
    the historical Jacobi chunk program, or its MG twin with the level
    hierarchy bound in (``poisson_tpu.mg``). One seam so the
    checkpointed, deadline-chunked and resilient paths all route the
    preconditioner identically. ``init`` builds the fresh start state —
    JITTED on the MG path (the V-cycle that computes z₀ must run as a
    compiled program, or eager-vs-compiled rounding costs the
    chunked-equals-one-shot bit-parity contract; the Jacobi init is
    elementwise and keeps its historical eager form)."""
    if preconditioner not in (None, "jacobi"):
        from poisson_tpu.mg import (
            DEFAULT_MG,
            resolve_preconditioner,
            validate_mg_problem,
        )
        from poisson_tpu.mg.hierarchy import device_hierarchy
        from poisson_tpu.mg.preconditioner import _run_chunk_mg, mg_ops

        resolve_preconditioner(preconditioner)
        cfg = mg_config or DEFAULT_MG
        validate_mg_problem(problem, cfg)
        from poisson_tpu.mg.preconditioner import _member_init_mg

        hier = device_hierarchy(problem, dtype_name, scaled,
                                geometry=geometry, config=cfg)
        ops = mg_ops(problem, a, b, aux, hier, cfg, scaled)
        advance = lambda s: _run_chunk_mg(
            problem, scaled, chunk, cfg, stagnation_window,
            int(stream_every), verify_every, verify_tol, a, b, aux,
            rhs if verify_every else None, hier, s)
        init = lambda: _member_init_mg(problem, scaled, cfg, a, b, aux,
                                       hier, rhs)
        return ops, advance, init
    ops = (
        scaled_single_device_ops(problem, a, b, aux)
        if scaled
        else single_device_ops(problem, a, b, aux)
    )
    advance = lambda s: _run_chunk(
        problem, scaled, chunk, stagnation_window, int(stream_every),
        verify_every, verify_tol, a, b, aux,
        rhs if verify_every else None, s)
    return ops, advance, (lambda: init_state(ops, rhs))


def _state_flag(state) -> Optional[int]:
    """Termination verdict of any solver state, or None for state types
    (the fused pallas loops) that do not track one."""
    flag = getattr(state, "flag", None)
    return None if flag is None else int(flag)


def _converged(state) -> bool:
    """True only for a genuinely converged stop. Solvers with verdict
    tracking require FLAG_CONVERGED — a breakdown/divergence/stagnation
    stop also sets ``done`` but must keep its checkpoint for recovery;
    verdict-less states keep the historical done-means-converged reading."""
    if not bool(state.done):
        return False
    flag = _state_flag(state)
    return True if flag is None else flag == FLAG_CONVERGED


def run_chunked(state, *, advance, to_portable, path: Optional[str],
                fingerprint: str,
                cap: int, keep_checkpoint: bool, primary=None, sync=None,
                keep_last: int = 2, watchdog=None, on_chunk=None,
                deadline=None, history: bool = False):
    """The one chunked-checkpoint driver loop, shared by all four
    checkpointed solvers (single/sharded × XLA/fused): advance until done
    or cap, persist the portable full-grid state after every chunk, clean
    up a *converged* run's checkpoint (a cap-hit keeps it for resume).
    ``path=None`` runs the same loop persistence-free (the deadline-only
    chunked mode the solve service uses — see :func:`pcg_solve_chunked`).

    ``state`` must expose ``.done`` and ``.k``; ``advance(state)`` runs one
    chunk; ``to_portable(state)`` produces the PCGState ``save_state``
    writes. ``primary``/``sync`` gate the file write to one process and
    barrier-order it against other processes' later reads (multi-process
    meshes); they default to single-process no-ops.

    ``deadline`` (duck-typed: anything with ``expired() -> bool``, e.g.
    ``poisson_tpu.serve.Deadline``) makes the chunking deadline-aware: the
    loop refuses to START a chunk once the deadline has expired, so a
    deadlined solve returns its partial state within one chunk of the
    cutoff instead of hanging to convergence. The caller stamps the
    result flag (FLAG_DEADLINE); the persisted state never carries it, so
    a later run can resume with a fresh budget. The deadline is checked
    at chunk boundaries only — overshoot is bounded by one chunk, which
    is what sizes ``chunk`` for deadline-sensitive callers.

    Resilience hooks:

    - ``keep_last`` generations of the checkpoint are retained (see
      :func:`save_state`);
    - ``watchdog`` (``parallel.watchdog.Watchdog``) is armed for the whole
      loop and beaten at every chunk boundary — a chunk that wedges (the
      multihost collective hang this repo has lived through) trips its
      timeout instead of stalling silently forever;
    - ``on_chunk(state, chunks_done)`` runs after each chunk is persisted
      and may return a replacement state or raise (fault injection — see
      ``testing.faults``);
    - ``history`` feeds each chunk boundary's ``(k, ‖Δw‖)`` into the
      forecast residual-history buffer (``obs.forecast``) — host-side
      only, the traced program is untouched, so the chunked dispatch
      path reports convergence rate without recompilation;
    - a state that went non-finite is *not* persisted and the stop is not
      treated as convergence: the newest good generation survives for the
      recovery driver.
    """
    primary = primary if primary is not None else (lambda: True)
    sync = sync if sync is not None else (lambda name: None)
    if watchdog is not None:
        watchdog.start()
    chunks_done = 0
    try:
        while (not bool(state.done)) and int(state.k) < cap:
            if deadline is not None and deadline.expired():
                # Don't start a chunk the deadline has already disowned:
                # the last persisted generation is the partial answer.
                from poisson_tpu import obs

                obs.inc("checkpoint.deadline_stops")
                obs.event("checkpoint.deadline_stop", k=int(state.k),
                          chunks=chunks_done)
                break
            state = advance(state)
            jax.block_until_ready(state)
            chunks_done += 1
            if watchdog is not None:
                watchdog.beat(k=int(state.k), diff=float(state.diff))
            if history:
                from poisson_tpu.obs.forecast import history_tap

                history_tap(int(state.k), float(state.diff))
            flag = _state_flag(state)
            if flag in (FLAG_NONFINITE, FLAG_INTEGRITY):
                # Poisoned state: saving it would overwrite the last good
                # generation with NaNs — or, for an integrity verdict
                # (poisson_tpu.integrity), with silently corrupted
                # buffers the CRC would then happily seal. ``flag`` is
                # mesh-replicated, so every process skips in step.
                break
            if _converged(state) and not keep_checkpoint:
                # The chunk just converged and the file would be deleted
                # below: skip the full-grid gather (an all-gather collective
                # on multi-process meshes) and the disk write outright.
                break
            if path:
                portable = to_portable(state)  # collective if multi-process
                if primary():
                    save_state(path, portable, fingerprint,
                               keep_last=keep_last)
                sync("poisson_ckpt_save")  # write lands before any read
            if on_chunk is not None:
                state = _apply_hook(on_chunk, state, chunks_done)
    except KeyboardInterrupt:
        if watchdog is not None:
            watchdog.raise_if_fired()   # timeout → typed SolveTimeout
        raise
    finally:
        if watchdog is not None:
            watchdog.stop()
    if path and _converged(state) and not keep_checkpoint and primary():
        remove_generations(path, keep_last)
    sync("poisson_ckpt_done")           # removal precedes any follow-up solve
    return state


def _apply_hook(on_chunk, state, chunks_done):
    replacement = on_chunk(state, chunks_done)
    return state if replacement is None else replacement


def checkpoint_generations(path: str, keep_last: int = 2) -> list:
    """Candidate checkpoint paths, newest first: ``path``, ``path.1``, …"""
    keep_last = max(1, int(keep_last))
    return [path] + [f"{path}.{i}" for i in range(1, keep_last)]


def remove_generations(path: str, keep_last: int = 2) -> None:
    """Delete every retained checkpoint generation (the converged-solve
    cleanup, shared by all chunked drivers)."""
    for candidate in checkpoint_generations(path, keep_last):
        if os.path.exists(candidate):
            os.remove(candidate)


def _payload_crc(fingerprint: str, arrays: dict) -> int:
    crc = zlib.crc32(fingerprint.encode())
    for key in sorted(arrays):
        a = np.ascontiguousarray(arrays[key])
        crc = zlib.crc32(key.encode(), crc)
        crc = zlib.crc32(str(a.dtype).encode(), crc)
        crc = zlib.crc32(str(a.shape).encode(), crc)
        # The array itself is a C-contiguous buffer: same CRC as
        # tobytes(), without materializing a full byte-copy per array
        # per checkpoint write/load.
        crc = zlib.crc32(a, crc)
    return crc & 0xFFFFFFFF


def save_state(path: str, state: PCGState, fingerprint: str,
               keep_last: int = 2) -> None:
    """Atomically persist ``state``: write to a tmp file, seal it with a
    CRC32 over the full payload, rotate the previous generations
    (``path`` → ``path.1`` → …, keeping ``keep_last`` total), then
    ``os.replace`` into place. A kill at any point leaves either the old
    generation chain or the new one — never a partial file at ``path``."""
    from poisson_tpu import obs

    arrays = {key: np.asarray(val) for key, val in zip(_STATE_KEYS, state)}
    # np.savez appends '.npz' to names without it — keep the temp name
    # suffixed so the atomic-replace source path is what savez wrote.
    tmp = f"{path}.{os.getpid()}.tmp.npz"
    try:
        with obs.span("checkpoint.write", path=path):
            np.savez(
                tmp,
                fingerprint=np.asarray(fingerprint),
                crc32=np.uint32(_payload_crc(fingerprint, arrays)),
                **arrays,
            )
            generations = checkpoint_generations(path, keep_last)
            for older, newer in zip(reversed(generations[1:]),
                                    reversed(generations[:-1])):
                if os.path.exists(newer):
                    os.replace(newer, older)
            os.replace(tmp, path)
        obs.inc("checkpoint.writes")
        obs.event("checkpoint.write", path=path, k=int(arrays["k"]))
    finally:
        if os.path.exists(tmp):   # savez died mid-write: no partials left
            os.remove(tmp)


def _read_state(path: str, fingerprint: str) -> PCGState:
    """Read and verify one checkpoint file. Raises CorruptCheckpointError
    for anything untrustworthy, ValueError for a fingerprint mismatch."""
    try:
        with np.load(path) as data:
            if "fingerprint" not in data:
                raise CorruptCheckpointError(
                    f"checkpoint {path} has no fingerprint record"
                )
            saved = str(data["fingerprint"])
            vals = {}
            for key in _STATE_KEYS:
                if key in data:
                    vals[key] = data[key]
                elif key in _OPTIONAL_DEFAULTS:
                    vals[key] = np.asarray(_OPTIONAL_DEFAULTS[key])
                else:
                    raise CorruptCheckpointError(
                        f"checkpoint {path} is missing state array {key!r}"
                    )
            stored_crc = int(data["crc32"]) if "crc32" in data else None
    except CorruptCheckpointError:
        raise
    except Exception as e:
        # Anything raised while parsing the file is corruption: np.load
        # surfaces truncated zips as ValueError/OSError, but a bit-flip in
        # an npy *header* escapes as SyntaxError/TokenError from numpy's
        # header parser — the failure set is open-ended by construction.
        # (The fingerprint-mismatch ValueError is raised after this block.)
        from poisson_tpu import obs

        obs.inc("checkpoint.corrupt")
        obs.event("checkpoint.corrupt", path=path, error=type(e).__name__)
        raise CorruptCheckpointError(
            f"checkpoint {path} is unreadable: {type(e).__name__}: {e}"
        ) from e
    if saved != fingerprint:
        raise ValueError(
            f"checkpoint {path} was written for a different problem "
            f"configuration:\n  saved:     {saved}\n  requested: "
            f"{fingerprint}"
        )
    if stored_crc is not None:
        actual = _payload_crc(saved, {k: np.asarray(v)
                                      for k, v in vals.items()})
        if actual != stored_crc:
            from poisson_tpu import obs

            obs.inc("checkpoint.crc_failures")
            obs.event("checkpoint.crc_failure", path=path,
                      stored=f"{stored_crc:#010x}",
                      payload=f"{actual:#010x}")
            raise CorruptCheckpointError(
                f"checkpoint {path} failed its integrity check "
                f"(stored CRC32 {stored_crc:#010x}, payload "
                f"{actual:#010x}) — the file was corrupted after writing"
            )
    # Normalize the scalar dtypes so a resumed while_loop carry is stable
    # regardless of which solver/precision wrote the file.
    state_dtype = vals["w"].dtype
    as_dev = lambda x: jnp.asarray(x)
    state = PCGState(**{key: as_dev(val) for key, val in vals.items()})
    return state._replace(
        k=jnp.asarray(vals["k"], jnp.int32),
        done=jnp.asarray(bool(vals["done"])),
        zr=jnp.asarray(vals["zr"], state_dtype),
        diff=jnp.asarray(vals["diff"], state_dtype),
        flag=jnp.asarray(vals["flag"], jnp.int32),
        best=jnp.asarray(vals["best"], state_dtype),
        stall=jnp.asarray(vals["stall"], jnp.int32),
    )


def load_state_any(path: str, fingerprints, keep_last: int = 2,
                   ) -> Optional[tuple[PCGState, int]]:
    """The one generation-walk loader: newest generation first, and
    within each generation the given ``fingerprints`` in preference
    order. Returns ``(state, index-of-matched-fingerprint)``, or None if
    no generation exists or every generation is corrupt (a corrupt-only
    chain warns and starts over rather than crashing the resume). A
    corrupt or mismatched newest generation falls back to ``path.1``,
    ``path.2``, …; a mismatch with no loadable older generation raises
    (the checkpoint belongs to a different problem — resuming would
    silently solve the wrong one). An unreadable/corrupt generation is
    skipped outright — no fingerprint could rescue it."""
    fingerprints = list(fingerprints)
    mismatch: Optional[ValueError] = None
    existed = 0
    for candidate in checkpoint_generations(path, keep_last):
        if not os.path.exists(candidate):
            continue
        existed += 1
        for index, fingerprint in enumerate(fingerprints):
            try:
                state = _read_state(candidate, fingerprint)
            except CorruptCheckpointError as e:
                warnings.warn(
                    f"{e} — falling back to the previous checkpoint "
                    f"generation", RuntimeWarning, stacklevel=3,
                )
                break   # unreadable regardless of fingerprint
            except ValueError as e:
                mismatch = mismatch or e
                continue
            if candidate != path:
                from poisson_tpu import obs

                obs.inc("checkpoint.generation_fallbacks")
                obs.event("checkpoint.generation_fallback", path=candidate)
                warnings.warn(
                    f"resuming from older checkpoint generation "
                    f"{candidate} (newest was corrupt or mismatched)",
                    RuntimeWarning, stacklevel=3,
                )
            return state, index
    if mismatch is not None:
        raise mismatch
    if existed:
        warnings.warn(
            f"all {existed} checkpoint generation(s) at {path} are "
            f"corrupt; starting the solve from iteration zero",
            RuntimeWarning, stacklevel=3,
        )
    return None


def load_state(path: str, fingerprint: str,
               keep_last: int = 2) -> Optional[PCGState]:
    """Returns the newest trustworthy saved state for ``fingerprint``, or
    None (see :func:`load_state_any` for the fallback semantics)."""
    found = load_state_any(path, [fingerprint], keep_last)
    return None if found is None else found[0]


def _deadline_flag(state, deadline):
    """The result flag for a chunked run: the state's own verdict, or the
    host-stamped FLAG_DEADLINE when the run was still healthy (verdict
    ``running``) and stopped only because its deadline expired. A solve
    that diverged (nonfinite/breakdown/stagnated) keeps that verdict even
    when the deadline has also lapsed — stamping over it would make the
    service hand a diverged iterate out as a usable partial result and
    skip the retry/escalation path. Never persisted — result-only
    provenance."""
    if (deadline is not None and deadline.expired()
            and _state_flag(state) in (None, FLAG_NONE)):
        return jnp.asarray(FLAG_DEADLINE, jnp.int32)
    return state.flag


def pcg_solve_checkpointed(problem: Problem, checkpoint_path: str,
                           chunk: int = 200, dtype=None, scaled=None,
                           keep_checkpoint: bool = False,
                           keep_last: int = 2,
                           stagnation_window: int = 0,
                           stream_every: int = 0,
                           watchdog=None,
                           on_chunk=None,
                           deadline=None,
                           verify_every: int = 0,
                           verify_tol=None,
                           preconditioner: str = "jacobi",
                           mg_config=None) -> PCGResult:
    """Solve with periodic state persistence and automatic resume.

    Every ``chunk`` iterations the CG state is written to
    ``checkpoint_path`` (atomic, CRC-sealed, ``keep_last`` generations —
    see :func:`save_state`); if a trustworthy checkpoint already exists
    (same problem fingerprint) the solve resumes from it instead of
    starting over, falling back to an older generation when the newest is
    corrupt. On convergence the checkpoint is removed unless
    ``keep_checkpoint``; a cap-hit or divergence stop (``PCGResult.flag``)
    keeps it. ``watchdog``/``on_chunk``/``deadline`` are the
    chunk-boundary resilience hooks documented on :func:`run_chunked`; a
    deadline expiry returns the partial iterate with
    ``flag == FLAG_DEADLINE`` (the checkpoint survives for a resume with
    a fresh budget). ``verify_every``/``verify_tol`` arm the in-loop
    integrity probe (``poisson_tpu.integrity``); a FLAG_INTEGRITY stop
    is never persisted — the last good generation survives for the
    verified-restart driver (``solvers.resilient``).
    ``preconditioner="mg"`` chunks the V-cycle-preconditioned solve
    (:mod:`poisson_tpu.mg`); its checkpoints carry the preconditioner
    in their fingerprint, so a Jacobi checkpoint never resumes under MG
    (two different Krylov recurrences) or vice versa.
    """
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    dtype_name = resolve_dtype(dtype)
    use_scaled = resolve_scaled(scaled, dtype_name)
    a, b, rhs, aux = host_setup(problem, dtype_name, use_scaled)
    fp = _fingerprint(problem, dtype_name, use_scaled, preconditioner,
                      mg_config)
    if preconditioner not in (None, "jacobi"):
        # One driver call = one MG solve (the rollout-fraction counter,
        # obs.metrics "mg.solves", must cover every dispatch path).
        from poisson_tpu import obs

        obs.inc("mg.solves")

    verify_every = int(verify_every)
    v_tol = (resolve_verify_tol(verify_tol, dtype_name)
             if verify_every > 0 else 0.0)
    ops, advance, init = _chunk_ops_advance(
        problem, dtype_name, use_scaled, a, b, aux, rhs, chunk,
        stagnation_window, stream_every, verify_every, v_tol,
        preconditioner=preconditioner, mg_config=mg_config)
    state = load_state(checkpoint_path, fp, keep_last=keep_last)
    if state is None:
        state = init()

    state = run_chunked(
        state,
        advance=advance,
        to_portable=lambda s: s,
        path=checkpoint_path, fingerprint=fp, cap=problem.iteration_cap,
        keep_checkpoint=keep_checkpoint, keep_last=keep_last,
        watchdog=watchdog, on_chunk=on_chunk, deadline=deadline,
    )

    w = state.w * aux if use_scaled else state.w
    return PCGResult(
        w=w, iterations=state.k, diff=state.diff, residual_dot=state.zr,
        flag=_deadline_flag(state, deadline),
    )


def pcg_solve_chunked(problem: Problem, chunk: int = 100, dtype=None,
                      scaled=None, rhs_gate=None,
                      stagnation_window: int = 0, stream_every: int = 0,
                      watchdog=None, on_chunk=None,
                      deadline=None, geometry=None,
                      verify_every: int = 0, verify_tol=None,
                      preconditioner: str = "jacobi",
                      mg_config=None, history: bool = False) -> PCGResult:
    """Chunked single-device solve WITHOUT persistence: the same
    chunk-boundary loop as :func:`pcg_solve_checkpointed` (watchdog beats,
    fault hooks, deadline awareness) minus the disk. This is the dispatch
    primitive the solve service (``poisson_tpu.serve``) uses for
    deadline-carrying requests — a request must be interruptible at chunk
    boundaries, but a short-lived service request has no resume story, so
    writing checkpoints for it would just burn disk on the hot path.

    Converging runs produce the exact ``pcg_solve`` iterate sequence
    (chunking never changes the iterates, only where the host observes
    them). ``rhs_gate`` mirrors ``pcg_solve``'s RHS multiplier; so does
    ``geometry`` (a :mod:`poisson_tpu.geometry` spec swaps the canvases,
    the chunked program is unchanged — the service's deadline-carrying
    geometry requests dispatch through here). A deadline expiry returns
    the partial iterate with ``flag == FLAG_DEADLINE``.
    ``verify_every``/``verify_tol`` arm the in-loop integrity probe
    (``poisson_tpu.integrity``) — the solve service's defensive
    verification rides this path for chunked dispatches. ``history``
    taps each chunk boundary into the forecast residual-history buffer
    (see :func:`run_chunked`).
    """
    from poisson_tpu.solvers.pcg import solve_setup

    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    dtype_name = resolve_dtype(dtype)
    use_scaled = resolve_scaled(scaled, dtype_name)
    a, b, rhs, aux = solve_setup(problem, dtype_name, use_scaled,
                                 geometry=geometry)
    if rhs_gate is not None:
        rhs = rhs * jnp.asarray(rhs_gate, rhs.dtype)
    if preconditioner not in (None, "jacobi"):
        from poisson_tpu import obs

        obs.inc("mg.solves")   # one driver call = one MG solve
    verify_every = int(verify_every)
    v_tol = (resolve_verify_tol(verify_tol, dtype_name)
             if verify_every > 0 else 0.0)
    ops, advance, init = _chunk_ops_advance(
        problem, dtype_name, use_scaled, a, b, aux, rhs, chunk,
        stagnation_window, stream_every, verify_every, v_tol,
        preconditioner=preconditioner, mg_config=mg_config,
        geometry=geometry)
    state = run_chunked(
        init(),
        advance=advance,
        to_portable=lambda s: s,
        path=None, fingerprint="", cap=problem.iteration_cap,
        keep_checkpoint=False,
        watchdog=watchdog, on_chunk=on_chunk, deadline=deadline,
        history=history,
    )
    w = state.w * aux if use_scaled else state.w
    return PCGResult(
        w=w, iterations=state.k, diff=state.diff, residual_dot=state.zr,
        flag=_deadline_flag(state, deadline),
    )
