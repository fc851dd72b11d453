"""Checkpoint/resume for the distributed solver.

The reference has no checkpointing at any stage (SURVEY §5: a solve runs to
convergence in one shot — ``stage2-mpi/poisson_mpi_decomp.cpp:400-460`` —
and an interrupted MPI job restarts from iteration zero). The framework's
single-device subsystem (``solvers.checkpoint``) names pod scale as its
motivation; this module delivers that: the sharded PCG loop runs as
fixed-size chunks of the shared body inside ``shard_map``, and at every
chunk boundary the gathered CG state is persisted in the *same* full-grid
``.npz`` format the single-device solver writes.

Same format + same fingerprint = portable checkpoints: a solve interrupted
on one mesh resumes on a different mesh shape, a different device count, or
on the single-device solver (and vice versa) — elastic recovery the
reference's MPI world could not express (a P-rank run could only ever be
restarted as the same P ranks, from scratch).

Why gathering the owned interiors is sufficient state: every sharded array
either keeps its halo ring zero by invariant (r, z and w are masked to the
owned interior each iteration — ``pcg_sharded._sharded_ops``) or has it
refreshed before use (the loop exchanges p's halos at the top of the body;
the scaled path exchanges sc·p inside ``apply_A``). Reconstructing blocks
with zero halo rings on resume is therefore exact, and the iterate sequence
is a pure function of the saved state.

Multi-process meshes (``jax.distributed`` — the real pod case): state
arrays span non-addressable devices, so before every save they are
resharded to fully-replicated (an all-gather every process participates
in), only the primary process writes the file (the reference's rank-0
idiom), and a cross-process sync orders the write before any later read.
``checkpoint_path`` must be on a filesystem every process can read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from poisson_tpu.config import Problem
from poisson_tpu.parallel.mesh import X_AXIS, Y_AXIS, block_size
from poisson_tpu.parallel.pcg_sharded import (
    _host_shard_blocks,
    _owned_mask,
    _sharded_ops,
)
from poisson_tpu.solvers.checkpoint import (
    _fingerprint,
    load_state,
    run_chunked,
)
from poisson_tpu.solvers.pcg import (
    PCGResult,
    PCGState,
    host_fields64,
    init_state,
    make_pcg_body,
    resolve_dtype,
    resolve_scaled,
)
from jax import shard_map

_STACKED = P((X_AXIS, Y_AXIS))   # (P, m̂+2, n̂+2) field blocks, mesh order
_BLOCKED = P(X_AXIS, Y_AXIS)     # (Px·m̂, Py·n̂) padded-global state arrays


def _multiprocess() -> bool:
    return jax.process_count() > 1


def _sync(name: str) -> None:
    """Cross-process barrier: orders the primary's host-side file write
    before any other process's subsequent read. No-op single-process."""
    if _multiprocess():
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


def _global_array(host: np.ndarray, mesh: Mesh, spec) -> jnp.ndarray:
    """Host array (identical on every process) → global jax.Array sharded
    per ``spec`` over a possibly multi-process mesh. Single-process keeps
    the plain device-put path."""
    if not _multiprocess():
        return jnp.asarray(host)
    sharding = NamedSharding(mesh, spec)
    return jax.make_array_from_callback(
        host.shape, sharding, lambda idx: host[idx]
    )


@functools.lru_cache(maxsize=8)
def _replicator(mesh: Mesh):
    """Cached jitted identity that reshards its argument to fully-replicated
    — one trace/compile per mesh, not per checkpoint boundary."""
    return jax.jit(lambda x: x, out_shardings=NamedSharding(mesh, P()))


def _fetchable(state: PCGState, mesh: Mesh) -> PCGState:
    """Reshard the state arrays to fully-replicated so ``np.asarray`` is
    legal on every process (multi-process state spans non-addressable
    devices). All processes must call this together — it is a collective."""
    if not _multiprocess():
        return state
    rep = _replicator(mesh)
    return state._replace(w=rep(state.w), r=rep(state.r),
                          z=rep(state.z), p=rep(state.p))


def _geometry(problem: Problem, mesh: Mesh):
    px_size = mesh.shape[X_AXIS]
    py_size = mesh.shape[Y_AXIS]
    m_blk = block_size(problem.M - 1, px_size)
    n_blk = block_size(problem.N - 1, py_size)
    return px_size, py_size, m_blk, n_blk


def _interiors(s: PCGState):
    inner = lambda x: x[1:-1, 1:-1]
    return (inner(s.w), inner(s.r), inner(s.z), inner(s.p),
            s.k, s.done, s.zr, s.diff, s.flag, s.best, s.stall)


def _state_specs():
    return (_BLOCKED, _BLOCKED, _BLOCKED, _BLOCKED,
            P(), P(), P(), P(), P(), P(), P())


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _init_sharded(problem: Problem, mesh: Mesh, scaled: bool,
                  a_blk, b_blk, rhs_blk, aux_blk):
    """Initial CG state over the mesh — the exact init ``pcg_solve_sharded``
    runs (same ops, same reductions), as padded-global interior arrays."""
    px_size, py_size, m_blk, n_blk = _geometry(problem, mesh)

    def shard_fn(a, b, rhs, aux):
        a, b, rhs, aux = a[0], b[0], rhs[0], aux[0]
        mask, _, _ = _owned_mask(problem, m_blk, n_blk, a.dtype)
        ops = _sharded_ops(problem, a, b, aux, mask, px_size, py_size, scaled)
        return _interiors(init_state(ops, rhs * mask))

    out = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(_STACKED, _STACKED, _STACKED, _STACKED),
        out_specs=_state_specs(),
        check_vma=False,
    )(a_blk, b_blk, rhs_blk, aux_blk)
    w, r, z, p, k, done, zr, diff, flag, best, stall = out
    return PCGState(k=k, done=done, w=w, r=r, z=z, p=p, zr=zr, diff=diff,
                    flag=flag, best=best, stall=stall)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _chunk_sharded(problem: Problem, mesh: Mesh, scaled: bool, chunk: int,
                   stagnation_window: int,
                   a_blk, b_blk, aux_blk, state: PCGState) -> PCGState:
    """Advance the sharded solve by at most ``chunk`` iterations."""
    px_size, py_size, m_blk, n_blk = _geometry(problem, mesh)

    def shard_fn(a, b, aux, w, r, z, p, k, done, zr, diff, flag, best, stall):
        a, b, aux = a[0], b[0], aux[0]
        mask, _, _ = _owned_mask(problem, m_blk, n_blk, a.dtype)
        ops = _sharded_ops(problem, a, b, aux, mask, px_size, py_size, scaled)
        body = make_pcg_body(
            ops, delta=problem.delta, weighted_norm=problem.weighted_norm,
            h1=problem.h1, h2=problem.h2,
            stagnation_window=stagnation_window,
        )
        pad1 = lambda x: jnp.pad(x, 1)   # zero halo ring (exact: see module doc)
        s0 = PCGState(k=k, done=done, w=pad1(w), r=pad1(r), z=pad1(z),
                      p=pad1(p), zr=zr, diff=diff,
                      flag=flag, best=best, stall=stall)
        stop_at = jnp.minimum(k + chunk, problem.iteration_cap)

        def cond(s: PCGState):
            return (~s.done) & (s.k < stop_at)

        return _interiors(lax.while_loop(cond, body, s0))

    out = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(_STACKED, _STACKED, _STACKED) + _state_specs(),
        out_specs=_state_specs(),
        check_vma=False,
    )(a_blk, b_blk, aux_blk, state.w, state.r, state.z, state.p,
      state.k, state.done, state.zr, state.diff,
      state.flag, state.best, state.stall)
    w, r, z, p, k, done, zr, diff, flag, best, stall = out
    return PCGState(k=k, done=done, w=w, r=r, z=z, p=p, zr=zr, diff=diff,
                    flag=flag, best=best, stall=stall)


def _to_full_grid(state: PCGState, problem: Problem) -> PCGState:
    """Padded-global interiors → the single-device full-grid ``.npz`` layout
    ((M+1, N+1) arrays, zero ring)."""
    M, N = problem.M, problem.N

    def full(x):
        x = np.asarray(x)
        out = np.zeros((M + 1, N + 1), x.dtype)
        out[1:M, 1:N] = x[: M - 1, : N - 1]
        return out

    return state._replace(w=full(state.w), r=full(state.r),
                          z=full(state.z), p=full(state.p))


def _to_padded_global(state: PCGState, problem: Problem, gm: int, gn: int,
                      mesh: Mesh) -> PCGState:
    """Full-grid ``.npz`` layout → this mesh's padded-global interiors.
    Also accepts a checkpoint written by a *different* mesh shape or by the
    single-device solver — the format is identical."""
    M, N = problem.M, problem.N

    def padded(x):
        x = np.asarray(x)
        out = np.zeros((gm, gn), x.dtype)
        out[: M - 1, : N - 1] = x[1:M, 1:N]
        return _global_array(out, mesh, _BLOCKED)

    def scalar(x):
        return _global_array(np.asarray(x), mesh, P())

    return state._replace(w=padded(state.w), r=padded(state.r),
                          z=padded(state.z), p=padded(state.p),
                          k=scalar(state.k), done=scalar(state.done),
                          zr=scalar(state.zr), diff=scalar(state.diff),
                          flag=scalar(state.flag), best=scalar(state.best),
                          stall=scalar(state.stall))


def pcg_solve_sharded_checkpointed(problem: Problem, mesh: Mesh,
                                   checkpoint_path: str, chunk: int = 200,
                                   dtype=None, scaled=None,
                                   keep_checkpoint: bool = False,
                                   keep_last: int = 2,
                                   stagnation_window: int = 0,
                                   watchdog=None,
                                   on_chunk=None) -> PCGResult:
    """Distributed solve with periodic state persistence and automatic resume.

    Chunked counterpart of ``pcg_solve_sharded`` (host setup): every
    ``chunk`` iterations the gathered CG state is written to
    ``checkpoint_path`` (atomic replace, CRC-sealed, ``keep_last``
    generations retained — see ``solvers.checkpoint.save_state``); an
    existing checkpoint with the same problem fingerprint is resumed —
    including one written by the single-device ``pcg_solve_checkpointed``
    or by a run on a different mesh shape, and falling back to an older
    generation if the newest is corrupt. On convergence the checkpoint is
    removed unless ``keep_checkpoint``; an unconverged cap-hit (or a
    divergence stop — see ``PCGResult.flag``) keeps it.

    ``watchdog`` (``parallel.watchdog.Watchdog``) is beaten at every chunk
    boundary — the heartbeat/timeout guard for wedged collectives on the
    multihost path. ``on_chunk(state, chunks_done)`` runs after each
    persisted chunk (fault injection uses this; see ``testing.faults``).
    """
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    from poisson_tpu.parallel.multihost import is_primary

    dtype_name = resolve_dtype(dtype)
    use_scaled = resolve_scaled(scaled, dtype_name)
    px_size, py_size, m_blk, n_blk = _geometry(problem, mesh)
    blocks = _host_shard_blocks(
        problem, px_size, py_size, m_blk, n_blk, dtype_name, use_scaled
    )
    if _multiprocess():
        # _host_shard_blocks builds identical host data on every process but
        # places it process-locally; re-wrap as global arrays for the mesh.
        blocks = tuple(
            _global_array(np.asarray(blk), mesh, _STACKED) for blk in blocks
        )
    a_blk, b_blk, rhs_blk, aux_blk = blocks
    fp = _fingerprint(problem, dtype_name, use_scaled)

    saved = load_state(checkpoint_path, fp)
    if saved is None:
        state = _init_sharded(problem, mesh, use_scaled,
                              a_blk, b_blk, rhs_blk, aux_blk)
    else:
        state = _to_padded_global(saved, problem,
                                  px_size * m_blk, py_size * n_blk, mesh)

    def to_portable(s):
        # The full-grid gather is the expensive part of a sharded
        # checkpoint (an all-gather collective on multi-process meshes) —
        # span it so slow checkpoints are visible on the timeline.
        from poisson_tpu import obs

        with obs.span("checkpoint.gather",
                      mesh=f"{px_size}x{py_size}"):
            return _to_full_grid(_fetchable(s, mesh), problem)

    state = run_chunked(
        state,
        advance=lambda s: _chunk_sharded(problem, mesh, use_scaled, chunk,
                                         stagnation_window,
                                         a_blk, b_blk, aux_blk, s),
        to_portable=to_portable,
        path=checkpoint_path, fingerprint=fp, cap=problem.iteration_cap,
        keep_checkpoint=keep_checkpoint, primary=is_primary, sync=_sync,
        keep_last=keep_last, watchdog=watchdog, on_chunk=on_chunk,
    )

    # Solution extraction, matching pcg_solve_sharded: unscale with the same
    # cast-to-device-dtype scaling vector the sharded ops used.
    w_y = np.asarray(_to_full_grid(_fetchable(state, mesh), problem).w)
    if use_scaled:
        _, _, _, aux64 = host_fields64(problem, True)
        w_y = w_y * np.asarray(aux64, w_y.dtype)
    return PCGResult(
        w=jnp.asarray(w_y), iterations=state.k, diff=state.diff,
        residual_dot=state.zr, flag=state.flag,
    )
