"""The HLO identity ledger: a declarative registry of flag-off programs.

Every opt-in subsystem in this repo ships with the same promise: *off
means off* — with the flag at its default, the lowered program is the
exact historical one, no callbacks, no collectives, no preconditioner
machinery. PRs 9–12 each pinned that promise with a hand-rolled
verbatim-reconstruction test; this module replaces the pattern with one
harness: each :class:`ProgramSpec` below names a flag-off program,
lowers it through the real entry point, canonicalizes the StableHLO
(``contracts.hlo``), fingerprints it, and checks **structural
assertions** (no ``custom_call``/callback with flags off, no
``shard_map``/``psum`` with ``mesh=None``, no ``dot_general`` under
jacobi — the MG coarse solve is a dense matmul) against the committed
ledger file ``poisson_tpu/contracts/ledger.json``.

A fingerprint mismatch means the flag-off lowering CHANGED — either an
intentional refactor (review the diff, run ``python -m
poisson_tpu.contracts --update-ledger``, commit the new ledger) or
exactly the drift class this gate exists to catch. Structural
violations are never ledgerable: a callback in a flag-off program is
wrong no matter what the committed fingerprint says.

Fingerprints are environment-sensitive (jax version, platform): the
ledger records both, and the check reports an environment mismatch
distinctly from genuine drift so a CPU ledger is never silently
"confirmed" by a TPU run.

Also here: the registry-drift allowlist ``POLICY_COVERAGE_EXEMPT`` —
every exemption carries a reason string, mirroring the lint's
suppression contract.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Callable, Optional, Tuple

from poisson_tpu.contracts.hlo import (
    find_forbidden,
    hlo_fingerprint,
    markers_for,
    strip_hlo_metadata,
)

LEDGER_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "ledger.json")
LEDGER_SCHEMA = "poisson_tpu.contracts.ledger/1"


@dataclass(frozen=True)
class ProgramSpec:
    """One flag-off program under ledger protection.

    ``build`` returns the lowered StableHLO text via the real entry
    point (lazy jax import — the lint/drift half of the checker never
    pays for it). ``forbid`` names marker sets from ``contracts.hlo``
    (symbolic, so the marker vocabulary evolves in one place).
    """

    name: str
    description: str
    forbid: Tuple[str, ...]
    build: Callable[[], str]


# -- program builders (lazy imports; 20×24 f64 / 20×24 f32-scaled keep
# lowering fast while exercising every default-off flag) ---------------

def _problem():
    from poisson_tpu.config import Problem

    return Problem(M=20, N=24)


def _setup(dtype_name: str, scaled: bool):
    from poisson_tpu.solvers.pcg import host_setup

    return host_setup(_problem(), dtype_name, scaled)


def _build_solve_jacobi_f64() -> str:
    from poisson_tpu.solvers.pcg import _solve

    a, b, rhs, aux = _setup("float64", False)
    return _solve.lower(_problem(), False, 0, 0, 0.0, False, 0,
                        a, b, rhs, aux).as_text()


def _build_solve_scaled_f32() -> str:
    from poisson_tpu.solvers.pcg import _solve

    a, b, rhs, aux = _setup("float32", True)
    return _solve.lower(_problem(), True, 0, 0, 0.0, False, 0,
                        a, b, rhs, aux).as_text()


def _build_solve_history_f64() -> str:
    """The history-ON solve (``history_every=5``) — the forecast seam's
    opt-in program. Pinned so the convergence-history callback's shape
    is itself a contract: drift here means the traced telemetry
    changed, not just the flag-off byte-pin."""
    from poisson_tpu.solvers.pcg import _solve

    a, b, rhs, aux = _setup("float64", False)
    return _solve.lower(_problem(), False, 0, 0, 0.0, False, 5,
                        a, b, rhs, aux).as_text()


def _build_batched_mesh_none() -> str:
    import functools

    import jax
    import numpy as np

    from poisson_tpu.solvers.batched import _solve_batched

    p = _problem()
    a, b, rhs, aux = _setup("float64", False)
    stack = np.stack([np.asarray(rhs), np.asarray(rhs) * 1.1])
    return jax.jit(
        functools.partial(_solve_batched.__wrapped__, p, False, 0, 0.0)
    ).lower(a, b, stack, aux).as_text()


def _build_lanes_step_geometry_off() -> str:
    import jax
    import jax.numpy as jnp

    from poisson_tpu.solvers.lanes import _step_lanes
    from poisson_tpu.solvers.pcg import init_state, single_device_ops

    p = _problem()
    a, b, rhs, aux = _setup("float64", False)
    member = init_state(single_device_ops(p, a, b, aux), rhs)
    stacked = jax.tree_util.tree_map(lambda x: jnp.stack([x, x]), member)
    return _step_lanes.lower(p, False, 25, a, b, aux, stacked).as_text()


def _build_chunk_verify_off() -> str:
    from poisson_tpu.solvers.checkpoint import _run_chunk
    from poisson_tpu.solvers.pcg import init_state, single_device_ops

    p = _problem()
    a, b, rhs, aux = _setup("float64", False)
    state = init_state(single_device_ops(p, a, b, aux), rhs)
    return _run_chunk.lower(p, False, 50, 0, 0, 0, 0.0,
                            a, b, aux, None, state).as_text()


def _build_member_init() -> str:
    from poisson_tpu.solvers.lanes import _member_init

    p = _problem()
    a, b, rhs, aux = _setup("float64", False)
    return _member_init.lower(p, False, a, b, aux, rhs).as_text()


def _build_batched_mode_independent() -> str:
    """solve_batched's mode="independent" default resolved through the
    REAL entry-point branch (poisson_tpu.krylov threading, PR 14): the
    mode dispatch is host-side, so the lowered program must be the
    byte-identical historical bucket executable — this entry's
    fingerprint must EQUAL batched.mesh_none_f64's (asserted by
    tests/test_krylov.py on the committed ledger)."""
    import functools

    import jax
    import numpy as np

    from poisson_tpu.krylov import KRYLOV_INDEPENDENT, resolve_krylov
    from poisson_tpu.krylov import KrylovPolicy
    from poisson_tpu.solvers.batched import _solve_batched

    # The default policy must resolve to the independent mode (the
    # flag-off contract of the whole krylov subsystem)…
    assert resolve_krylov(None).mode == KRYLOV_INDEPENDENT
    assert KrylovPolicy().mode == KRYLOV_INDEPENDENT
    # …and the program it dispatches is the historical one.
    p = _problem()
    a, b, rhs, aux = _setup("float64", False)
    stack = np.stack([np.asarray(rhs), np.asarray(rhs) * 1.1])
    return jax.jit(
        functools.partial(_solve_batched.__wrapped__, p, False, 0, 0.0)
    ).lower(a, b, stack, aux).as_text()


def _build_stencil_apply_A() -> str:
    import jax
    import numpy as np

    from poisson_tpu.ops.stencil import apply_A

    p = _problem()
    a, b, _, _ = _setup("float64", False)
    w = np.zeros((p.M + 1, p.N + 1))
    return jax.jit(
        lambda w_, a_, b_: apply_A(w_, a_, b_, p.h1, p.h2)
    ).lower(w, np.asarray(a), np.asarray(b)).as_text()


def _build_session_step_cold() -> str:
    """A durable session's cold Poisson step resolved through the REAL
    entry point: ``solvers.session.session_step_solve`` with no warm
    iterate calls the literal historical ``pcg_solve``, so the lowered
    program must be the byte-identical flags-off executable — this
    entry's fingerprint must EQUAL solve.jacobi_f64's (asserted by
    tests/test_session.py on the committed ledger)."""
    from poisson_tpu.solvers.pcg import _solve

    a, b, rhs, aux = _setup("float64", False)
    return _solve.lower(_problem(), False, 0, 0, 0.0, False, 0,
                        a, b, rhs, aux).as_text()


def _build_session_warm_f64() -> str:
    import numpy as np

    from poisson_tpu.solvers.session import _solve_warm

    p = _problem()
    a, b, rhs, aux = _setup("float64", False)
    w0 = np.zeros((p.M + 1, p.N + 1))
    return _solve_warm.lower(p, False, a, b, rhs, aux, w0).as_text()


def _heat_operands():
    import numpy as np

    from poisson_tpu.solvers.session import shifted_setup

    p = _problem()
    a, b, rhs0, aux = shifted_setup(p, None, "float64", False, 0.5)
    u = np.zeros((p.M + 1, p.N + 1))
    return p, a, b, rhs0, aux, np.asarray(0.5, np.float64), u


def _build_session_heat_cold() -> str:
    from poisson_tpu.solvers.session import _solve_shifted

    p, a, b, rhs0, aux, m, u = _heat_operands()
    return _solve_shifted.lower(p, False, False, a, b, rhs0, aux,
                                m, u, u).as_text()


def _build_session_heat_warm() -> str:
    from poisson_tpu.solvers.session import _solve_shifted

    p, a, b, rhs0, aux, m, u = _heat_operands()
    return _solve_shifted.lower(p, False, True, a, b, rhs0, aux,
                                m, u, u).as_text()


def _build_serve_routed_default() -> str:
    from poisson_tpu.serve.router import executor_backend
    from poisson_tpu.solvers.pcg import _solve

    # The router is an OBSERVATION-plane chooser: whatever arm it
    # names, execution runs through the xla executor gate until a
    # future PR lands real pallas dispatch. If that gate ever opens,
    # this program is no longer the flags-off lowering and the pin
    # below must be revisited deliberately, not silently.
    for arm in ("xla", "pallas_resident", "pallas_ca"):
        if executor_backend(arm) != "xla":
            raise AssertionError(
                f"executor_backend({arm!r}) no longer gates to xla — "
                "the routed default program is not the flags-off "
                "lowering any more")
    a, b, rhs, aux = _setup("float64", False)
    return _solve.lower(_problem(), False, 0, 0, 0.0, False, 0,
                        a, b, rhs, aux).as_text()


_ALL_OFF = ("callbacks", "collectives", "mg")

PROGRAMS: Tuple[ProgramSpec, ...] = (
    ProgramSpec(
        name="solve.jacobi_f64",
        description="pcg_solve default path (jacobi, stream/verify/"
                    "abft off, f64 unscaled) — the flagship flag-off "
                    "executable every golden count rests on",
        forbid=_ALL_OFF,
        build=_build_solve_jacobi_f64,
    ),
    ProgramSpec(
        name="solve.scaled_f32",
        description="pcg_solve scaled-f32 path (the TPU default "
                    "precision policy), all flags off",
        forbid=_ALL_OFF,
        build=_build_solve_scaled_f32,
    ),
    ProgramSpec(
        name="solve.history_f64",
        description="pcg_solve with history_every=5 — the forecast "
                    "residual-history seam's opt-in program (callbacks "
                    "legal here; collectives/mg still forbidden)",
        forbid=("collectives", "mg"),
        build=_build_solve_history_f64,
    ),
    ProgramSpec(
        name="batched.mesh_none_f64",
        description="solve_batched with mesh=None — the single-device "
                    "bucket executable family (no shard_map/psum ever)",
        forbid=_ALL_OFF,
        build=_build_batched_mesh_none,
    ),
    ProgramSpec(
        name="lanes.step_geometry_off",
        description="LaneBatch chunk stepping, geometry/verify off — "
                    "the continuous engine's flag-off lane program",
        forbid=_ALL_OFF,
        build=_build_lanes_step_geometry_off,
    ),
    ProgramSpec(
        name="chunk.verify_off",
        description="checkpoint _run_chunk with stream/verify off — "
                    "the chunked drivers' flag-off advance program",
        forbid=_ALL_OFF,
        build=_build_chunk_verify_off,
    ),
    ProgramSpec(
        name="lanes.member_init",
        description="jitted member init (splice seam) — byte-identical "
                    "state construction for every spliced member",
        forbid=_ALL_OFF,
        build=_build_member_init,
    ),
    ProgramSpec(
        name="batched.mode_independent_f64",
        description="solve_batched mode='independent' (the krylov "
                    "flag-off default) — must lower to the byte-"
                    "identical historical bucket executable "
                    "(fingerprint equals batched.mesh_none_f64)",
        forbid=_ALL_OFF,
        build=_build_batched_mode_independent,
    ),
    ProgramSpec(
        name="stencil.apply_A_unbatched",
        description="the unbatched 5-point stencil application — the "
                    "PR 9 batch-polymorphism pin (2D HLO unchanged)",
        forbid=_ALL_OFF,
        build=_build_stencil_apply_A,
    ),
    ProgramSpec(
        name="session.step_cold_f64",
        description="a durable session's cold Poisson step (no warm "
                    "iterate offered) — must lower to the byte-"
                    "identical historical flags-off executable "
                    "(fingerprint equals solve.jacobi_f64)",
        forbid=_ALL_OFF,
        build=_build_session_step_cold,
    ),
    ProgramSpec(
        name="session.warm_f64",
        description="the warm-started session step (restart_state "
                    "from the previous iterate instead of zero init; "
                    "same flags-off PCG body)",
        forbid=_ALL_OFF,
        build=_build_session_warm_f64,
    ),
    ProgramSpec(
        name="session.heat_cold_f64",
        description="one implicit-Euler heat step (A + m*I, transient "
                    "RHS composed in-graph), zero init — the cold "
                    "shifted-operator program every heat session "
                    "stream compiles once",
        forbid=_ALL_OFF,
        build=_build_session_heat_cold,
    ),
    ProgramSpec(
        name="session.heat_warm_f64",
        description="the warm implicit-Euler heat step (restart from "
                    "the previous time level) — the steady-state "
                    "program of a converging transient stream",
        forbid=_ALL_OFF,
        build=_build_session_heat_warm,
    ),
    ProgramSpec(
        name="serve.routed_default_f64",
        description="the program a router-enabled service actually "
                    "executes on the default path: every routed arm "
                    "gates through the xla executor, so the lowering "
                    "must stay byte-identical to the historical "
                    "flags-off executable (fingerprint equals "
                    "solve.jacobi_f64) — the router may only ever "
                    "change attribution, never numerics",
        forbid=_ALL_OFF,
        build=_build_serve_routed_default,
    ),
)


def _environment() -> dict:
    import jax

    return {
        "jax_version": jax.__version__,
        "platform": jax.default_backend(),
    }


def lower_program(spec: ProgramSpec) -> str:
    """Lower one registered program (enables x64 first — the f64
    entries are the oracle-parity lowerings and must not silently
    truncate to f32)."""
    import jax

    jax.config.update("jax_enable_x64", True)
    return spec.build()


def load_ledger(path: Optional[str] = None) -> Optional[dict]:
    path = path or LEDGER_PATH
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def run_ledger_check(update: bool = False,
                     path: Optional[str] = None) -> dict:
    """Lower every registered program, check structure + fingerprint
    against the committed ledger. Returns a report dict with
    ``problems`` (each ``{kind, program, message}``) — empty means the
    contract holds. ``update=True`` rewrites the ledger from the
    current tree (structural violations still fail: they are never
    ledgerable)."""
    path = path or LEDGER_PATH
    env = _environment()
    ledger = load_ledger(path)
    problems: list = []
    entries: dict = {}
    if ledger is None and not update:
        # A gate that silently stopped producing evidence is not a
        # passing gate: an absent/corrupt committed ledger must FAIL,
        # not degrade into "nothing to compare against".
        problems.append({
            "kind": "ledger-absent", "program": "*",
            "message": (
                f"committed ledger missing or unreadable at {path} — "
                f"restore it from version control, or mint a reviewed "
                f"one with --update-ledger"),
        })
    for spec in PROGRAMS:
        try:
            text = lower_program(spec)
        except Exception as e:  # a program that no longer lowers IS drift
            problems.append({
                "kind": "lowering-error", "program": spec.name,
                "message": f"entry point failed to lower: {e!r}",
            })
            continue
        violations = find_forbidden(text, markers_for(spec.forbid))
        if violations:
            problems.append({
                "kind": "hlo-structure", "program": spec.name,
                "message": (
                    f"forbidden op marker(s) {violations} in the "
                    f"flag-off lowering — never ledgerable"),
            })
        fp = hlo_fingerprint(text)
        entries[spec.name] = {
            "fingerprint": fp,
            "canonical_bytes": len(strip_hlo_metadata(text)),
            "forbid": list(spec.forbid),
            "description": spec.description,
        }
        if update or ledger is None:
            continue
        committed = (ledger.get("entries") or {}).get(spec.name)
        if committed is None:
            problems.append({
                "kind": "ledger-missing", "program": spec.name,
                "message": (
                    "program is registered but absent from the "
                    "committed ledger — run --update-ledger and commit"),
            })
        elif committed.get("fingerprint") != fp:
            env_committed = {k: ledger.get(k) for k in
                            ("jax_version", "platform")}
            env_note = ("" if env_committed == env else
                        f" (environment differs: ledger {env_committed} "
                        f"vs current {env} — re-run where the ledger "
                        f"was minted before judging)")
            problems.append({
                "kind": "ledger-drift", "program": spec.name,
                "message": (
                    f"flag-off lowering changed: committed "
                    f"{committed.get('fingerprint', '?')[:16]}…, "
                    f"current {fp[:16]}… — an intentional refactor "
                    f"needs --update-ledger + review; anything else is "
                    f"the drift this gate exists for{env_note}"),
            })
    stale = set((ledger or {}).get("entries") or {}) - {
        s.name for s in PROGRAMS}
    for name in sorted(stale):
        problems.append({
            "kind": "ledger-stale", "program": name,
            "message": "ledger entry has no registered program — "
                       "remove it via --update-ledger",
        })
    report = {
        "schema": "poisson_tpu.contracts.ledger-check/1",
        "ledger": path,
        "environment": env,
        "programs": len(PROGRAMS),
        "entries": entries,
        "problems": problems,
        "updated": False,
    }
    if update and not any(p["kind"] in ("hlo-structure", "lowering-error")
                          for p in problems):
        with open(path, "w") as f:
            json.dump({"schema": LEDGER_SCHEMA, **env,
                       "entries": entries}, f, indent=1, sort_keys=True)
            f.write("\n")
        report["updated"] = True
        # drift/missing/stale problems are resolved by the rewrite
        report["problems"] = [p for p in problems if p["kind"]
                              in ("hlo-structure", "lowering-error")]
    return report


# -- registry-drift allowlist (reason strings required) ----------------

# ServicePolicy/FleetPolicy fields a chaos scenario need not exercise —
# each with the reason it is exempt. Everything else must appear in at
# least one scenario (kwarg or attribute) in testing/chaos.py.
POLICY_COVERAGE_EXEMPT = {
    "ServicePolicy.slo": "SLO accounting is scored by the flight "
                         "recorder over ordinary outcomes; burn-driven "
                         "degradation is opt-in and covered by "
                         "tests/test_flight.py, deliberately not by "
                         "the deterministic chaos campaign (default "
                         "OFF keeps scenario outcomes seed-stable)",
    "ServicePolicy.preconditioner": "the MG service default changes "
                                    "numerics, not failure handling; "
                                    "serve-side MG is exercised by "
                                    "tests/test_mg.py cohort-split "
                                    "tests",
}
