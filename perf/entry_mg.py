"""The entry of the multigrid cells: the library function the CLI runs for
``poisson_tpu M N --preconditioner mg`` on the cell's own devices.

A module of its own, beside ``perf/entry.py``, so that the control
(``perf/control_mg.py``) can put the plain reference in its place.
"""

from __future__ import annotations

from unittest import mock

from perf import entry


def pick_backend(run) -> str:
    """The backend ``--backend auto --preconditioner mg`` picks for this
    grid, seen from the cell's own devices only."""
    import jax

    from poisson_tpu import cli

    p = run.config["problem"]
    argv = [str(p["M"]), str(p["N"]), "--preconditioner",
            run.config["preconditioner"]]
    args = cli.build_parser().parse_args(argv)
    devices = list(run.devices)
    with mock.patch.object(jax, "devices", lambda *a, **k: devices):
        return cli._pick_backend(args)


def solve_entry(run):
    """(backend name, solve(gate) -> PCGResult): ``pcg_solve`` with the
    configuration's preconditioner and its default cycle."""
    backend = pick_backend(run)
    if backend != "xla":
        raise SystemExit(f"cli._pick_backend chose {backend!r} for "
                         "--preconditioner mg; the harness drives only the "
                         "xla solve (solvers.pcg.pcg_solve) there")
    from poisson_tpu.solvers.pcg import pcg_solve

    problem, dtype = entry.problem(run.config), run.config["dtype"]
    preconditioner = run.config["preconditioner"]
    return backend, (lambda gate: pcg_solve(
        problem, dtype=dtype, rhs_gate=gate, preconditioner=preconditioner))
