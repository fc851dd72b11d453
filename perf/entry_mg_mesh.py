"""The entry of the multigrid cells split over a mesh: the library function
the CLI runs for ``poisson_tpu M N --preconditioner mg --mesh PXxPY`` on the
cell's own devices, and the configuration's plain reference placed over the
same mesh.

A module of its own, beside ``perf/entry_mg.py``, so that the control
(``perf/control_mg_mesh.py``) can put the plain reference in its place.
"""

from __future__ import annotations

import functools
import json
from unittest import mock

from perf import entry


def mesh(run):
    """The configuration's solver mesh over the cell's devices."""
    from poisson_tpu.parallel import make_solver_mesh

    return make_solver_mesh(run.devices, grid=tuple(run.config["mesh"]))


def pick_backend(run) -> str:
    """The backend ``--backend auto --preconditioner mg --mesh PXxPY``
    picks for this grid, seen from the cell's own devices only."""
    import jax

    from poisson_tpu import cli

    p = run.config["problem"]
    argv = [str(p["M"]), str(p["N"]), "--preconditioner",
            run.config["preconditioner"], "--mesh",
            "{}x{}".format(*run.config["mesh"])]
    args = cli.build_parser().parse_args(argv)
    devices = list(run.devices)
    with mock.patch.object(jax, "devices", lambda *a, **k: devices):
        return cli._pick_backend(args)


def solve_entry(run):
    """(backend name, solve(gate) -> PCGResult): ``pcg_solve`` with the
    configuration's preconditioner and its default cycle, over the
    configuration's mesh. Refuses, before any set-up, a program whose CLI
    sends ``--preconditioner mg`` on a mesh anywhere else."""
    backend = pick_backend(run)
    if backend != "sharded":
        raise SystemExit(f"cli._pick_backend chose {backend!r} for "
                         "--preconditioner mg on a mesh; the harness drives "
                         "only the MG solve over the mesh there "
                         "(solvers.pcg.pcg_solve(mesh=...))")
    from poisson_tpu.solvers.pcg import pcg_solve

    problem, dtype = entry.problem(run.config), run.config["dtype"]
    preconditioner, over = run.config["preconditioner"], mesh(run)
    return backend, (lambda gate: pcg_solve(
        problem, dtype=dtype, rhs_gate=gate, preconditioner=preconditioner,
        mesh=over))


@functools.lru_cache(maxsize=1)
def _host_levels(name: str, problem_json: str):
    """The reference's host fp64 levels, kept for the process: the runs
    of one cell in one process (``perf/tools/repeat.py``, the control's
    readings) build them once."""
    module = entry.load_module(entry.PERF / "reference" / f"{name}.py")
    return module, module._MG.host_levels(json.loads(problem_json))


def reference(run, dtype=None):
    """The configuration's plain reference over the cell's mesh, in the
    configuration's dtype (or ``dtype``: the control's)."""
    config = run.config
    module, host = _host_levels(config["reference"],
                                json.dumps(config["problem"], sort_keys=True))
    return module.Reference(config["problem"], config["reference_max_iter"],
                            dtype or config["dtype"], mesh=mesh(run),
                            host=host)
