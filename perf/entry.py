"""What the drivers share: the program's problem and backend choice for a
configuration, and the configuration's reference."""

from __future__ import annotations

import importlib.util
import pathlib
from unittest import mock

PERF = pathlib.Path(__file__).resolve().parent


def load_module(path: pathlib.Path):
    """Import a file by path: metric files carry dots in their names."""
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(
        "perf_" + path.stem.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def problem(config: dict):
    from poisson_tpu.config import Problem

    return Problem(**config["problem"])


def reference(run, dtype=None):
    """The configuration's plain reference on the cell's first device, in
    the configuration's dtype (or ``dtype``: the control's)."""
    config = run.config
    module = load_module(PERF / "reference" / f"{config['reference']}.py")
    return module.Reference(config["problem"], config["reference_max_iter"],
                            dtype or config["dtype"], device=run.devices[0])


def mesh(run):
    """The configuration's solver mesh over the cell's devices, or None
    on one chip."""
    if not run.config.get("mesh"):
        return None
    from poisson_tpu.parallel import make_solver_mesh

    return make_solver_mesh(run.devices, grid=tuple(run.config["mesh"]))


def pick_backend(run) -> str:
    """The backend ``--backend auto`` of the CLI picks for this grid, as
    seen from the cell's own devices only: a one-chip cell on a host with
    more chips is not sent to a sharded path."""
    import jax

    from poisson_tpu import cli

    p = run.config["problem"]
    argv = [str(p["M"]), str(p["N"])]
    if run.config.get("mesh"):
        argv += ["--mesh", "{}x{}".format(*run.config["mesh"])]
    args = cli.build_parser().parse_args(argv)
    devices = list(run.devices)
    with mock.patch.object(jax, "devices", lambda *a, **k: devices):
        return cli._pick_backend(args)


def _refuse_sharded_xla(problem, mesh, dtype):
    raise SystemExit(
        "the CLI's auto choice is the XLA sharded solve "
        "(parallel.pcg_solve_sharded), which takes no per-request "
        "right-hand side (no rhs_gate): this cell cannot draw its inputs "
        "from the seed through it")


def _pallas(problem, mesh, dtype):
    from poisson_tpu.ops.pallas_cg import pallas_cg_solve

    return lambda gate: pallas_cg_solve(problem, rhs_gate=gate)


def _xla(problem, mesh, dtype):
    from poisson_tpu.solvers.pcg import pcg_solve

    return lambda gate: pcg_solve(problem, dtype=dtype, rhs_gate=gate)


def _pallas_sharded(problem, mesh, dtype):
    from poisson_tpu.parallel import pallas_cg_solve_sharded

    return lambda gate: pallas_cg_solve_sharded(problem, mesh, rhs_gate=gate)


# Every name cli._pick_backend returns under --backend auto, mapped to the
# library function that name runs: (problem, mesh, dtype) -> solve(gate).
ENTRIES = {
    "pallas": _pallas,
    "xla": _xla,
    "pallas-sharded": _pallas_sharded,
    "sharded": _refuse_sharded_xla,
}


def solve_entry(run):
    """(backend name, solve(gate) -> PCGResult) for the cell."""
    backend = pick_backend(run)
    if backend not in ENTRIES:
        raise SystemExit(f"cli._pick_backend chose {backend!r}, which the "
                         f"harness does not know (known: {sorted(ENTRIES)})")
    return backend, ENTRIES[backend](problem(run.config), mesh(run),
                                     run.config["dtype"])


def batch_entry(run):
    """(bucket, solve(gates) -> PCGResult): the program's batched driver
    with its defaults, and the bucket width it pads a batch to."""
    from poisson_tpu.solvers.batched import bucket_size, solve_batched

    p, dtype = problem(run.config), run.config["dtype"]
    size = int(run.traffic["batch"])
    return bucket_size(size), (
        lambda gates: solve_batched(p, rhs_gates=gates, dtype=dtype))
