"""The entry of the batched multigrid cell: the program's batched driver,
``solvers.batched.solve_batched``, with the configuration's preconditioner
and every other argument at its default (the cycle, the dtype and scaling
policy, the bucket ladder).

A module of its own, beside ``perf/entry.py``, so that the control
(``perf/control_batch_mg.py``) can put the plain reference in its place.
"""

from __future__ import annotations

from perf import entry


def batch_entry(run):
    """(bucket, solve(gates) -> PCGResult): ``solve_batched`` with the
    configuration's preconditioner, and the bucket width it pads a batch
    of the traffic's size to."""
    from poisson_tpu.solvers.batched import bucket_size, solve_batched

    problem, dtype = entry.problem(run.config), run.config["dtype"]
    preconditioner = run.config["preconditioner"]
    size = int(run.traffic["batch"])
    return bucket_size(size), (lambda gates: solve_batched(
        problem, rhs_gates=gates, dtype=dtype,
        preconditioner=preconditioner))
