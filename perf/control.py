"""The control of the comparison that decides ``correct``: the plain
reference, computed in bfloat16 (the nearest precision below the
configurations' float32), put in the program's place on the timed path.
A run under the control has to come out as not correct.

- single and sharded solves: the cell's solve entry answers each gate
  with the bfloat16 reference's field, iteration count and last update;
- batches: the batched entry answers every member the same way.

``perf/tools/readings.py`` runs it on the chip at the cells' sizes;
``perf/tests/test_control.py`` keeps it at a size a test run can hold.
"""

from __future__ import annotations

import contextlib
import types
from unittest import mock

import numpy as np

from perf import entry

DTYPE = "bfloat16"


def _result(w, k, diff):
    import jax.numpy as jnp

    return types.SimpleNamespace(w=jnp.asarray(w, jnp.float32),
                                 iterations=jnp.asarray(k),
                                 diff=jnp.asarray(diff, jnp.float32))


def _solve_entry(run):
    ref = entry.reference(run, dtype=DTYPE)
    return "control-" + DTYPE, lambda gate: _result(*ref.solve(gate))


def _batch_entry(run):
    import jax.numpy as jnp

    from poisson_tpu.solvers.pcg import FLAG_CONVERGED

    ref = entry.reference(run, dtype=DTYPE)
    delta = run.config["problem"]["delta"]

    def solve(gates):
        out = [ref.solve(g) for g in gates]
        ks = [k for _, k, _ in out]
        return types.SimpleNamespace(
            w=jnp.asarray(np.stack([w for w, _, _ in out]), jnp.float32),
            iterations=jnp.asarray(ks),
            flag=jnp.asarray([FLAG_CONVERGED if d < delta else 0
                              for _, _, d in out]),
            max_iterations=max(ks))

    return int(run.traffic["batch"]), solve


@contextlib.contextmanager
def in_place():
    """Within the block, every driver's timed path answers with the
    bfloat16 reference of the cell's configuration on its first chip."""
    with mock.patch.object(entry, "solve_entry", _solve_entry), \
            mock.patch.object(entry, "batch_entry", _batch_entry):
        yield
