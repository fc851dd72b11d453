"""The control of the multigrid cells over a mesh: the configuration's
plain reference in bfloat16 (``perf/control.py``'s precision), placed over
the cell's mesh, in the MG mesh entry's place (``perf/entry_mg_mesh.py``).
A run under it has to come out as not correct.

``perf/tools/readings_mg_mesh.py`` runs it on the chip at the cell's size;
``perf/tests/test_mg_mesh_cell.py`` keeps it at a size a test run can
hold.
"""

from __future__ import annotations

import contextlib
from unittest import mock

from perf import control, entry_mg_mesh


def _solve_entry(run):
    ref = entry_mg_mesh.reference(run, dtype=control.DTYPE)
    return ("control-" + control.DTYPE,
            lambda gate: control._result(*ref.solve(gate)))


@contextlib.contextmanager
def in_place():
    """Within the block, the MG mesh driver's timed path answers with the
    bfloat16 reference over the cell's mesh."""
    with mock.patch.object(entry_mg_mesh, "solve_entry", _solve_entry):
        yield
