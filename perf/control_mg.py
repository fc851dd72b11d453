"""The control of the multigrid cells: ``perf/control.py``'s, with the MG
entry (``perf/entry_mg.py``) answering each gate with the configuration's
plain reference in bfloat16 too. A run under it has to come out as not
correct.

``perf/tools/readings_mg.py`` runs it on the chip at the cells' sizes;
``perf/tests/test_mg_cell.py`` keeps it at a size a test run can hold.
"""

from __future__ import annotations

import contextlib
from unittest import mock

from perf import control, entry_mg


@contextlib.contextmanager
def in_place():
    """Within the block, every driver's timed path, the MG one included,
    answers with the bfloat16 reference on the cell's first chip."""
    with control.in_place(), \
            mock.patch.object(entry_mg, "solve_entry", control._solve_entry):
        yield
