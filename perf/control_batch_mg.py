"""The control of the batched multigrid cell: ``perf/control.py``'s, with
the batched MG entry (``perf/entry_batch_mg.py``) answering every member
with the configuration's plain reference in bfloat16 too. A run under it
has to come out as not correct.

``perf/tests/test_batch_mg_cell.py`` keeps it at a size a test run can
hold; ``python -m perf.tools.repeat --control perf.control_batch_mg`` runs
it on the chip.
"""

from __future__ import annotations

import contextlib
from unittest import mock

from perf import control, entry_batch_mg


@contextlib.contextmanager
def in_place():
    """Within the block, every driver's timed path, the batched MG one
    included, answers with the bfloat16 reference on the cell's first
    chip."""
    with control.in_place(), \
            mock.patch.object(entry_batch_mg, "batch_entry",
                              control._batch_entry):
        yield
