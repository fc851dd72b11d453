"""The control, the plain reference in bfloat16 put in the program's
place, has to come out as not correct in every cell; the program, as it
stands, correct. At a grid the CPU holds; ``perf/tools/readings.py`` runs
the same at the cells' own sizes on the chip."""

import pytest

from perf import control
from perf.tests.conftest import rehearse

CELLS = [("solve-2400x3200", "pallas", {}),
         ("batch64-400x600", None, {}),
         ("mesh2x2-2400x3200", "pallas-sharded", {})]


@pytest.mark.parametrize("workload,backend,traffic", CELLS)
def test_program_is_correct(workload, backend, traffic):
    result = rehearse(workload, backend=backend, **traffic)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0


@pytest.mark.parametrize("workload,backend,traffic", CELLS)
def test_control_is_not_correct(workload, backend, traffic):
    with control.in_place():
        result = rehearse(workload, backend=backend, **traffic)
    assert not result["correct"], result["checks"]
