"""``perf/tools/readings.py`` for the multigrid cells: the same readings,
with the control in the MG entry's place as well (``perf/control_mg.py``).

    python -m perf.tools.readings_mg --workload mg-6400x9600 \
        --seeds 1,2,3 --control-seeds 4,5,6 --seconds 3
"""

from __future__ import annotations

import sys
import types
from unittest import mock

from perf import control_mg
from perf.tools import readings


def main(argv=None) -> int:
    with mock.patch.object(readings, "control",
                           types.SimpleNamespace(in_place=control_mg.in_place)):
        return readings.main(argv)


if __name__ == "__main__":
    sys.exit(main())
