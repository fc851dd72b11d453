"""``perf/tools/readings.py`` for the multigrid cells over a mesh: the same
readings, with the control in the MG mesh entry's place
(``perf/control_mg_mesh.py``).

    python -m perf.tools.readings_mg_mesh --workload mg-mesh2x2-12800x19200 \
        --seeds 1,2 --control-seeds 3,4 --seconds 3
"""

from __future__ import annotations

import sys
import types
from unittest import mock

from perf import control_mg_mesh
from perf.tools import readings


def main(argv=None) -> int:
    control = types.SimpleNamespace(in_place=control_mg_mesh.in_place)
    with mock.patch.object(readings, "control", control):
        return readings.main(argv)


if __name__ == "__main__":
    sys.exit(main())
