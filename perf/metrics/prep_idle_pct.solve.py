"""Share of the traced window in which the device sat idle while the
host prepared a solve: device idle time under the solve entries'
``prepare`` spans (perf/spans.py), mean over the cell's chips."""

from perf import spans


def read(run):
    return spans.prepare_idle_pct(
        run, ("pallas_cg_solve", "pallas_cg_solve_sharded"))
