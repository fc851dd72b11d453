"""Share of the traced window in which the device sat idle while the
host prepared a solve: device idle time under ``pcg_solve.prepare``
(``perf/mg_trace.py``), mean over the cell's chips."""

from perf import mg_trace


def read(run):
    return mg_trace.prepare_idle_pct(run)
