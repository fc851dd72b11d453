"""Share of device busy time the V-cycle took: the ops tagged with any
``mg_level`` (``perf/mg_trace.py``), mean over the cell's chips. The rest
is the CG recurrence: the operator, dots and updates on the finest grid."""

from perf import mg_trace


def read(run):
    return mg_trace.level_pct(run)
