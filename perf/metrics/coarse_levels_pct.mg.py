"""Share of device busy time the coarse levels took: the ops tagged with
an ``mg_level`` at or below the configuration's ``coarse_below`` grid
(200x300 and below at 6400x9600, the coarsest solve with them), where the
cycle is bound by launches and latency rather than bandwidth
(``perf/mg_trace.py``), mean over the cell's chips."""

from perf import mg_trace


def read(run):
    return mg_trace.level_pct(run, coarse=True)
