"""Share of the traced window in which the device sat idle while the
host prepared a batch: device idle time under ``solve_batched``'s
``prepare`` span (perf/spans.py), mean over the cell's chips."""

from perf import spans


def read(run):
    return spans.prepare_idle_pct(run, ("solve_batched",))
