"""Share of the traced window in which collective operations (halo
permutes, all-reduces) ran on a chip, mean over the cell's chips."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    collective = run.trace.collective_s()
    return 100.0 * (sum(collective) / len(collective)) / run.trace.window_s
