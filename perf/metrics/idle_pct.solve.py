"""Share of the traced window in which no operation ran on the device:
1 - the union of busy intervals over the window, mean over the cell's
chips."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * (1.0 - run.trace.mean_busy_s() / run.trace.window_s)
