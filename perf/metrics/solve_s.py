"""Seconds per converged solve: the whole window, first dispatch to last
completion, over the solves completed in it."""


def read(run):
    if not run.records:
        return None
    return run.window_s / len(run.records)
