"""Converged member solves per second over the whole window."""


def read(run):
    if not run.records or run.window_s <= 0:
        return None
    return sum(sum(r["converged"]) for r in run.records) / run.window_s
