"""Mean iteration count the entry returned for the window's solves (the
solver layer's count of work; moves solve_s)."""


def read(run):
    if not run.records:
        return None
    return sum(r["iterations"] for r in run.records) / len(run.records)
