"""Device time of the program's named Pallas kernels per CG iteration:
the kernels' time in the traced window (found by their stable names,
perf/spans.py), mean over the cell's chips, over the iterations of the
traced solves."""

from perf import spans


def read(run):
    found = spans.load(run)
    if found is None:
        return None
    per_chip = [found.kernel_ns(d.name) for d in run.trace.devices]
    traced = run.records[:run.info.get("traced", len(run.records))]
    iterations = sum(r["iterations"] for r in traced)
    if not any(per_chip) or iterations <= 0:
        return None
    return sum(per_chip) / len(per_chip) * 1e-3 / iterations
