"""Share of the HBM roofline the traced solves reached: the compulsory
CG-state bytes of every iteration they ran (perf/work.py), spread over the
cell's chips, at the published peak (perf/peaks.json), over the device
busy time of the slowest chip in the traced window."""

from perf import work


def read(run):
    if run.trace is None or not run.trace.devices or run.peak is None:
        return None
    p = run.config["problem"]
    per_iteration = work.cg_state_bytes_per_iteration(p["M"], p["N"])
    traced = run.records[:run.info.get("traced", len(run.records))]
    total = per_iteration * sum(r["iterations"] for r in traced)
    least = work.least_seconds(total / len(run.devices),
                               run.peak["hbm_bytes_per_s"])
    return 100.0 * least / max(run.trace.busy_s())
