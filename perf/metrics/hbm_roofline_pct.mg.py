"""Share of the HBM roofline the traced multigrid-preconditioned solves
reached: the compulsory bytes of every iteration they ran
(``perf/work_mg.py``: the CG state, each level's residual and correction,
the coarsest inverse), at the published peak (``perf/peaks.json``), over
the device busy time of the slowest chip in the traced window."""

from perf import work_mg


def read(run):
    if run.trace is None or not run.trace.devices or run.peak is None:
        return None
    p = run.config["problem"]
    per_iteration = work_mg.mg_bytes_per_iteration(p["M"], p["N"])
    traced = run.records[:run.info.get("traced", len(run.records))]
    total = per_iteration * sum(r["iterations"] for r in traced)
    least = total / len(run.devices) / run.peak["hbm_bytes_per_s"]
    return 100.0 * least / max(run.trace.busy_s())
