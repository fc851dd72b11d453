"""Share of the HBM roofline the window's batches reached: the compulsory
CG-state bytes (perf/work.py) of the member-iterations of converged
members in the traced batches, at the published peak (perf/peaks.json),
over the device busy time in the traced window."""

from perf import work


def read(run):
    if run.trace is None or not run.trace.devices or run.peak is None:
        return None
    p = run.config["problem"]
    per_iteration = work.cg_state_bytes_per_iteration(p["M"], p["N"])
    traced = run.records[:run.info.get("traced", len(run.records))]
    useful = sum(k for r in traced
                 for k, ok in zip(r["iterations"], r["converged"]) if ok)
    least = work.least_seconds(per_iteration * useful / len(run.devices),
                               run.peak["hbm_bytes_per_s"])
    return 100.0 * least / max(run.trace.busy_s())
