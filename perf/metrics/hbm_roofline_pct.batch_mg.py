"""Share of the HBM roofline the window's batched multigrid-preconditioned
dispatches reached: the compulsory bytes (``perf/work_batch_mg.py``) of the
member-iterations of converged members in the traced batches, and of the
coarsest inverse once per iteration of each traced batch's loop, at the
published peak (``perf/peaks.json``), over the device busy time of the
slowest chip in the traced window."""

from perf import work_batch_mg


def read(run):
    if run.trace is None or not run.trace.devices or run.peak is None:
        return None
    p = run.config["problem"]
    traced = run.records[:run.info.get("traced", len(run.records))]
    useful = sum(k for r in traced
                 for k, ok in zip(r["iterations"], r["converged"]) if ok)
    loops = sum(r["max_iterations"] for r in traced)
    total = work_batch_mg.batch_bytes(p["M"], p["N"], useful, loops)
    least = total / len(run.devices) / run.peak["hbm_bytes_per_s"]
    return 100.0 * least / max(run.trace.busy_s())
