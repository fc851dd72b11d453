"""Closed loop of single solves, one caller: each solve starts when the
previous one's field and iteration count are on the host.

The entry is the program's own choice (``cli._pick_backend`` over the
cell's devices). Each solve's right-hand side is the configuration's,
scaled by a gate from the traffic's pool (the entry's ``rhs_gate``). The
window closes with the first solve that completes at or after
``--seconds``; ``window_s`` runs from the first dispatch to that
completion. A seeded sample of the window's solves (``check.sample``) is
compared with the reference: field and iteration count.
"""

from __future__ import annotations

import time

import numpy as np

from perf import compare, entry, generate


def setup(run):
    import jax

    backend, solve = entry.solve_entry(run)
    run.info["backend"] = backend
    # A zero right-hand side stops every entry after one iteration (a
    # degenerate direction), on the same compiled programs the window
    # drives: the warm-up compiles and loads them without a whole solve.
    r = solve(0.0)
    jax.block_until_ready((r.w, r.iterations))
    return {"solve": solve}


def window(run, state, span):
    import jax

    solve = state["solve"]
    gates = generate.gates(run.traffic["gates"], run.seed)
    sample = generate.Reservoir(run.traffic["check"]["sample"], run.seed)
    t0 = time.perf_counter()
    while True:
        gate = next(gates)
        t_start = time.perf_counter() - t0
        with span("perf.dispatch"):
            r = solve(gate)
        with span("perf.fetch"):
            jax.block_until_ready((r.w, r.iterations))
            k, diff = int(r.iterations), float(r.diff)
        done = time.perf_counter() - t0
        rec = {"gate": gate, "iterations": k, "diff": diff,
               "start": t_start, "done": done}
        run.records.append(rec)
        sample.offer((len(run.records) - 1, r.w))
        if done >= run.seconds:
            break
    run.window_s = done
    # The quickest and the slowest solve of the window, from dispatch to
    # its answer on the host: a far-off run says whether one solve stalled.
    took = [r["done"] - r["start"] for r in run.records]
    run.info["solve_s_range"] = [min(took), max(took)]
    state["sample"] = sample.items


def release(run, state):
    """Bring the sampled fields to the host; drop every device array."""
    run.kept = [(i, np.asarray(w, np.float64)) for i, w in state["sample"]]
    state.clear()


def check(run):
    delta = run.config["problem"]["delta"]
    checks = compare.Checks(run.config["limits"])
    ref = entry.reference(run)
    for i, w in run.kept:
        rec = run.records[i]
        w_ref, k_ref, _ = ref.solve(rec["gate"])
        checks.add("iters_gap", abs(rec["iterations"] - k_ref))
        checks.add("field_gap", compare.field_gap(w, w_ref))
    failed = sum(not (r["diff"] < delta) for r in run.records)
    return checks, len(run.records), failed
