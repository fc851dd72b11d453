"""Closed loop of batched dispatches, one caller: each batch of
``batch`` right-hand sides on one operator goes through the program's
batched driver (``solvers.batched.solve_batched``, its defaults) once the
previous batch's fields and per-member counts are on the host.

Every batch is a fresh seeded permutation of the whole gate pool, so
every batch does the same work. The window closes with the first batch
that completes at or after ``--seconds``. Every member's iteration count
is compared with the reference's for its gate; the fields of a seeded
sample of batches (``check.sample``), every member, too.
"""

from __future__ import annotations

import time

import numpy as np

from perf import compare, entry, generate


def setup(run):
    import jax

    size = int(run.traffic["batch"])
    if size != int(run.traffic["gates"]["n"]):
        raise SystemExit("a batch takes the whole gate pool: batch "
                         f"{size} != pool {run.traffic['gates']['n']}")
    bucket, solve = entry.batch_entry(run)
    run.info["bucket"] = bucket
    # All-zero right-hand sides stop every member after one iteration on
    # the bucket program the window drives.
    r = solve([0.0] * size)
    jax.block_until_ready((r.w, r.iterations))
    return {"solve": solve}


def window(run, state, span):
    import jax

    from poisson_tpu.solvers.pcg import FLAG_CONVERGED

    solve = state["solve"]
    size = int(run.traffic["batch"])
    gates = generate.gates(run.traffic["gates"], run.seed)
    sample = generate.Reservoir(run.traffic["check"]["sample"], run.seed)
    t0 = time.perf_counter()
    while True:
        batch = [next(gates) for _ in range(size)]
        t_start = time.perf_counter() - t0
        with span("perf.dispatch"):
            r = solve(batch)
        with span("perf.fetch"):
            jax.block_until_ready((r.w, r.iterations))
            iters = np.asarray(r.iterations).tolist()
            flags = np.asarray(r.flag).tolist()
            max_iters = int(r.max_iterations)
        done = time.perf_counter() - t0
        run.records.append({
            "gates": batch, "iterations": iters,
            "converged": [f == FLAG_CONVERGED for f in flags],
            "max_iterations": max_iters, "start": t_start, "done": done})
        sample.offer((len(run.records) - 1, r.w))
        if done >= run.seconds:
            break
    run.window_s = done
    # The quickest and the slowest batch of the window, from dispatch to
    # its answer on the host: a far-off run says whether one batch stalled.
    took = [r["done"] - r["start"] for r in run.records]
    run.info["batch_s_range"] = [min(took), max(took)]
    state["sample"] = sample.items


def release(run, state):
    run.kept = [(i, np.asarray(w, np.float64)) for i, w in state["sample"]]
    state.clear()


def check(run):
    checks = compare.Checks(run.config["limits"])
    ref = entry.reference(run)
    solved = {}
    for g in generate.pool(run.traffic["gates"], run.seed):
        solved[g] = ref.solve(g)
    for rec in run.records:
        for g, k in zip(rec["gates"], rec["iterations"]):
            checks.add("iters_gap", abs(k - solved[g][1]))
    for i, w in run.kept:
        for g, w_member in zip(run.records[i]["gates"], w):
            checks.add("field_gap", compare.field_gap(w_member, solved[g][0]))
    attempted = sum(len(r["gates"]) for r in run.records)
    failed = sum(not c for r in run.records for c in r["converged"])
    return checks, attempted, failed
