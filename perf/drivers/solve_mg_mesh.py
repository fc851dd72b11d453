"""Closed loop of single multigrid-preconditioned solves over the cell's
mesh, one caller: the window and release of ``solve.py`` (loaded from it,
not copied), with the entry ``perf/entry_mg_mesh.py`` gives:
``pcg_solve(preconditioner="mg", mesh=...)``, where ``cli._pick_backend``
sends ``--preconditioner mg`` on a mesh. The check is ``solve.py``'s
against the configuration's reference placed over the same mesh
(``perf/reference/ellipse_mgpcg_mesh.py``).

``info.mg`` carries the program's MG counters over the run's set-up and
window (``mg.solves``, ``mg.hierarchy_cache.{hits,misses}``) and its gauges
(``mg.levels``, ``mg.coarse_dense``, ``mg.pallas_levels``: levels on the
strip kernels, ``mg.replicated_from``: the level where the cycle leaves
the shards). ``info.reference_bytes`` holds the most device memory a chip
had in use with the reference's arrays in place.
"""

from __future__ import annotations

import pathlib

from perf import compare, entry, entry_mg_mesh

_solve = entry.load_module(pathlib.Path(__file__).with_name("solve.py"))
window_solves = _solve.window
release = _solve.release

COUNTERS = ("mg.solves", "mg.hierarchy_cache.hits",
            "mg.hierarchy_cache.misses")
GAUGES = ("mg.levels", "mg.coarse_dense", "mg.pallas_levels",
          "mg.replicated_from")


def _counts() -> dict:
    from poisson_tpu.obs import metrics

    snap = metrics.snapshot(rank=0)
    counts = {name: snap["counters"].get(name, 0) for name in COUNTERS}
    counts.update((name, snap["gauges"].get(name)) for name in GAUGES)
    return counts


def setup(run):
    import jax

    before = _counts()
    backend, solve = entry_mg_mesh.solve_entry(run)
    run.info["backend"] = backend
    # A zero right-hand side stops the solve after one iteration (a
    # degenerate direction), on the program the window drives.
    r = solve(0.0)
    jax.block_until_ready((r.w, r.iterations))
    return {"solve": solve, "counts": before}


def window(run, state, span):
    before = state.pop("counts")
    window_solves(run, state, span)
    after = _counts()
    run.info["mg"] = {name: after[name] - (before[name] if name in COUNTERS
                                           else 0)
                      for name in COUNTERS + GAUGES}


def check(run):
    delta = run.config["problem"]["delta"]
    checks = compare.Checks(run.config["limits"])
    ref = entry_mg_mesh.reference(run)
    for i, w in run.kept:
        rec = run.records[i]
        w_ref, k_ref, _ = ref.solve(rec["gate"])
        checks.add("iters_gap", abs(rec["iterations"] - k_ref))
        checks.add("field_gap", compare.field_gap(w, w_ref))
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in run.devices]
    if all(b is not None for b in in_use):
        run.info["reference_bytes"] = max(in_use)
    failed = sum(not (r["diff"] < delta) for r in run.records)
    return checks, len(run.records), failed
