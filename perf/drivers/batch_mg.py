"""Closed loop of batched multigrid-preconditioned dispatches, one caller:
the window, release and check of ``batch.py`` (loaded from it, not
copied), with the entry ``perf/entry_batch_mg.py`` gives:
``solve_batched`` with the configuration's preconditioner.

``info.mg`` carries the program's own counters over the run's set-up and
window: its batched MG dispatches and their members
(``batched.mg.{dispatches,members}``), ``mg.solves`` and
``mg.hierarchy_cache.{hits,misses}``, and its gauges (``mg.levels``,
``mg.coarse_dense``: 1 where the coarsest level is solved with the dense
inverse). A program without a counter reads 0 there.
"""

from __future__ import annotations

import pathlib

from perf import entry, entry_batch_mg

_batch = entry.load_module(pathlib.Path(__file__).with_name("batch.py"))
release = _batch.release
check = _batch.check

COUNTERS = ("batched.mg.dispatches", "batched.mg.members", "mg.solves",
            "mg.hierarchy_cache.hits", "mg.hierarchy_cache.misses")
GAUGES = ("mg.levels", "mg.coarse_dense")


def _counts() -> dict:
    from poisson_tpu.obs import metrics

    snap = metrics.snapshot(rank=0)
    counts = {name: snap["counters"].get(name, 0) for name in COUNTERS}
    counts.update((name, snap["gauges"].get(name)) for name in GAUGES)
    return counts


def setup(run):
    import jax

    size = int(run.traffic["batch"])
    if size != int(run.traffic["gates"]["n"]):
        raise SystemExit("a batch takes the whole gate pool: batch "
                         f"{size} != pool {run.traffic['gates']['n']}")
    before = _counts()
    bucket, solve = entry_batch_mg.batch_entry(run)
    run.info["bucket"] = bucket
    # All-zero right-hand sides stop every member after one iteration (a
    # degenerate direction) on the bucket program the window drives.
    r = solve([0.0] * size)
    jax.block_until_ready((r.w, r.iterations))
    return {"solve": solve, "counts": before}


def window(run, state, span):
    before = state.pop("counts")
    _batch.window(run, state, span)
    after = _counts()
    run.info["mg"] = {name: after[name] - (before[name] if name in COUNTERS
                                           else 0)
                      for name in COUNTERS + GAUGES}
