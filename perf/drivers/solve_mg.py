"""Closed loop of single multigrid-preconditioned solves, one caller: the
window, release and check of ``solve.py`` (loaded from it, not copied),
with the entry ``perf/entry_mg.py`` gives: ``pcg_solve`` with the
configuration's preconditioner, where ``cli._pick_backend`` sends
``--preconditioner mg``.

``info.mg`` carries the program's own MG counters over the run's set-up and
window (``mg.solves``, ``mg.hierarchy_cache.{hits,misses}``) and its gauges
(``mg.levels``, ``mg.coarse_dense``: 1 where the coarsest level is solved
with the dense inverse).
"""

from __future__ import annotations

import pathlib

from perf import entry, entry_mg

_solve = entry.load_module(pathlib.Path(__file__).with_name("solve.py"))
release = _solve.release
check = _solve.check

COUNTERS = ("mg.solves", "mg.hierarchy_cache.hits",
            "mg.hierarchy_cache.misses")
GAUGES = ("mg.levels", "mg.coarse_dense")


def _counts() -> dict:
    from poisson_tpu.obs import metrics

    snap = metrics.snapshot(rank=0)
    counts = {name: snap["counters"].get(name, 0) for name in COUNTERS}
    counts.update((name, snap["gauges"].get(name)) for name in GAUGES)
    return counts


def setup(run):
    import jax

    before = _counts()
    backend, solve = entry_mg.solve_entry(run)
    run.info["backend"] = backend
    # A zero right-hand side stops the solve after one iteration (a
    # degenerate direction), on the program the window drives.
    r = solve(0.0)
    jax.block_until_ready((r.w, r.iterations))
    return {"solve": solve, "counts": before}


def window(run, state, span):
    before = state.pop("counts")
    _solve.window(run, state, span)
    after = _counts()
    run.info["mg"] = {name: after[name] - (before[name] if name in COUNTERS
                                           else 0)
                      for name in COUNTERS + GAUGES}
