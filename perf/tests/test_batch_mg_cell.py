"""The batched multigrid cell ``batch32-mg-1600x2400`` on the CPU: its
driver, check and control rehearsed at a grid that coarsens (200x300,
three levels down to the same 50x75 coarsest level) with three members
padded to a bucket of four, a batch whose members carry one another's
fields caught by the check, the compulsory bytes of
``perf/work_batch_mg.py``, and its roofline reader."""

import types
from unittest import mock

import jax.numpy as jnp
import pytest

from perf import control_batch_mg, entry_batch_mg, work_batch_mg, work_mg
from perf import run as harness
from perf.tests.conftest import rehearse

CELL = "batch32-mg-1600x2400"
SMALL = {"M": 200, "N": 300, "batch": 3,
         "gates": {"lo": 0.95, "hi": 1.05, "n": 3, "draw": "uniform"}}


def _load(name):
    return harness.load_module(harness.PERF / "metrics" / f"{name}.py")


@pytest.mark.parametrize("traced", [False, True])
def test_batch_mg_records(traced):
    result = rehearse(CELL, trace=traced, **SMALL)
    info = result["info"]
    assert info["bucket"] == 4 and info["window_compiles"] == 0
    assert result["correct"], result["checks"]
    assert set(result["checks"]) == {"iters_gap", "field_gap"}
    batches = result["attempted"] // 3
    assert batches >= 1 and result["attempted"] == 3 * batches
    assert result["failed"] == 0
    mg = info["mg"]
    assert mg["mg.levels"] == 3 and mg["mg.coarse_dense"] == 1
    # The warm-up's dispatch and the window's, each of three members (the
    # padding member is no solve).
    assert mg["batched.mg.dispatches"] == batches + 1
    assert mg["batched.mg.members"] == mg["mg.solves"] == 3 * (batches + 1)
    assert mg["mg.hierarchy_cache.misses"] <= 1
    assert mg["mg.hierarchy_cache.hits"] + mg[
        "mg.hierarchy_cache.misses"] == batches + 1
    # Device-trace metrics have nothing to read off the TPU.
    want = ({"masked_iter_pct.batch"} if traced
            else {"setup_s", "batch_solves_per_s"})
    assert set(result["metrics"]) == want


def test_batch_mg_control_is_not_correct():
    with control_batch_mg.in_place():
        result = rehearse(CELL, **SMALL)
    assert not result["correct"], result["checks"]


def _roll(w):
    return jnp.roll(w, 1, axis=0)


def _first_for_all(w):
    return jnp.broadcast_to(w[:1], w.shape)


@pytest.mark.parametrize("swap", [_roll, _first_for_all],
                         ids=["rolled", "first-for-all"])
def test_batch_mg_wrong_member_is_not_correct(swap):
    """Members answered with another member's field: every batch holds
    both ends of the gate pool, so a rolled batch (each of the three
    pairs compared once) or member 0's field for all reads a gap of at
    least 4.8% against some member's reference, over ``field_gap``."""
    entry_of = entry_batch_mg.batch_entry

    def swapped(run):
        bucket, solve = entry_of(run)

        def solve_swapped(gates):
            r = solve(gates)
            return r._replace(w=swap(r.w))

        return bucket, solve_swapped

    with mock.patch.object(entry_batch_mg, "batch_entry", swapped):
        result = rehearse(CELL, **SMALL)
    assert result["failed"] == 0
    assert result["checks"]["field_gap"]["value"] > 0.04, result["checks"]
    assert not result["correct"], result["checks"]


def test_batch_mg_bytes():
    per = work_mg.mg_bytes_per_iteration(1600, 2400)
    inverse = work_batch_mg.inverse_bytes(1600, 2400)
    member = work_batch_mg.member_bytes_per_iteration(1600, 2400)
    # The 50x75 coarsest level: 49 x 74 unknowns, a 52.6 MB fp32 inverse.
    assert inverse == (49 * 74) ** 2 * 4 == 52_591_504
    # 6 CG-state passes and 2 a level (8/3 in all) over the 15.4 MB grid.
    fine = 1601 * 2401 * 4
    assert member == 6 * fine + sum(2 * (m + 1) * (n + 1) * 4 for m, n in
                                    work_mg.levels(1600, 2400))
    assert member + inverse == per and 133.2e6 < member < 133.3e6
    # A batch of 32 members of 16 iterations: the inverse once a loop
    # iteration, about 69 GB in all.
    total = work_batch_mg.batch_bytes(1600, 2400, 32 * 16, 16)
    assert total == 32 * 16 * member + 16 * inverse
    assert 69.0e9 < total < 69.1e9


def test_batch_mg_roofline_reader():
    batches = [{"iterations": [16, 15, 1], "converged": [True, True, False],
                "max_iterations": 16}] * 5
    tr = types.SimpleNamespace(devices=[object()], busy_s=lambda: [2.0])
    run = types.SimpleNamespace(
        records=batches, trace=tr, devices=[None], info={"traced": 4},
        config={"problem": {"M": 1600, "N": 2400}},
        peak={"hbm_bytes_per_s": 819e9})
    got = _load("hbm_roofline_pct.batch_mg").read(run)
    # Four traced batches: 31 member-iterations of converged members and
    # 16 loop iterations each.
    want = work_batch_mg.batch_bytes(1600, 2400, 4 * 31, 4 * 16)
    assert got == pytest.approx(100 * want / 819e9 / 2.0)
    assert _load("hbm_roofline_pct.batch_mg").read(
        types.SimpleNamespace(trace=None, peak=None)) is None
