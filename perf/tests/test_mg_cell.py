"""The multigrid cell ``mg-6400x9600`` on the CPU: its driver, check and
control rehearsed at a grid that coarsens (200x300, three levels down to
the same 50x75 coarsest level), the compulsory bytes of ``perf/work_mg.py``,
and the level and span reduction of ``perf/mg_trace.py`` on intervals made
by hand and on a trace recorded on the chip without the MG names."""

import pathlib
import types

import pytest

from perf import control_mg, mg_trace, trace, work, work_mg
from perf import run as harness
from perf.tests.conftest import rehearse

CELL = "mg-6400x9600"
GRID = {"M": 200, "N": 300}
DATA = pathlib.Path(__file__).with_name("data")


def _load(name):
    return harness.load_module(harness.PERF / "metrics" / f"{name}.py")


@pytest.mark.parametrize("traced", [False, True])
def test_mg_records(traced):
    result = rehearse(CELL, trace=traced, **GRID)
    info = result["info"]
    assert info["backend"] == "xla" and info["window_compiles"] == 0
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 2 and result["failed"] == 0
    mg = info["mg"]
    assert mg["mg.levels"] == 3 and mg["mg.coarse_dense"] == 1
    # One hierarchy at most (the process may hold it already), then a
    # cache hit a solve: the warm-up's and the window's.
    assert mg["mg.hierarchy_cache.misses"] <= 1
    assert mg["mg.solves"] == result["attempted"] + 1
    assert mg["mg.hierarchy_cache.hits"] + mg[
        "mg.hierarchy_cache.misses"] == mg["mg.solves"]
    # Device-trace metrics have nothing to read off the TPU.
    want = {"iters.solve"} if traced else {"setup_s", "solve_s"}
    assert set(result["metrics"]) == want


def test_mg_control_is_not_correct():
    with control_mg.in_place():
        result = rehearse(CELL, **GRID)
    assert result["info"]["backend"] == "control-bfloat16"
    assert not result["correct"], result["checks"]


def test_mg_bytes():
    assert work_mg.levels(6400, 9600) == [
        (6400, 9600), (3200, 4800), (1600, 2400), (800, 1200),
        (400, 600), (200, 300), (100, 150), (50, 75)]
    assert work_mg.coarsest_unknowns(6400, 9600) == 49 * 74 == 3626
    per = work_mg.mg_bytes_per_iteration(6400, 9600)
    fine = 6401 * 9601 * 4
    # 6 CG-state passes, 2 a level (8/3 in all), the 52.6 MB inverse:
    # about 8.9 fine-grid passes, 2.18 GB.
    assert 8.85 < per / fine < 8.9 and 2.18e9 < per < 2.19e9
    assert per - work.cg_state_bytes_per_iteration(6400, 9600) == (
        sum(2 * (m + 1) * (n + 1) * 4 for m, n in work_mg.levels(6400, 9600))
        + 3626 * 3626 * 4)
    # A grid that cannot coarsen is one level, solved by the dense inverse.
    assert work_mg.levels(9, 40) == [(9, 40)]


def _hlo(name, opcode, level=None):
    attrs = "" if level is None else f', frontend_attributes={{mg_level="{level}"}}'
    return f"%{name} = f32[51,76]{{1,0}} {opcode}(f32[51,76] %x){attrs}"


def _made():
    window = (0, 100)
    events = {"/device:TPU:0": [
        (_hlo("f.1", "fusion"), 0, 10),            # the CG recurrence
        (_hlo("f.2", "fusion", 0), 10, 40),
        (_hlo("f.3", "fusion", 1), 40, 50),
        (_hlo("f.4", "fusion", 2), 50, 55),
        (_hlo("r.1", "reduce", 2), 52, 60),        # overlaps f.4
        (_hlo("w.1", "while", 1), 0, 100),         # encloses, not counted
        (_hlo("f.5", "fusion", 0), 60, 80),
        (_hlo("f.6", "fusion", 0), 95, 120),       # clipped to the window
    ]}
    host = [("perf.window",) + window, ("perf.dispatch", 80, 95)]
    program = [("pcg_solve", 80, 95), ("pcg_solve.prepare", 80, 90),
               ("pcg_solve.launch", 90, 95), ("other.prepare", 0, 100)]
    summary = trace.summarize(events, host, window)
    return summary, mg_trace.summarize(events, host + program, window)


def test_levels_and_phases_from_intervals():
    summary, found = _made()
    assert mg_trace.level_of(_hlo("f", "fusion", 12)) == 12
    assert mg_trace.level_of(_hlo("f", "fusion")) is None
    dev = "/device:TPU:0"
    assert found.level_ns(dev) == 30 + 10 + 10 + 20 + 5
    assert found.level_ns(dev, 2) == 10
    assert found.level_ns(dev, 1, 1) == 10
    assert found.phase("prepare") == [(80, 90)]
    report = mg_trace.report(summary, found)
    assert report["level_s"] == pytest.approx(
        {"0": 55e-9, "1": 10e-9, "2": 10e-9})
    assert report["untagged_s"] == pytest.approx(10e-9)
    assert report["idle_s"]["prepare"] == pytest.approx(10e-9)
    assert report["idle_s"]["launch"] == pytest.approx(5e-9)

    run = types.SimpleNamespace(
        trace=summary, _mg_trace=found, devices=[None],
        config={"problem": {"M": 400, "N": 600},
                "mg": {"coarse_below": [200, 300]}})
    busy = summary.devices[0].busy_ns
    assert busy == 85
    assert _load("vcycle_pct.mg").read(run) == pytest.approx(100 * 75 / 85)
    # 400x600 -> 200x300 -> 100x150 -> 50x75: levels 1 and below.
    assert mg_trace.coarse_from(run) == 1
    assert _load("coarse_levels_pct.mg").read(run) == pytest.approx(
        100 * 20 / 85)
    assert _load("prep_idle_pct.mg").read(run) == pytest.approx(10)


def test_roofline_reader():
    records = [{"iterations": 18}] * 5
    tr = types.SimpleNamespace(devices=[object()], busy_s=lambda: [4.0])
    run = types.SimpleNamespace(
        records=records, trace=tr, devices=[None], info={"traced": 4},
        config={"problem": {"M": 6400, "N": 9600}},
        peak={"hbm_bytes_per_s": 819e9})
    got = _load("hbm_roofline_pct.mg").read(run)
    per = work_mg.mg_bytes_per_iteration(6400, 9600)
    assert got == pytest.approx(100 * 4 * 18 * per / 819e9 / 4.0)
    assert _load("hbm_roofline_pct.mg").read(
        types.SimpleNamespace(trace=None, peak=None)) is None


def test_a_trace_without_the_names_reads_none():
    """The chip trace of a program without the MG tags and spans (a
    Pallas solve): every MG reader reports nothing, none raises."""
    path = str(DATA / "spans-1chip.xplane.pb")
    events, host, window = trace.read_xspace(path)
    run = types.SimpleNamespace(
        trace=trace.summarize(events, host, window),
        _mg_trace=mg_trace.read(path), devices=[None],
        config={"problem": {"M": 2400, "N": 3200},
                "mg": {"coarse_below": [200, 300]}})
    assert not run._mg_trace.tagged() and run._mg_trace.host == []
    for name in ("vcycle_pct.mg", "coarse_levels_pct.mg", "prep_idle_pct.mg"):
        assert _load(name).read(run) is None
    # No trace at all.
    for name in ("vcycle_pct.mg", "coarse_levels_pct.mg", "prep_idle_pct.mg"):
        assert _load(name).read(types.SimpleNamespace(trace=None)) is None
