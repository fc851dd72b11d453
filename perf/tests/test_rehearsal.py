"""CPU rehearsals of every traffic driver and metric reader at a tiny grid:
the records each driver produces, the seeded gate pools, and what each
reader returns."""

import json
import types

import pytest

from perf import generate
from perf import run as harness
from perf.tests.conftest import rehearse, tiny

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


def _load(kind, name):
    return harness.load_module(harness.PERF / kind / f"{name}.py")


@pytest.mark.parametrize("trace", [False, True])
def test_solve_records(trace):
    result = rehearse("solve-2400x3200", backend="pallas", trace=trace)
    info = result["info"]
    assert info["backend"] == "pallas" and info["window_compiles"] == 0
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert set(result["checks"]) == {"iters_gap", "field_gap"}
    want = ({"iters.solve"} if trace else {"setup_s", "solve_s"})
    # Device-trace metrics have nothing to read off the TPU.
    assert set(result["metrics"]) == want
    assert list(result)[-1] == "checks"


def test_batch_records():
    result = rehearse("batch64-400x600")
    assert result["attempted"] % 64 == 0 and result["attempted"] >= 64
    assert set(result["metrics"]) == {"setup_s", "batch_solves_per_s"}
    traced = rehearse("batch64-400x600", trace=True)
    # Gates within 5% of f = 1 converge within a few iterations of one
    # another: little of the loop is spent on converged members.
    assert 0 <= traced["metrics"]["masked_iter_pct.batch"]["value"] < 5


def test_gate_pools():
    ladder = {"lo": 0.25, "hi": 4.0, "n": 5}
    assert generate.pool(ladder, 1) == generate.pool(ladder, 2) == \
        pytest.approx([0.25, 0.5, 1.0, 2.0, 4.0])
    uniform = {"lo": 0.95, "hi": 1.05, "n": 64, "draw": "uniform"}
    big = 2**33 + 5
    a, b = generate.pool(uniform, big), generate.pool(uniform, big + 1)
    # The same seed, the same inputs; another seed, other inputs within
    # the range, but always both of its ends (the slowest gate).
    assert a == generate.pool(uniform, big) and a != b
    for p in (a, b):
        assert len(p) == 64 and min(p) == 0.95 and max(p) == 1.05
    # Cycles through the gates are permutations of the whole pool.
    g = generate.gates(uniform, big)
    assert sorted(next(g) for _ in range(64)) == sorted(a)
    with pytest.raises(ValueError):
        generate.pool(dict(uniform, draw="normal"), 1)


def test_mesh_records_on_four_devices():
    result = rehearse("mesh2x2-2400x3200", backend="pallas-sharded")
    assert result["device"]["count"] == 4
    assert result["info"]["backend"] == "pallas-sharded"
    assert result["correct"], result["checks"]


def test_trace_seconds_keeps_the_trace_to_the_first_dispatches():
    result = rehearse("mesh2x2-2400x3200", backend="pallas-sharded",
                      trace=True, seconds=1.5, trace_seconds=0.3)
    traced = result["info"]["traced"]
    # The trace stopped between dispatches, past 0.3 s: whole dispatches,
    # fewer than the window's, which ran on to its end.
    assert 1 <= traced < result["attempted"]
    assert result["info"]["window_s"] >= 1.5
    assert result["correct"], result["checks"]


def test_the_auto_choice_decides_the_entry():
    from perf import entry

    run = types.SimpleNamespace(config=tiny(
        "solve-2400x3200")[2], devices=__import__("jax").devices()[:1])
    # Off the TPU the CLI's auto choice is the XLA solve, and that is the
    # entry the harness drives.
    assert entry.pick_backend(run) == "xla"
    assert set(entry.ENTRIES) == {"pallas", "xla", "pallas-sharded",
                                  "sharded"}
    with pytest.raises(SystemExit, match="rhs_gate"):
        entry.ENTRIES["sharded"](None, None, "float32")


def _trace(busy, collective=0.0, window=10.0, devices=1):
    dev = types.SimpleNamespace(busy_ns=busy * 1e9,
                                collective_ns=collective * 1e9)
    return types.SimpleNamespace(
        devices=[dev] * devices, window_s=window,
        busy_s=lambda: [busy] * devices,
        mean_busy_s=lambda: busy,
        collective_s=lambda: [collective] * devices)


def _run(records, trace=None, devices=1, **info):
    config = {"problem": {"M": 2400, "N": 3200}}
    return types.SimpleNamespace(
        records=records, trace=trace, devices=[None] * devices, info=info,
        config=config, window_s=10.0, setup_s=12.5,
        peak={"hbm_bytes_per_s": 819e9})


def test_readers():
    solves = [{"iterations": 2449}] * 4
    run = _run(solves, _trace(busy=8.0))
    assert _load("metrics", "solve_s").read(run) == 2.5
    assert _load("metrics", "setup_s").read(run) == 12.5
    assert _load("metrics", "iters.solve").read(run) == 2449
    roof = _load("metrics", "hbm_roofline_pct.solve").read(run)
    assert roof == pytest.approx(100 * 4 * 2449 * 184_454_424 / 819e9 / 8)
    assert _load("metrics", "idle_pct.solve").read(run) == pytest.approx(20)
    # Only the traced dispatches count against the traced busy time.
    sliced = _run(solves + [{"iterations": 10**6}], _trace(busy=8.0),
                  traced=4)
    assert _load("metrics", "hbm_roofline_pct.solve").read(sliced) == roof
    mesh = _run(solves, _trace(busy=4.0, collective=1.0, devices=4),
                devices=4)
    assert _load("metrics", "collective_pct.solve").read(mesh) == \
        pytest.approx(10)
    assert _load("metrics", "hbm_roofline_pct.solve").read(mesh) == \
        pytest.approx(roof * 8 / 4 / 4)
    batches = [{"iterations": [10, 20], "converged": [True, True],
                "max_iterations": 20}] * 3
    run = _run(batches, _trace(busy=5.0), bucket=2)
    assert _load("metrics", "masked_iter_pct.batch").read(run) == 25
    assert _load("metrics", "batch_solves_per_s").read(run) == 0.6
    # Nothing to read: no trace, or no peak for the device.
    for name in ("hbm_roofline_pct.solve", "idle_pct.solve",
                 "collective_pct.solve"):
        assert _load("metrics", name).read(_run(solves)) is None


def test_every_metric_has_a_reader():
    for kind in ("end_to_end", "per_layer"):
        for m in BENCH[kind]:
            assert hasattr(_load("metrics", m["name"]), "read"), m["name"]
