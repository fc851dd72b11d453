"""Performance attribution & regression sentinel suite (CPU, tier-1).

Pins the three contracts ISSUE 4 introduced:

- the analytic 5-point-stencil cost model agrees with what XLA's
  ``cost_analysis()`` counts for a real compiled PCG iteration body,
  within ±25%, across dtype and scaling variants — the drift alarm that
  fires before any wall-clock regression does;
- the Prometheus exposition round-trips (names, types, values) through
  the textfile and the live ``/metrics`` endpoint;
- ``benchmarks/regress.py`` classifies a crashed run as such and never
  judges a CPU record against a TPU cohort, while flagging a synthetic
  2× slowdown with a nonzero exit.
"""

from __future__ import annotations

import json
import sys
import urllib.request

import pytest

from poisson_tpu import obs
from poisson_tpu.config import Problem
from poisson_tpu.obs import costs, export, metrics

sys.path.insert(0, str(__import__("pathlib").Path(
    __file__).resolve().parents[1]))
from benchmarks import regress  # noqa: E402

pytestmark = pytest.mark.perf_obs


@pytest.fixture(autouse=True)
def _clean_registry():
    metrics.reset()
    yield
    metrics.reset()
    obs.shutdown()


# -- analytic model vs compiled executable -------------------------------


@pytest.mark.parametrize("scaled", [True, False])
def test_model_agrees_with_cost_analysis_f32(scaled):
    report = costs.measured_iteration_cost(
        Problem(M=64, N=64), dtype="float32", scaled=scaled
    )
    assert report["hlo_bytes_per_iter"] is not None
    assert report["hlo_flops_per_iter"] is not None
    # The acceptance invariant: bytes per iteration within +-25%.
    assert report["model_agreement"] == pytest.approx(1.0, abs=0.25)
    assert report["hlo_flops_per_iter"] == pytest.approx(
        report["model_flops_per_iter"], rel=0.25
    )
    # Gauges landed in the registry for the exposition path.
    snap = metrics.snapshot()["gauges"]
    assert snap["cost.hlo_bytes_per_iter"] == report["hlo_bytes_per_iter"]
    assert snap["cost.model_agreement"] == report["model_agreement"]


def test_model_tracks_dtype_bytes_f64():
    # fp64 state doubles bytes, not FLOPs; the model must scale with the
    # dtype and still agree with the compiled program.
    r32 = costs.measured_iteration_cost(
        Problem(M=64, N=96), dtype="float32", scaled=True
    )
    r64 = costs.measured_iteration_cost(
        Problem(M=64, N=96), dtype="float64", scaled=True
    )
    assert r64["model_bytes_per_iter"] == 2 * r32["model_bytes_per_iter"]
    assert r64["model_agreement"] == pytest.approx(1.0, abs=0.25)


def test_analytic_model_closed_form():
    model = costs.analytic_iteration_cost(64, 64, dtype_bytes=4,
                                          scaled=True)
    pts = 65 * 65
    assert model["bytes"] == model["passes"] * pts * 4
    assert model["flops"] == model["flops_per_point"] * pts
    assert sum(model["terms"].values()) == model["passes"]


def test_solve_program_costs_and_memory():
    report = costs.solve_program_costs(Problem(M=48, N=48),
                                       dtype="float32")
    assert report["flops"] and report["flops"] > 0
    assert report["bytes_accessed"] and report["bytes_accessed"] > 0
    assert report["peak_memory_bytes"] and report["peak_memory_bytes"] > 0
    snap = metrics.snapshot()["gauges"]
    assert snap["cost.solve.peak_memory_bytes"] > 0


def test_roofline_summary_known_and_unknown_ceiling(monkeypatch):
    monkeypatch.delenv("POISSON_TPU_PEAK_GBPS", raising=False)
    problem = Problem(M=800, N=1200)
    # The committed TPU record: 989 iterations in 0.0397 s on a v5e.
    rl = costs.roofline_summary(problem, "xla", 4, 989, 0.0397,
                                device_kind="TPU v5 lite")
    assert rl["peak_gbps"] == 820.0
    # BENCH.md's own sanity arithmetic puts this run near the ceiling.
    assert 0.7 < rl["fraction"] < 1.1
    unknown = costs.roofline_summary(problem, "xla", 4, 989, 0.0397,
                                     device_kind="SomeCPU")
    assert unknown["fraction"] is None
    assert unknown["achieved_gbps"] == rl["achieved_gbps"]
    # Env override supplies a ceiling for unlisted parts.
    monkeypatch.setenv("POISSON_TPU_PEAK_GBPS", "100")
    forced = costs.roofline_summary(problem, "xla", 4, 989, 0.0397,
                                    device_kind="SomeCPU")
    assert forced["peak_gbps"] == 100.0
    # No pass model for this backend -> all-None, never a guess.
    native = costs.roofline_summary(problem, "native", 8, 989, 0.5)
    assert native["achieved_gbps"] is None


def test_solve_report_carries_roofline_fields(monkeypatch):
    import time

    from poisson_tpu.solvers.pcg import pcg_solve
    from poisson_tpu.utils.timing import solve_report

    monkeypatch.setenv("POISSON_TPU_PEAK_GBPS", "40")
    problem = Problem(M=40, N=40)
    t0 = time.perf_counter()
    result = pcg_solve(problem, dtype="float32")
    report = solve_report(problem, result, time.perf_counter() - t0,
                          compile_seconds=0.0, dtype="float32",
                          backend="xla")
    assert report.bytes_per_iter_model == 8.0 * 41 * 41 * 4
    assert report.achieved_gbps is not None and report.achieved_gbps > 0
    assert report.roofline_fraction is not None
    assert "attribution:" in report.table()
    # An unmodelled backend leaves the fields None, not wrong.
    report2 = solve_report(problem, result, 0.1, compile_seconds=0.0,
                           dtype="float32", backend="native")
    assert report2.achieved_gbps is None


# -- Prometheus exposition ----------------------------------------------


def test_exposition_round_trip():
    metrics.inc("pcg.solves.converged", 3)
    metrics.inc("time.compile_seconds", 1.25)
    metrics.gauge("roofline.fraction", 0.93)
    metrics.gauge("bench.note", "strings-have-no-exposition")
    text = export.render()
    parsed = export.parse_text(text)
    assert parsed["poisson_tpu_pcg_solves_converged"] == {
        "type": "counter", "value": 3.0}
    assert parsed["poisson_tpu_time_compile_seconds"] == {
        "type": "counter", "value": 1.25}
    assert parsed["poisson_tpu_roofline_fraction"] == {
        "type": "gauge", "value": 0.93}
    assert "poisson_tpu_bench_note" not in parsed
    assert "# skipped non-numeric gauge 'bench.note'" in text


def test_exposition_textfile(tmp_path):
    metrics.inc("watchdog.beats", 7)
    path = tmp_path / "sub" / "metrics.prom"
    export.write_textfile(str(path))
    parsed = export.parse_text(path.read_text())
    assert parsed["poisson_tpu_watchdog_beats"]["value"] == 7.0


def test_metrics_http_endpoint():
    metrics.inc("pcg.solves.converged")
    server = export.start_http_server(port=0)
    try:
        port = server.server_port
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ).read().decode()
        assert export.parse_text(body)[
            "poisson_tpu_pcg_solves_converged"]["value"] == 1.0
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=5)
    finally:
        export.stop_http_server(server)


def test_configure_serves_and_snapshots(tmp_path):
    prom = tmp_path / "m.prom"
    obs.configure(prom_path=str(prom), metrics_port=0)
    obs.inc("pcg.solves.converged")
    port = int(metrics.snapshot()["gauges"]["export.http_port"])
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
    assert "poisson_tpu_pcg_solves_converged" in body
    obs.shutdown()
    assert "poisson_tpu_pcg_solves_converged" in prom.read_text()
    # Endpoint is down after shutdown.
    with pytest.raises(OSError):
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=1)


# -- profiler capture ----------------------------------------------------


def test_profile_capture_writes_artifacts(tmp_path):
    import os

    import jax
    import jax.numpy as jnp

    from poisson_tpu.obs import profile

    with profile.capture("unit", profile_dir=str(tmp_path)) as out:
        jax.block_until_ready(jnp.ones((16, 16)) * 2)
    files = sum(len(f) for _, _, f in os.walk(out))
    assert files > 0
    assert metrics.get("profile.captures") == 1


def test_profile_capture_noop_when_unconfigured():
    from poisson_tpu.obs import profile

    assert not profile.enabled()
    with profile.capture("unit") as out:
        assert out is None
    assert metrics.get("profile.captures") == 0


# -- regression sentinel -------------------------------------------------


def _bench_result(value, platform, *, grid=(800, 1200),
                  backend="xla", dtype="float32"):
    return {"metric": "mlups", "value": value, "unit": "MLUPS",
            "detail": {"grid": list(grid), "iterations": 989,
                       "solve_seconds": 0.04, "dtype": dtype,
                       "backend": backend, "devices": 1,
                       "platform": platform}}


def _fixture_history():
    recs = []
    for i, v in enumerate([23840.0, 23600.0, 23950.0]):
        recs.append(regress.record_from_result(
            _bench_result(v, "tpu"), f"tpu-{i}"))
    recs.append(regress.record_from_result(
        _bench_result(160.0, "cpu"), "cpu"))
    return recs


def test_regress_cpu_record_never_judges_tpu_cohort():
    verdict = regress.evaluate(_fixture_history())
    assert verdict["verdict"] == "ok"
    by_source = {v["source"]: v for v in verdict["records"]}
    # 150x slower, but on another platform: its own (empty) cohort.
    assert by_source["cpu"]["classification"] == "no_baseline"
    assert all(by_source[f"tpu-{i}"]["classification"] == "ok"
               for i in range(3))


def test_regress_flags_2x_slowdown():
    history = _fixture_history()
    history.append(regress.record_from_result(
        _bench_result(11900.0, "tpu"), "tpu-slow"))
    verdict = regress.evaluate(history)
    assert verdict["verdict"] == "regression"
    assert "tpu-slow" in verdict["regressions"]
    # The CPU record still is not part of the alarm.
    by_source = {v["source"]: v for v in verdict["records"]}
    assert by_source["cpu"]["classification"] == "no_baseline"


def test_regress_jitter_is_not_a_regression():
    history = _fixture_history()
    history.append(regress.record_from_result(
        _bench_result(22700.0, "tpu"), "tpu-jitter"))  # -5%
    verdict = regress.evaluate(history)
    assert verdict["verdict"] == "ok"


def test_regress_cohorts_split_by_backend_and_dtype():
    history = [
        regress.record_from_result(
            _bench_result(23840.0, "tpu"), "tpu-xla"),
        # A pallas record at ~1.3x xla must not make xla look slow, nor
        # vice versa: different cohort.
        regress.record_from_result(
            _bench_result(31000.0, "tpu", backend="pallas_fused"),
            "tpu-pallas"),
    ]
    verdict = regress.evaluate(history)
    by_source = {v["source"]: v for v in verdict["records"]}
    assert by_source["tpu-xla"]["classification"] == "no_baseline"
    assert by_source["tpu-pallas"]["classification"] == "no_baseline"


def _snapshot(tmp_path, name, rc, parsed):
    path = tmp_path / name
    path.write_text(json.dumps({"n": 1, "cmd": "python bench.py", "rc": rc,
                                "tail": "", "parsed": parsed}))
    return path


def test_regress_main_classifies_driver_snapshots(tmp_path, capsys):
    # A crashed run is evidence (failed_run) but never a baseline; a CPU
    # run and TPU runs form their own cohorts: verdict ok, exit 0.
    paths = [_snapshot(tmp_path, "BENCH_r01.json", 1, None),
             _snapshot(tmp_path, "BENCH_r02.json", 0,
                       _bench_result(160.0, "cpu"))]
    paths += [_snapshot(tmp_path, f"BENCH_r1{i}.json", 0,
                        _bench_result(v, "tpu"))
              for i, v in enumerate([23840.0, 23600.0])]
    rc = regress.main(["--history", *map(str, paths)])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["verdict"] == "ok"
    by_source = {v["source"]: v for v in out["records"]}
    assert by_source["BENCH_r01.json"]["classification"] == "failed_run"
    assert by_source["BENCH_r02.json"]["classification"] == "no_baseline"
    assert by_source["BENCH_r10.json"]["classification"] == "ok"


def test_regress_main_nonzero_on_synthetic_slowdown(tmp_path, capsys):
    slow = _snapshot(tmp_path, "BENCH_r99.json", 0,
                     _bench_result(11900.0, "tpu"))
    session = tmp_path / "session.jsonl"
    session.write_text("".join(
        json.dumps({"step": f"bench_{i}", "ok": True,
                    "result": _bench_result(v, "tpu")}) + "\n"
        for i, v in enumerate([23840.0, 23600.0, 23950.0])))
    rc = regress.main(["--history", str(slow), "--session", str(session)])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["verdict"] == "regression"
    assert "BENCH_r99.json" in out["regressions"]


def test_regress_loaders_on_synthetic_artifacts(tmp_path):
    crashed = regress.load_driver_artifact(
        _snapshot(tmp_path, "crash.json", 1, None))
    assert crashed[0]["failed"]
    ok = regress.load_driver_artifact(
        _snapshot(tmp_path, "ok.json", 0, _bench_result(160.0, "cpu")))
    assert ok[0]["platform"] == "cpu" and not ok[0]["failed"]
    garbled = tmp_path / "garbled.json"
    garbled.write_text("{not json")
    assert regress.load_driver_artifact(garbled)[0]["failed"]
    session = tmp_path / "session.jsonl"
    session.write_text(json.dumps({"step": "identity", "result": {}})
                       + "\n" + json.dumps(
                           {"step": "bench", "result":
                            _bench_result(23839.9, "tpu")}) + "\n")
    recs = regress.load_session(session)
    assert len(recs) == 1              # the identity step is no record
    assert recs[0]["platform"] == "tpu" and recs[0]["value"] == 23839.9


# -- bench integration (subprocess: needs a single-device env) ----------


@pytest.mark.slow
def test_bench_record_carries_costs_and_platform(tmp_path):
    import os
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)     # single CPU device, like the driver
    env["POISSON_TPU_METRICS_OUT"] = str(tmp_path / "metrics.json")
    proc = subprocess.run(
        [sys.executable, "bench.py", "64", "64"],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=str(__import__("pathlib").Path(
            __file__).resolve().parents[1]),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    record = json.loads(proc.stdout.strip().splitlines()[-1])
    assert record["detail"]["platform"] == "cpu"
    assert record["detail"]["backend"] == "xla"
    block = record["costs"]
    assert block["model_agreement"] == pytest.approx(1.0, abs=0.25)
    assert block["hlo_bytes_per_iter"] > 0
    assert block["peak_memory_bytes"] > 0
    snap = json.loads((tmp_path / "metrics.json").read_text())
    assert snap["gauges"]["cost.model_agreement"] == pytest.approx(
        block["model_agreement"])
