"""Unified telemetry (`poisson_tpu.obs`): spans, counters, streaming.

The acceptance surface of the observability subsystem:

- emitted trace files load as valid Chrome trace JSON (required
  ``ph``/``ts``/``name`` keys) and open-in-Perfetto structure;
- counters record the expected restart/escalation counts under fault
  injection (``testing.faults``), and the resilient driver surfaces its
  recovery history on SUCCESS, not only inside ``DivergenceError``;
- a CPU-mesh sharded solve produces mergeable per-rank event logs;
- streaming enabled vs disabled leaves iteration counts identical (the
  golden-count guarantee is structural: ``stream_every`` is a static
  compile flag);
- the CLI acceptance command wires the whole stack end to end.
"""

from __future__ import annotations

import json
import warnings

import numpy as np
import pytest

from poisson_tpu import obs
from poisson_tpu.config import Problem
from poisson_tpu.obs import metrics, stream
from poisson_tpu.obs.trace import TraceRecorder, load_events, merge_trace_dir
from poisson_tpu.solvers.pcg import FLAG_CONVERGED, pcg_solve
from poisson_tpu.solvers.resilient import RecoveryPolicy, pcg_solve_resilient
from poisson_tpu.testing.faults import FaultPlan, chunk_hook, inject_nan

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Telemetry state is process-global; each test starts and ends
    clean so order cannot leak counters or recorders across tests."""
    obs.shutdown()
    metrics.reset()
    yield
    obs.shutdown()
    metrics.reset()


def _load_trace(path) -> list[dict]:
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert events, f"no traceEvents in {path}"
    for ev in events:
        for key in ("ph", "ts", "name"):
            assert key in ev, f"trace event missing {key!r}: {ev}"
    return events


# ---------------------------------------------------------------------------
# Spans / trace files
# ---------------------------------------------------------------------------


def test_trace_file_is_valid_chrome_trace(tmp_path):
    rec = obs.configure(trace_dir=str(tmp_path))
    with obs.span("outer", grid="40x40"):
        with obs.span("inner"):
            pass
    obs.event("marker", k=7)
    obs.finalize()
    events = _load_trace(rec.trace_path)
    by_name = {ev["name"]: ev for ev in events}
    assert {"outer", "inner", "marker"} <= set(by_name)
    # Spans are complete events with real durations; nesting is recorded
    # in wall time (inner inside outer).
    assert by_name["outer"]["ph"] == "X" and by_name["outer"]["dur"] >= 0
    assert by_name["marker"]["ph"] == "i"
    assert by_name["inner"]["ts"] >= by_name["outer"]["ts"]
    # Every event is attributed to this process's rank.
    assert {ev["pid"] for ev in events} == {rec.rank}


def test_event_log_schema_and_span_nesting(tmp_path):
    obs.configure(trace_dir=str(tmp_path))
    with obs.span("phase"):
        with obs.span("step"):
            obs.event("tick", k=1)
    obs.finalize()
    records = load_events(str(tmp_path))
    assert [r["name"] for r in records] == [
        "phase", "step", "tick", "step", "phase"
    ]
    for r in records:
        for key in ("at_unix", "at_mono", "rank", "kind", "name"):
            assert key in r
    step_end = [r for r in records
                if r["kind"] == "span_end" and r["name"] == "step"][0]
    assert step_end["span_path"] == "phase/step"
    assert step_end["seconds"] >= 0


def test_unconfigured_telemetry_is_a_noop():
    """Call sites never guard: spans/events with no recorder must work
    (and record nothing)."""
    assert obs.recorder() is None
    with obs.span("anything"):
        obs.event("nothing", a=1)
    assert obs.recent_events() == []
    obs.finalize()  # idempotent with no configuration


# ---------------------------------------------------------------------------
# Counters under fault injection
# ---------------------------------------------------------------------------


def test_restart_counters_match_injected_fault():
    p = Problem(M=40, N=40)
    hook = chunk_hook(FaultPlan(nan_at_iteration=15))
    with pytest.warns(RuntimeWarning, match="nonfinite.*restart"):
        res = pcg_solve_resilient(p, chunk=10, on_chunk=hook)
    assert int(res.flag) == FLAG_CONVERGED
    assert metrics.get("resilient.restarts") == 1
    assert metrics.get("resilient.escalations") == 0
    # Recovery history is surfaced on SUCCESS too (satellite: it used to
    # exist only inside DivergenceError).
    assert res.restarts == 1
    assert len(res.recovery_history) == 1
    k, verdict, action = res.recovery_history[0]
    assert verdict == "nonfinite" and action.startswith("restart@")


def test_escalation_counter_counts_the_ladder():
    p = Problem(M=40, N=40)
    count = {"n": 0}

    def hook(state, chunks_done):
        if count["n"] < 2 and int(state.k) >= 10:
            count["n"] += 1
            return inject_nan(state)
        return None

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        res = pcg_solve_resilient(p, dtype="float32", chunk=10,
                                  on_chunk=hook)
    assert int(res.flag) == FLAG_CONVERGED
    assert metrics.get("resilient.restarts") == 2
    assert metrics.get("resilient.escalations") == 1
    assert res.restarts == 2
    assert any("escalate->" in action
               for _, _, action in res.recovery_history)


def test_clean_solve_reports_no_recovery():
    p = Problem(M=40, N=40)
    res = pcg_solve_resilient(p, chunk=10,
                              policy=RecoveryPolicy(stagnation_window=200))
    assert res.restarts == 0 and res.recovery_history == ()
    assert metrics.get("resilient.restarts") == 0


def test_checkpoint_counters(tmp_path):
    from poisson_tpu.solvers import checkpoint as ckpt
    from poisson_tpu.testing.faults import corrupt_file

    p = Problem(M=40, N=40)
    path = str(tmp_path / "ck.npz")
    ckpt.pcg_solve_checkpointed(p, path, chunk=10, keep_checkpoint=True)
    writes = metrics.get("checkpoint.writes")
    assert writes >= 4          # 50 iterations / chunk 10
    # Corrupt the newest generation: the reload falls back and counts
    # the corruption (a flipped byte lands either in array payload —
    # CRC catch — or in the zip structure — unreadable) plus the
    # generation fallback.
    corrupt_file(path, "flip")
    fp = ckpt._fingerprint(p, "float64", False)
    with pytest.warns(RuntimeWarning):
        state = ckpt.load_state(path, fp)
    assert state is not None    # fell back to ck.npz.1
    assert (metrics.get("checkpoint.crc_failures")
            + metrics.get("checkpoint.corrupt")) == 1
    assert metrics.get("checkpoint.generation_fallbacks") == 1


def test_crc_failure_counter_on_payload_flip(tmp_path):
    """A flip confined to array payload passes the zip/npy parsers and
    is caught ONLY by the CRC seal — the counter must say so."""
    import numpy as np_

    from poisson_tpu.solvers import checkpoint as ckpt

    p = Problem(M=40, N=40)
    path = str(tmp_path / "ck.npz")
    ckpt.pcg_solve_checkpointed(p, path, chunk=10, keep_checkpoint=True)
    # Rewrite the newest generation uncompressed-equivalent: flip one
    # byte inside the 'w' array payload specifically.
    with np_.load(path) as data:
        arrays = {k: np_.array(data[k]) for k in data.files}
    w = arrays["w"]
    w.view(np_.uint8).reshape(-1)[w.nbytes // 2] ^= 0xFF
    np_.savez(path, **arrays)       # CRC record kept, payload changed
    fp = ckpt._fingerprint(p, "float64", False)
    with pytest.warns(RuntimeWarning):
        state = ckpt.load_state(path, fp)
    assert state is not None
    assert metrics.get("checkpoint.crc_failures") == 1


# ---------------------------------------------------------------------------
# Sharded solves: mergeable per-rank event logs
# ---------------------------------------------------------------------------


def test_sharded_solve_produces_mergeable_per_rank_logs(tmp_path):
    """A sharded solve records telemetry under its rank; logs written by
    other ranks of a multihost run (simulated here — single-process CPU
    meshes are all rank 0) merge into one timeline."""
    import jax

    from poisson_tpu.parallel import make_solver_mesh
    from poisson_tpu.parallel.checkpoint_sharded import (
        pcg_solve_sharded_checkpointed,
    )

    tdir = str(tmp_path)
    obs.configure(trace_dir=tdir, rank=0)
    p = Problem(M=40, N=40)
    mesh = make_solver_mesh(jax.devices()[:4], grid=(2, 2))
    with obs.span("sharded_solve"):
        res = pcg_solve_sharded_checkpointed(
            p, mesh, str(tmp_path / "ck.npz"), chunk=10,
        )
    assert int(res.iterations) == 50
    obs.finalize()

    # A second rank's recorder, as another host of the same run would
    # write it (same dir, different rank).
    other = TraceRecorder(trace_dir=tdir, rank=1)
    with other.span("sharded_solve"):
        other.event("checkpoint.write", k=10)
    other.close()

    records = load_events(tdir)
    assert {r["rank"] for r in records} == {0, 1}
    assert [r["at_unix"] for r in records] == sorted(
        r["at_unix"] for r in records
    )
    # Rank 0's real solve emitted checkpoint telemetry.
    assert any(r["rank"] == 0 and r["name"] == "checkpoint.write"
               for r in records)

    merged = merge_trace_dir(tdir)
    pids = {ev["pid"] for ev in merged["traceEvents"]}
    assert pids == {0, 1}
    # The merged document itself is a valid Chrome trace.
    _load_trace(str(tmp_path / "trace-merged.trace.json"))


# ---------------------------------------------------------------------------
# Streaming: parity and recording
# ---------------------------------------------------------------------------


def test_streaming_keeps_iterations_bit_for_bit():
    p = Problem(M=40, N=40)
    baseline = pcg_solve(p)
    sink = stream.StreamSink()
    stream.set_sink(sink)
    streamed = pcg_solve(p, stream_every=7)
    stream.drain()
    assert int(streamed.iterations) == int(baseline.iterations) == 50
    np.testing.assert_array_equal(np.asarray(streamed.w),
                                  np.asarray(baseline.w))
    ks = [k for k, _ in sink.samples]
    assert ks == [7, 14, 21, 28, 35, 42, 49]
    diffs = [d for _, d in sink.samples]
    assert all(np.isfinite(d) for d in diffs)
    assert diffs[-1] < diffs[0]     # it is a convergence curve


def test_streaming_without_sink_drops_samples():
    p = Problem(M=40, N=40)
    res = pcg_solve(p, stream_every=7)   # no sink installed
    assert int(res.iterations) == 50


def test_streamed_resilient_solve_keeps_counts():
    p = Problem(M=40, N=40)
    sink = stream.StreamSink()
    stream.set_sink(sink)
    res = pcg_solve_resilient(p, chunk=10, stream_every=5)
    stream.drain()
    assert int(res.iterations) == 50
    assert [k for k, _ in sink.samples] == list(range(5, 51, 5))


# ---------------------------------------------------------------------------
# Metrics snapshots and merging
# ---------------------------------------------------------------------------


def test_metrics_snapshot_and_merge(tmp_path):
    metrics.inc("a.count")
    metrics.inc("a.count", 2)
    metrics.gauge("g", 1.5)
    path = str(tmp_path / "m.json")
    metrics.write_snapshot(path, rank=0)
    with open(path) as f:
        snap = json.load(f)
    assert snap["counters"]["a.count"] == 3
    assert snap["gauges"]["g"] == 1.5
    assert "at_unix" in snap and "at_mono" in snap
    other = {"rank": 1, "counters": {"a.count": 4, "b": 1},
             "gauges": {"g": 9.0}}
    merged = metrics.merge([snap, other])
    assert merged["counters"] == {"a.count": 7, "b": 1}
    assert merged["gauges_by_rank"]["0"]["g"] == 1.5
    assert merged["gauges_by_rank"]["1"]["g"] == 9.0


# ---------------------------------------------------------------------------
# Watchdog: monotonic diagnostics with recent telemetry events
# ---------------------------------------------------------------------------


def test_watchdog_diagnostics_carry_monotonic_and_recent_events(tmp_path):
    from poisson_tpu.parallel.watchdog import Watchdog

    obs.configure(trace_dir=str(tmp_path))
    obs.event("solve.phase", phase="chunk-3")
    hb = str(tmp_path / "hb.json")
    fired = {}
    wd = Watchdog(heartbeat_path=hb, timeout=0.1, poll_interval=0.02,
                  on_timeout=lambda diag: fired.update(diag))
    with wd:
        wd.beat(k=30, diff=1e-3)
        import time as _time

        deadline = _time.monotonic() + 5.0
        while not wd.fired and _time.monotonic() < deadline:
            _time.sleep(0.02)
    assert wd.fired
    # The heartbeat file carries both clocks.
    with open(hb) as f:
        beat = json.load(f)
    assert "at_unix" in beat and "at_mono" in beat
    # The diagnostics file: monotonic stall arithmetic + wall view +
    # the recent unified-telemetry events (what the solve was doing).
    with open(hb + ".stalled.json") as f:
        diag = json.load(f)
    assert diag["elapsed_seconds"] >= 0.1          # monotonic verdict
    assert diag["elapsed_wall_seconds"] is not None
    assert "at_mono" in diag
    names = [e["name"] for e in diag["recent_events"]]
    assert "solve.phase" in names and "watchdog.beat" in names
    assert metrics.get("watchdog.stalls") == 1
    assert metrics.get("watchdog.beats") == 1


# ---------------------------------------------------------------------------
# CLI end to end (the PR acceptance command) + configured round trip
# ---------------------------------------------------------------------------


def test_cli_acceptance_command(tmp_path, capsys):
    from poisson_tpu.cli import main

    tdir = str(tmp_path / "tr")
    mpath = str(tmp_path / "m.json")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        rc = main(["--M", "40", "--N", "40", "--resilient",
                   "--fault-nan-at", "5", "--trace-dir", tdir,
                   "--metrics-out", mpath, "--json"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(out)
    # Joinable with bench session records: backend + device_kind.
    assert rec["backend"] == "xla"
    assert rec["device_kind"]
    # Same final iterate as the un-instrumented run (the recovered solve
    # converges to tolerance at the golden count).
    assert rec["iterations"] == 50
    assert rec["restarts"] == 1
    # Metrics: the restart counter matches the injected fault.
    with open(mpath) as f:
        m = json.load(f)
    assert m["counters"]["resilient.restarts"] == 1
    # Perfetto-loadable trace.
    events = _load_trace(tdir + "/trace-rank0.trace.json")
    assert any(ev["name"] == "resilient.restart" for ev in events)
    assert any(ev["name"] == "solve.report" for ev in events)


def test_cli_grid_flag_aliases(capsys):
    from poisson_tpu.cli import main

    with pytest.raises(SystemExit, match="not both"):
        main(["40", "40", "--M", "40"])
    with pytest.raises(SystemExit, match="missing grid size N"):
        main(["--M", "40"])


def test_cli_stream_every_guard():
    from poisson_tpu.cli import main

    with pytest.raises(SystemExit, match="stream-every"):
        main(["40", "40", "--backend", "native", "--stream-every", "5"])
    with pytest.raises(SystemExit, match="stream-every"):
        main(["40", "40", "--backend", "sharded", "--stream-every", "5"])


def test_cli_telemetry_off_leaves_no_recorder(capsys):
    """With the flags off the CLI must not configure telemetry (golden
    counts bit-for-bit is structural: no recorder, no stream, no trace)."""
    from poisson_tpu.cli import main

    assert main(["40", "40", "--backend", "xla", "--json"]) == 0
    assert obs.recorder() is None
    assert stream.get_sink() is None
    assert json.loads(
        capsys.readouterr().out.strip().splitlines()[-1]
    )["iterations"] == 50


def test_configured_solve_writes_stream_file_and_counts(tmp_path):
    """configure → a streamed solve through the report path → finalize:
    the per-rank stream file holds samples at the configured stride and
    the metrics snapshot counted the converged solve."""
    from poisson_tpu.utils.timing import solve_report

    mpath = str(tmp_path / "m.json")
    rec = obs.configure(trace_dir=str(tmp_path), metrics_path=mpath,
                        stream_every=5)
    p = Problem(M=40, N=40)
    res = pcg_solve(p, stream_every=5)
    solve_report(p, res, 0.1, compile_seconds=0.0, dtype="float64")
    obs.finalize()
    with open(tmp_path / f"stream-rank{rec.rank}.jsonl") as f:
        samples = [json.loads(line) for line in f if line.strip()]
    assert [s["k"] for s in samples] == list(range(5, 51, 5))
    with open(mpath) as f:
        assert json.load(f)["counters"]["pcg.solves.converged"] == 1


def test_forensics_report_renders(tmp_path, capsys):
    """summarize_session --telemetry renders the forensics report from a
    real CLI telemetry directory."""
    import subprocess
    import sys as _sys

    from poisson_tpu.cli import main

    tdir = str(tmp_path / "tr")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert main(["--M", "40", "--N", "40", "--resilient",
                     "--fault-nan-at", "5", "--stream-every", "10",
                     "--trace-dir", tdir, "--json"]) == 0
    capsys.readouterr()
    proc = subprocess.run(
        [_sys.executable, "benchmarks/summarize_session.py",
         "--telemetry", tdir],
        capture_output=True, text=True, cwd="/root/repo",
    )
    assert proc.returncode == 0, proc.stderr
    assert "Solve forensics" in proc.stdout
    assert "resilient.restart" in proc.stdout
    assert "Streamed convergence" in proc.stdout
    assert "MLUPS" in proc.stdout
