"""Unit tests for benchmarks/tpu_session.py's decision logic.

The session itself needs the real chip, but its three decision mechanisms
are pure logic that has already eaten review findings twice — these tests
pin them:

- ``decide_backend_chain``: which Pallas backends are credited as
  hardware-proven, in what order, when the forced re-measurements fire,
  and when the affirmative-negative empty chain is written.
- ``Session`` resume filtering: which prior log entries may satisfy a
  re-armed session.
- ``Session.run`` skip/replay behavior around the hung-device abort.

No test here touches a JAX backend (no device).
"""

from __future__ import annotations

import importlib.util
import json
import sys

import pytest

_ROOT = __file__.rsplit("/tests/", 1)[0]
_spec = importlib.util.spec_from_file_location(
    "tpu_session", _ROOT + "/benchmarks/tpu_session.py"
)
tpu_session = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tpu_session)


def _bench(backend, value, platform="tpu"):
    return {"value": value,
            "detail": {"backend": backend, "platform": platform}}


def _no_runner():
    raise AssertionError("forced bench runner must not be called")


def _decide(bench800, ca, fused_probe_ok=False,
            ca_runner=_no_runner, fused_runner=_no_runner,
            xla_runner=None):
    return tpu_session.decide_backend_chain(
        bench800, ca, fused_probe_ok, ca_runner, fused_runner,
        xla_runner=xla_runner,
    )


class TestDecideBackendChain:
    def test_fused_only(self):
        got = _decide(_bench("pallas_fused", 40000.0), {"ok": False})
        assert got["chain"] == ["pallas_fused"]
        assert got["evidence"] == {"pallas_fused": 40000.0}

    def test_ca_promoted_when_faster(self):
        ca = {"ok": True, "flagship_iters": 989}
        got = _decide(_bench("pallas_fused", 40000.0), ca,
                      ca_runner=lambda: _bench("pallas_ca", 55000.0))
        assert got["chain"] == ["pallas_ca", "pallas_fused"]
        assert got["evidence"] == {"pallas_ca": 55000.0,
                                   "pallas_fused": 40000.0}

    def test_ca_behind_when_slower(self):
        ca = {"ok": True, "flagship_iters": 989}
        got = _decide(_bench("pallas_fused", 40000.0), ca,
                      ca_runner=lambda: _bench("pallas_ca", 30000.0))
        assert got["chain"] == ["pallas_fused", "pallas_ca"]

    def test_bench_on_ca_does_not_credit_fused(self):
        # bench800 ran pallas_ca (a prior chain led with it); the CA probe
        # then timed out and the kernel probe was inconclusive. fused has
        # NO evidence this session and must not enter the chain.
        got = _decide(_bench("pallas_ca", 50000.0), {"timeout": True})
        assert got["chain"] == ["pallas_ca"]
        assert got["evidence"] == {"pallas_ca": 50000.0}

    def test_fused_probe_triggers_forced_measurement(self):
        # The ratchet-breaker: bench800 ran pallas_ca, but the kernel
        # probe proved the fused path healthy — fused gets a bench-grade
        # forced measurement and re-enters the chain.
        got = _decide(_bench("pallas_ca", 50000.0), {"timeout": True},
                      fused_probe_ok=True,
                      fused_runner=lambda: _bench("pallas_fused", 42000.0))
        assert got["chain"] == ["pallas_ca", "pallas_fused"]

    def test_forced_fused_demotion_is_not_credited(self):
        got = _decide(_bench("pallas_ca", 50000.0), {"timeout": True},
                      fused_probe_ok=True,
                      fused_runner=lambda: {"ok": False, "rc": 1})
        assert got["chain"] == ["pallas_ca"]

    def test_forced_ca_bench_demotion_is_not_credited(self):
        ca = {"ok": True, "flagship_iters": 989}
        got = _decide(_bench("pallas_fused", 40000.0), ca,
                      ca_runner=lambda: {"ok": False, "rc": 1})
        assert got["chain"] == ["pallas_fused"]

    def test_all_demoted_on_tpu_writes_empty_chain(self):
        got = _decide(_bench("xla", 23000.0), {"ok": False, "error": "x"})
        assert got["chain"] == []

    def test_probe_rescues_even_after_bench_demotion(self):
        # bench800 demoted to xla, but the kernel probe passed (e.g. the
        # gate switched layouts after bench800's chain had already
        # demoted): the forced measurement still gives fused its chance
        # before any negative verdict.
        got = _decide(_bench("xla", 23000.0), {"ok": False},
                      fused_probe_ok=True,
                      fused_runner=lambda: _bench("pallas_fused", 41000.0))
        assert got["chain"] == ["pallas_fused"]

    def test_xla_winning_empties_the_chain_with_evidence(self):
        got = _decide(_bench("pallas_fused", 20000.0), {"ok": False},
                      xla_runner=lambda: _bench("xla", 24000.0))
        assert got["chain"] == []
        assert got["evidence"] == {"pallas_fused": 20000.0, "xla": 24000.0}
        assert "xla measured fastest" in got["note"]

    def test_xla_losing_keeps_the_chain_and_the_comparison(self):
        got = _decide(_bench("pallas_fused", 40000.0), {"ok": False},
                      xla_runner=lambda: _bench("xla", 24000.0))
        assert got["chain"] == ["pallas_fused"]
        assert got["evidence"] == {"pallas_fused": 40000.0, "xla": 24000.0}

    def test_failed_xla_measurement_keeps_proven_chain(self):
        got = _decide(_bench("pallas_fused", 20000.0), {"ok": False},
                      xla_runner=lambda: {"ok": False, "timeout": True})
        assert got["chain"] == ["pallas_fused"]

    def test_cpu_downgraded_xla_run_is_not_hardware_evidence(self):
        # A forced xla bench that reports a CPU platform: its ~160 MLUPS
        # number must not enter the verdict, and the proven Pallas
        # chain must not be compared against it.
        got = _decide(_bench("pallas_fused", 20000.0), {"ok": False},
                      xla_runner=lambda: _bench("xla", 160.0,
                                                platform="cpu"))
        assert got["chain"] == ["pallas_fused"]
        assert "xla" not in got["evidence"]

    def test_bench800_xla_value_reused_without_runner(self):
        # bench800 itself ran xla (demoted chain); a probe-rescued fused
        # measurement still gets compared against that xla number with no
        # second forced xla run.
        got = _decide(_bench("xla", 24000.0), {"ok": False},
                      fused_probe_ok=True,
                      fused_runner=lambda: _bench("pallas_fused", 20000.0),
                      xla_runner=_no_runner)
        assert got["chain"] == []
        assert got["evidence"] == {"pallas_fused": 20000.0, "xla": 24000.0}

    def test_cpu_fallback_makes_no_statement(self):
        got = _decide(_bench("xla", 160.0, platform="cpu"), None)
        assert got is None

    def test_bench_timeout_makes_no_statement(self):
        got = _decide({"ok": False, "timeout": True}, None)
        assert got is None

    def test_ca_suspect_iterations_not_probed_further(self):
        ca = {"ok": True, "flagship_iters": 1200}
        got = _decide(_bench("pallas_fused", 40000.0), ca)
        assert got["chain"] == ["pallas_fused"]

    def test_zero_valued_bench_is_still_evidence(self):
        # A legitimate 0-valued record must not be dropped by a
        # truthiness filter (round-4 advisor finding); with no xla
        # comparison available it still proves the backend ran.
        got = _decide(_bench("pallas_fused", 0.0), {"ok": False})
        assert got["chain"] == ["pallas_fused"]
        assert got["evidence"] == {"pallas_fused": 0.0}

    def test_hardware_record_without_value_logged_not_silent(self, capsys):
        rec = {"detail": {"backend": "pallas_fused", "platform": "tpu"}}
        got = _decide(rec, {"ok": False})
        assert got is None
        assert "record excluded" in capsys.readouterr().out


class TestSummarizerBandwidthCheck:
    """The summarizer's passes-at-ceiling column is the working form of
    BENCH.md's physical-consistency rule; pin it against a real sane
    record and a round-2-style overlap artifact."""

    def _mod(self):
        spec = importlib.util.spec_from_file_location(
            "summarize_session",
            _ROOT + "/benchmarks/summarize_session.py",
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_sane_and_suspect_verdicts(self):
        m = self._mod()
        sane = {"grid": [800, 1200], "solve_seconds": 0.0397,
                "iterations": 989, "backend": "xla", "platform": "tpu",
                "device_kind": "TPU v5 lite"}
        budget, verdict = m._passes_budget(sane)
        assert float(budget) == pytest.approx(8.6, abs=0.1)
        assert verdict == " sane"
        # The withdrawn round-2 flagship row: 0.0211 s / 989 iters on the
        # fused kernels — admits ~4.5 passes where the kernels move 14.7.
        r2 = {"grid": [800, 1200], "solve_seconds": 0.0211,
              "iterations": 989, "backend": "pallas_fused",
              "platform": "tpu", "device_kind": "TPU v5e"}
        budget, verdict = m._passes_budget(r2)
        assert float(budget) < 5.0
        assert "SUSPECT" in verdict

    def test_verdict_gated_on_v5e(self):
        """The 0.82 TB/s ceiling is a v5e number; a session captured on
        another TPU generation prints the passes figure with no verdict
        instead of mislabeling every row (round-5 advice)."""
        m = self._mod()
        base = {"grid": [800, 1200], "solve_seconds": 0.0397,
                "iterations": 989, "backend": "xla", "platform": "tpu"}
        for kind in ("TPU v4", "TPU v5p", "TPU v5", "TPU v6e", None):
            budget, verdict = m._passes_budget({**base,
                                                "device_kind": kind})
            assert budget != "—"      # the number still prints
            assert verdict == "", kind
        # device_kind may also arrive from the enclosing record.
        _, verdict = m._passes_budget(base, "TPU v5 lite")
        assert verdict == " sane"

    def test_incomplete_records_stay_quiet(self):
        m = self._mod()
        assert m._passes_budget({}) == ("—", "")
        cpu = {"grid": [40, 40], "solve_seconds": 0.1, "iterations": 50,
               "backend": "xla", "platform": "cpu",
               "device_kind": "TPU v5e"}
        _, verdict = m._passes_budget(cpu)
        assert verdict == ""


class TestProbeSnippets:
    """The session's embedded probe programs only ever execute on a
    chip; a typo or a renamed import must be
    caught here, not there."""

    _NAMES = ("_KERNEL_PROBE", "_CA_PROBE", "_SHARDED_1X1",
              "_CA_SHARDED_1X1", "_RESIDENT_PROBE", "_BIG_GRID")

    @pytest.mark.parametrize("name", _NAMES)
    def test_parses_and_imports_resolve(self, name):
        import ast
        import importlib

        src = getattr(tpu_session, name)
        tree = ast.parse(src)
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module \
                    and node.module.startswith("poisson_tpu"):
                mod = importlib.import_module(node.module)
                for alias in node.names:
                    assert hasattr(mod, alias.name), (
                        f"{name}: {node.module}.{alias.name} missing"
                    )


class TestSessionResume:
    def _mklog(self, tmp_path, entries):
        log = tmp_path / "session.jsonl"
        log.write_text("".join(json.dumps(e) + "\n" for e in entries))
        return tmp_path

    def test_prior_filtering(self, tmp_path):
        outdir = self._mklog(tmp_path, [
            {"step": "old", "at": "2026-07-29T00:00:00+00:00", "ok": True,
             "result": {"v": 1}},
            {"step": "fresh", "at": "2026-07-30T06:00:00+00:00", "ok": True,
             "result": {"v": 2}},
            {"step": "failed", "at": "2026-07-30T06:01:00+00:00",
             "ok": False, "rc": 1},
            {"step": "identity", "at": "2026-07-30T06:02:00+00:00",
             "ok": True, "result": {"platform": "tpu"}},
            {"step": "nullres", "at": "2026-07-30T06:03:00+00:00",
             "ok": True, "result": None},
        ])
        s = tpu_session.Session(
            outdir, resume_after="2026-07-30T00:00:00+00:00"
        )
        # old (stale), failed, identity (always live), and null results
        # are all excluded; only the fresh ok step replays.
        assert set(s.prior) == {"fresh"}

    _LAYOUT_ENTRIES = [
        {"step": "kernel_probe", "at": "2026-07-30T06:00:00+00:00",
         "ok": True, "result": {"serial_reduce": True, "ok": True}},
        {"step": "kernel_probe_serial",
         "at": "2026-07-30T06:05:00+00:00",
         "ok": True, "result": {"serial_reduce": True, "ok": True}},
        # every layout-dependent step is filtered, not just the
        # probes (review finding): a CA number measured under
        # serial-Kahan is not evidence for a per-strip session
        {"step": "ca_probe", "at": "2026-07-30T06:10:00+00:00",
         "ok": True, "result": {"serial_reduce": True, "ok": True}},
        # bench.py records the layout under detail (review finding:
        # the filter must look there, not only at the top level) ...
        {"step": "bench_800x1200", "at": "2026-07-30T06:15:00+00:00",
         "ok": True, "result": {"value": 1.0, "detail":
                                {"backend": "pallas_fused",
                                 "serial_reduce": True}}},
        # ... an xla bench makes no layout claim (no Pallas
        # kernel ran; the stamp is just the ambient env) ...
        {"step": "bench_1600x2400", "at": "2026-07-30T06:17:00+00:00",
         "ok": True, "result": {"value": 2.0, "detail":
                                {"backend": "xla",
                                 "serial_reduce": True}}},
        # ... and roofline.py nests it per solver row
        {"step": "roofline_2400x3200", "at": "2026-07-30T06:20:00+00:00",
         "ok": True, "result": {"solver": [{"serial_reduce": True},
                                           {"serial_reduce": True}]}},
        # steps that record no layout replay regardless
        {"step": "curve_800x1200", "at": "2026-07-30T06:25:00+00:00",
         "ok": True, "result": {"rows": 989}},
    ]

    def _session(self, tmp_path):
        outdir = self._mklog(tmp_path, self._LAYOUT_ENTRIES)
        return tpu_session.Session(
            outdir, resume_after="2026-07-30T00:00:00+00:00"
        )

    def test_replayed_layout_mismatch_is_dropped(self, tmp_path,
                                                 monkeypatch):
        # Steps recorded under serial-Kahan must not replay into a
        # launch that would run them per-strip: the gate would credit
        # the wrong layout and the evidence the wrong provenance.
        # Matching env: all stand.
        monkeypatch.delenv("POISSON_TPU_SERIAL_REDUCE", raising=False)
        s = self._session(tmp_path)
        # env pins per-strip: every serial-run Pallas step is dropped
        # wherever it recorded its layout; the explicitly-serial A/B
        # step, the layout-free curve step, and the xla bench keep
        # their replays.
        assert set(s.prior) == {"kernel_probe_serial", "bench_1600x2400",
                                "curve_800x1200"}
        monkeypatch.setenv("POISSON_TPU_SERIAL_REDUCE", "1")
        s = self._session(tmp_path)
        assert set(s.prior) == {e["step"] for e in self._LAYOUT_ENTRIES}

    def test_bench_replay_follows_the_env_not_leftover_files(
            self, tmp_path, monkeypatch):
        # A layout verdict left in the results directory by an earlier
        # session decides nothing: bench.py runs the layout its env
        # gives, so a serial-recorded bench replay is dropped when the
        # env leaves the default in place.
        monkeypatch.delenv("POISSON_TPU_SERIAL_REDUCE", raising=False)
        (tmp_path / "layout_decision.json").write_text(
            json.dumps({"serial_reduce": True, "reason": "ab"}))
        s = self._session(tmp_path)
        assert set(s.prior) == {"kernel_probe_serial", "bench_1600x2400",
                                "curve_800x1200"}

    def test_no_resume_means_no_prior(self, tmp_path):
        outdir = self._mklog(tmp_path, [
            {"step": "fresh", "at": "2026-07-30T06:00:00+00:00", "ok": True,
             "result": {"v": 2}},
        ])
        assert tpu_session.Session(outdir).prior == {}

    def test_replay_returns_prior_result(self, tmp_path):
        outdir = self._mklog(tmp_path, [
            {"step": "fresh", "at": "2026-07-30T06:00:00+00:00", "ok": True,
             "result": {"v": 2}},
        ])
        s = tpu_session.Session(
            outdir, resume_after="2026-07-30T00:00:00+00:00"
        )
        got = s.run("fresh", ["false"], timeout=5, parse_json_tail=True)
        assert got == {"v": 2}  # the subprocess ("false") never ran

    def test_abort_skips_subsequent_steps(self, tmp_path):
        s = tpu_session.Session(tmp_path)
        s.aborted = True
        got = s.run("anything", ["true"], timeout=5, parse_json_tail=True)
        assert got.get("skipped") and not got.get("timeout")

    def test_step_success_and_failure_recording(self, tmp_path):
        s = tpu_session.Session(tmp_path)
        ok = s.run("good", [sys.executable, "-c", "print('{\"x\": 1}')"],
                   timeout=30, parse_json_tail=True)
        assert ok == {"x": 1}
        bad = s.run("bad", [sys.executable, "-c",
                            "import sys; print('boom', file=sys.stderr); "
                            "sys.exit(3)"], timeout=30)
        assert bad == {"ok": False, "rc": 3}
        # full stderr rides along as a file for root-causing
        assert (tmp_path / "bad_stderr.txt").read_text().strip() == "boom"

    def test_extra_env_reaches_the_step(self, tmp_path):
        s = tpu_session.Session(tmp_path)
        got = s.run("env", [sys.executable, "-c",
                            "import os, json; "
                            "print(json.dumps({'b': os.environ.get('BENCH_BACKEND')}))"],
                    timeout=30, parse_json_tail=True,
                    extra_env={"BENCH_BACKEND": "pallas_ca"})
        assert got == {"b": "pallas_ca"}

    def test_decide_layout_is_recorded_in_the_log_only(self, tmp_path):
        s = tpu_session.Session(tmp_path)
        s.decide_layout(False, "inconclusive", affirmative=False)
        s.decide_layout(True, "serial proved healthy")
        entries = [json.loads(line) for line in
                   (tmp_path / "session.jsonl").read_text().splitlines()]
        assert [(e["step"], e["serial_reduce"], e["affirmative"])
                for e in entries] == [("layout_decision", False, False),
                                      ("layout_decision", True, True)]
        # No verdict file for a later process to adopt.
        assert [p.name for p in tmp_path.iterdir()] == ["session.jsonl"]
