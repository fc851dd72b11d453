"""Chaos campaign: every named scenario runs green, deterministically,
asserting the no-lost-request invariant from the emitted serve.* metrics
snapshot (tier-1, CPU; -m serve)."""

import json

import pytest

from poisson_tpu.obs import export, metrics
from poisson_tpu.testing import chaos

pytestmark = pytest.mark.serve

# The acceptance matrix: the campaign must exercise each of these
# survival properties in at least one scenario.
REQUIRED = ("breaker-trip", "deadline-mid-chunk", "poison-requeue",
            "overload-shed")

# Each subsystem's headline metrics, as the scrape sees them after the
# scenario that drills that subsystem.
FAMILY_METRICS = {
    "overload-shed": ("poisson_tpu_serve_admitted",
                      'poisson_tpu_serve_latency_seconds{quantile="0.99"}'),
    "refill-poison-splice": ("poisson_tpu_serve_refill_splices",
                             "poisson_tpu_serve_refill_retired_lanes"),
    "fleet-worker-kill-mid-dispatch": (
        "poisson_tpu_serve_fleet_quarantines",
        "poisson_tpu_serve_fleet_recovered_requests"),
    "journal-crash-replay": ("poisson_tpu_serve_journal_records",),
    "geometry-mixed-cobatch": ("poisson_tpu_geom_cache_hits",
                               "poisson_tpu_geom_cache_misses"),
    "sdc-verified-restart": (
        "poisson_tpu_integrity_detections",
        "poisson_tpu_integrity_verified_restarts",
        "poisson_tpu_serve_integrity_detections",
        "poisson_tpu_serve_integrity_suspect_cohorts"),
    "device-loss-mid-dispatch": ("poisson_tpu_serve_fleet_device_losses",
                                 "poisson_tpu_serve_placement_rebinds",
                                 "poisson_tpu_serve_placement_epoch"),
    "deflation-stale-basis": ("poisson_tpu_krylov_cache_hits",
                              "poisson_tpu_krylov_cache_misses",
                              "poisson_tpu_krylov_harvests",
                              "poisson_tpu_krylov_warm_solves",
                              "poisson_tpu_krylov_iterations_saved"),
    "session-stale-warm-start": ("poisson_tpu_session_opens",
                                 "poisson_tpu_session_steps",
                                 "poisson_tpu_session_warm_hits",
                                 "poisson_tpu_session_closes",
                                 "poisson_tpu_session_slo_good"),
    "forecast-predicted-shed": (
        "poisson_tpu_obs_forecast_predictions",
        "poisson_tpu_obs_forecast_cold_cohorts",
        "poisson_tpu_obs_forecast_calibration_err_pct",
        "poisson_tpu_serve_forecast_admission_checks",
        "poisson_tpu_serve_shed_predicted_deadline"),
    "router-mispredict-downshift": (
        "poisson_tpu_serve_router_decisions",
        "poisson_tpu_serve_router_cold_decisions",
        "poisson_tpu_serve_router_chosen_xla",
        "poisson_tpu_obs_roofline_observations",
        "poisson_tpu_obs_roofline_fraction"),
    "tenant-noisy-neighbor": ("poisson_tpu_serve_tenant_quota_sheds",
                              "poisson_tpu_serve_shed_quota_exceeded",
                              "poisson_tpu_serve_tenant_promotions",
                              "poisson_tpu_serve_tenant_share_victim"),
    "tenant-retry-storm": (
        "poisson_tpu_serve_tenant_retry_exhausted",
        "poisson_tpu_serve_tenant_dispatches_poison",
        "poisson_tpu_serve_tenant_retry_tokens_poison"),
}


@pytest.fixture(autouse=True)
def _fresh_registry():
    yield
    metrics.reset()


def test_required_scenarios_registered():
    names = chaos.scenario_names()
    for required in REQUIRED:
        assert required in names


@pytest.mark.parametrize("name", chaos.scenario_names())
def test_scenario_green_with_invariant(name):
    report = chaos.run_scenario(name, seed=0)
    assert report["ok"], report["checks"]
    # The invariant is read from the scenario's own metrics snapshot —
    # the emitted counters, not the service's in-memory ledger.
    snap = report["metrics_snapshot"]["counters"]
    admitted = snap.get("serve.admitted", 0)
    terminated = (snap.get("serve.completed", 0)
                  + snap.get("serve.errors", 0)
                  + snap.get("serve.shed", 0))
    assert admitted - terminated == 0
    assert report["invariant"]["lost"] == 0
    # Every counter survives the Prometheus exposition with its value,
    # and the drilled subsystem's headline metrics are in the scrape.
    parsed = export.parse_text(export.render(report["metrics_snapshot"]))
    for counter, value in snap.items():
        assert parsed[export.metric_name(counter)] == {
            "type": "counter", "value": float(value)}, counter
    missing = set(FAMILY_METRICS.get(name, ())) - set(parsed)
    assert not missing, missing


def test_campaign_is_deterministic_under_a_seed():
    def fingerprint(campaign):
        return json.dumps(
            [{k: v for k, v in s.items() if k != "detail"}
             for s in campaign["scenarios"]],
            sort_keys=True, default=str,
        )

    a = chaos.run_campaign(["poison-requeue", "breaker-trip"], seed=3)
    b = chaos.run_campaign(["poison-requeue", "breaker-trip"], seed=3)
    assert a["ok"] and fingerprint(a) == fingerprint(b)


def test_campaign_writes_per_scenario_artifacts(tmp_path):
    out = tmp_path / "chaos"
    campaign = chaos.run_campaign(["overload-shed"], seed=0,
                                  out_dir=str(out))
    assert campaign["ok"]
    snap = json.loads((out / "metrics-overload-shed.json").read_text())
    assert snap["counters"]["serve.admitted"] == 14
    # Prometheus text of the same snapshot, parseable with the serve
    # counters intact.
    from poisson_tpu.obs import export

    parsed = export.parse_text(
        (out / "metrics-overload-shed.prom").read_text())
    assert parsed["poisson_tpu_serve_admitted"]["value"] == 14
    report = json.loads((out / "campaign.json").read_text())
    assert report["ok"] and len(report["scenarios"]) == 1


def test_unknown_scenario_raises():
    with pytest.raises(KeyError, match="unknown chaos scenario"):
        chaos.run_scenario("no-such-scenario")


def test_virtual_clock():
    vc = chaos.VirtualClock(start=5.0)
    assert vc() == 5.0
    vc.sleep(2.0)
    vc.advance(1.0)
    assert vc.now() == 8.0
    vc.sleep(-1.0)                     # sleeping never rewinds time
    assert vc.now() == 8.0


# -- CLI ----------------------------------------------------------------


def test_chaos_cli_list(capsys):
    from poisson_tpu.cli import main

    assert main(["chaos", "--list"]) == 0
    out = capsys.readouterr().out.split()
    for required in REQUIRED:
        assert required in out


def test_chaos_cli_named_scenario(capsys):
    from poisson_tpu.cli import main

    assert main(["chaos", "overload-shed", "--seed", "0"]) == 0
    out = capsys.readouterr().out
    assert "ok  overload-shed" in out
    assert "chaos campaign ok" in out


def test_chaos_cli_json_verdict(capsys):
    from poisson_tpu.cli import main

    assert main(["chaos", "poison-requeue", "--json"]) == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["ok"] and rec["scenarios"][0]["invariant"]["lost"] == 0


def test_chaos_cli_rejects_bad_usage():
    from poisson_tpu.cli import main

    with pytest.raises(SystemExit):
        main(["chaos"])                         # nothing to run
    with pytest.raises(SystemExit):
        main(["chaos", "--all", "overload-shed"])   # both forms
    with pytest.raises(SystemExit):
        main(["chaos", "no-such-scenario"])
