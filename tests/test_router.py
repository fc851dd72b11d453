"""Roofline observatory & cost-model backend router (tier-1,
CPU-deterministic; -m router).

Four layers under test: the measured-attribution arithmetic
(:mod:`poisson_tpu.obs.roofline` — achieved GB/s against the analytic
bytes/iteration model, per-cohort streaming fraction profiles,
CRC-sealed snapshots), the cold analytic routing table and the
warm-evidence argmin (:mod:`poisson_tpu.serve.router`), the
misprediction sentinel lifecycle (typed event → arm demotion →
cooldown → half-open re-probe → recovery) under an injected
:class:`VirtualClock`, and the byte-compat pins: a router-less service
keeps its historical cohort strings and ``stats()`` shape, and
``executor_backend`` gates every arm through xla so the routed default
path lowers byte-identically (ledger-pinned as
``serve.routed_default_f64``). regress.py cohort-splits on
``routed_backend`` so auto-routed runs never judge fixed baselines,
and the ``top`` scoreboard's Backends pane reads identically from a
live registry snapshot or the Prometheus exposition round trip.
"""

import json
import os
import sys

import pytest

from poisson_tpu.config import Problem
from poisson_tpu.obs import export, forecast, metrics
from poisson_tpu.obs.roofline import (
    DEFAULT_COLD_FRACTION,
    RESIDENT_EFFECTIVE_PASSES,
    RooflineModel,
    effective_passes,
    roofline_cohort,
    snapshot_path,
)
from poisson_tpu.obs.costs import EFFECTIVE_PASSES, grid_points
from poisson_tpu.serve import (
    RouterPolicy,
    ServicePolicy,
    SolveRequest,
    SolveService,
)
from poisson_tpu.serve.router import (
    BACKEND_CA,
    BACKEND_RESIDENT,
    BACKEND_XLA,
    BackendRouter,
    analytic_choice,
    available_backends,
    executor_backend,
    fits_resident_bytes,
)
from poisson_tpu.testing.chaos import VirtualClock

sys.path.insert(0, str(__import__("pathlib").Path(
    __file__).resolve().parents[1]))
from benchmarks import regress  # noqa: E402

pytestmark = pytest.mark.router

P40 = Problem(M=40, N=40)


@pytest.fixture(autouse=True)
def _fresh_registry():
    metrics.reset()
    yield
    metrics.reset()


def _observe(model, backend="xla", M=40, N=40, seconds=1e-3,
             iterations=100, **kw):
    return model.observe(backend=backend, M=M, N=N, seconds=seconds,
                         iterations=iterations, **kw)


# -- measured attribution arithmetic -------------------------------------


def test_achieved_fraction_matches_bytes_model(monkeypatch):
    """fraction = passes·points·bytes·iters / seconds / peak — checked
    against a hand computation with a pinned env peak."""
    monkeypatch.setenv("POISSON_TPU_PEAK_GBPS", "100")
    model = RooflineModel()
    s = _observe(model, backend="xla", M=40, N=40, seconds=1e-3,
                 iterations=100, dtype_bytes=8, device_kind="tpu v5e")
    want_bytes = EFFECTIVE_PASSES["xla"] * grid_points(40, 40) * 8 * 100
    want_gbps = want_bytes / 1e-3 / 1e9
    assert s is not None
    assert s.achieved_gbps == pytest.approx(want_gbps, rel=1e-3)
    assert s.peak_gbps == 100.0
    assert s.fraction == pytest.approx(want_gbps / 100.0, rel=1e-3)
    # the first sample is graded against the analytic prior
    assert s.cold and s.expected_fraction == DEFAULT_COLD_FRACTION
    assert metrics.get("obs.roofline.observations") == 1
    assert metrics.get("obs.roofline.cold_cohorts") == 1


def test_unmeasurable_dispatch_is_skipped_not_sampled():
    model = RooflineModel()
    assert _observe(model, seconds=0.0) is None      # VirtualClock
    assert _observe(model, iterations=0) is None
    assert _observe(model, backend="nonesuch") is None  # no model
    assert metrics.get("obs.roofline.skipped") == 3
    assert metrics.get("obs.roofline.observations") == 0


def test_cohort_warms_and_expectation_tracks_p50():
    model = RooflineModel()
    for k in range(5):
        s = _observe(model, seconds=1e-3)
    assert not s.cold and s.samples == 4
    cohort = roofline_cohort("xla", 40, 40, 1, 8, None, 0, None)
    expected, cold, n = model.expected_fraction(cohort)
    assert not cold and n == 5
    # identical dispatches → p50 equals the per-sample fraction and
    # the calibration error collapses to ~0 on repeats
    assert expected == pytest.approx(s.fraction, rel=1e-9)
    assert model.calibration_err_pct() == pytest.approx(0.0, abs=1e-6)


def test_effective_passes_table():
    assert effective_passes("xla") == EFFECTIVE_PASSES["xla"]
    assert effective_passes("pallas_resident") \
        == RESIDENT_EFFECTIVE_PASSES
    assert effective_passes("nonesuch") is None
    # MG adds the V-cycle's fine-equivalent traffic on top
    plain = effective_passes("xla", None, 64, 64, 8)
    mg = effective_passes("xla", "mg", 64, 64, 8)
    assert mg > plain


def test_snapshot_roundtrip_and_torn_audibility(tmp_path):
    model = RooflineModel()
    for _ in range(3):
        _observe(model, seconds=1e-3)
    path = snapshot_path(str(tmp_path / "serve.journal"))
    assert model.save(path)
    loaded = RooflineModel()
    assert loaded.load(path)
    assert loaded.backend_fraction("xla") \
        == model.backend_fraction("xla")
    assert metrics.get("obs.roofline.snapshot.saves") == 1
    assert metrics.get("obs.roofline.snapshot.loads") == 1
    # tear the seal: the torn snapshot is counted and the model stays
    # cold — never trusted
    blob = json.loads(open(path).read())
    blob["crc32"] ^= 1
    open(path, "w").write(json.dumps(blob))
    torn = RooflineModel()
    assert not torn.load(path)
    assert torn.backend_fraction("xla") is None
    assert metrics.get("obs.roofline.snapshot.torn") == 1
    assert export.parse_text(export.render())[
        "poisson_tpu_obs_roofline_snapshot_torn"]["value"] == 1
    # a missing snapshot is silent (cold start, not an incident)
    fresh = RooflineModel()
    assert not fresh.load(str(tmp_path / "absent.json"))
    assert metrics.get("obs.roofline.snapshot.torn") == 1


# -- the cold analytic routing table -------------------------------------


def test_available_backends_gate_on_device_kind():
    assert available_backends(None) == (BACKEND_XLA,)
    assert available_backends("cpu") == (BACKEND_XLA,)
    assert set(available_backends("TPU v5e")) \
        == {BACKEND_XLA, BACKEND_RESIDENT, BACKEND_CA}
    assert set(available_backends("cpu",
                                  assume=(BACKEND_RESIDENT,))) \
        == {BACKEND_XLA, BACKEND_RESIDENT}


def test_analytic_choice_table():
    arms = (BACKEND_XLA, BACKEND_RESIDENT, BACKEND_CA)
    # VMEM-resident small grid → the resident kernel
    assert fits_resident_bytes(40, 40)
    assert analytic_choice(40, 40, 8, arms) == BACKEND_RESIDENT
    # too big for VMEM, below the CA plateau → xla
    assert not fits_resident_bytes(800, 800)
    assert analytic_choice(800, 800, 8, arms) == BACKEND_XLA
    # on the HBM plateau → communication-avoiding kernel
    assert analytic_choice(4000, 4000, 8, arms) == BACKEND_CA
    # candidates constrain the choice: xla-only part routes xla
    assert analytic_choice(40, 40, 8, (BACKEND_XLA,)) == BACKEND_XLA


def test_executor_gate_pins_every_arm_to_xla():
    """The contract behind the serve.routed_default_f64 ledger pin:
    whatever arm the router names, execution today runs the historical
    xla program — routing changes attribution, never numerics."""
    for arm in (BACKEND_XLA, BACKEND_RESIDENT, BACKEND_CA):
        assert executor_backend(arm) == "xla"


# -- the sentinel lifecycle ----------------------------------------------


def _router(vc, **overrides):
    kw = dict(assume_available=(BACKEND_RESIDENT,),
              misprediction_fraction=0.5, demote_after=1,
              cooldown_seconds=0.05, warm_min_samples=3)
    kw.update(overrides)
    return BackendRouter(RouterPolicy(**kw), RooflineModel(),
                         clock=vc)


def test_misprediction_demotes_then_half_open_recovers():
    vc = VirtualClock()
    router = _router(vc)
    # Cold route on a VMEM-sized grid picks the resident arm
    d1 = router.route(M=40, N=40, dtype_bytes=8)
    assert d1.backend == BACKEND_RESIDENT and d1.cold
    # A slow measured dispatch lands far below the predicted fraction
    vc.advance(1.0)
    slow = router.roofline.observe(
        backend=BACKEND_RESIDENT, M=40, N=40, iterations=50,
        seconds=1.0)
    router.grade(d1, slow)
    assert metrics.get("serve.router.mispredictions") == 1
    assert metrics.get("serve.router.demotions") == 1
    assert router.demoted_arms() == (f"{BACKEND_RESIDENT}:0",)
    # While demoted, traffic downshifts to the xla floor
    d2 = router.route(M=40, N=40, dtype_bytes=8)
    assert d2.backend == BACKEND_XLA
    good2 = router.roofline.observe(
        backend=BACKEND_XLA, M=40, N=40, iterations=50, seconds=5e-5)
    router.grade(d2, good2)
    # Past the cooldown the arm half-opens: one probe, graded against
    # the cold prior, and a healthy measurement recovers it
    vc.advance(0.06)
    d3 = router.route(M=40, N=40, dtype_bytes=8)
    assert d3.backend == BACKEND_RESIDENT
    assert metrics.get("serve.router.half_opens") == 1
    probe = router.roofline.observe(
        backend=BACKEND_RESIDENT, M=40, N=40, iterations=50,
        seconds=5e-5)
    router.grade(d3, probe)
    assert metrics.get("serve.router.recoveries") == 1
    assert router.demoted_arms() == ()
    st = router.stats()
    assert st["chosen"][BACKEND_RESIDENT] == 2
    assert st["chosen"][BACKEND_XLA] == 1


def test_failed_probe_redemotes_without_counting_twice():
    vc = VirtualClock()
    router = _router(vc)
    d1 = router.route(M=40, N=40, dtype_bytes=8)
    vc.advance(1.0)
    router.grade(d1, router.roofline.observe(
        backend=BACKEND_RESIDENT, M=40, N=40, iterations=50,
        seconds=1.0))
    vc.advance(0.06)
    d2 = router.route(M=40, N=40, dtype_bytes=8)
    assert d2.backend == BACKEND_RESIDENT      # the half-open probe
    vc.advance(1.0)
    router.grade(d2, router.roofline.observe(
        backend=BACKEND_RESIDENT, M=40, N=40, iterations=50,
        seconds=1.0))
    assert metrics.get("serve.router.demotions") == 2
    assert metrics.get("serve.router.recoveries") == 0
    assert router.demoted_arms() == (f"{BACKEND_RESIDENT}:0",)


def test_warm_evidence_argmin_prefers_measured_fast_arm():
    vc = VirtualClock()
    router = _router(vc, warm_min_samples=2)
    # Warm the xla cohort with healthy evidence
    for _ in range(3):
        router.roofline.observe(backend=BACKEND_XLA, M=800, N=800,
                                iterations=50, seconds=5e-3)
    d = router.route(M=800, N=800, dtype_bytes=8)
    # 800×800 doesn't fit VMEM; warm xla evidence seals the choice
    assert d.backend == BACKEND_XLA and not d.cold
    assert metrics.get("serve.router.warm_decisions") == 1


def test_backend_downshift_rung_forces_the_floor():
    vc = VirtualClock()
    router = _router(vc, downshift_at=0.5)
    d = router.route(M=40, N=40, dtype_bytes=8, queue_fraction=0.9)
    assert d.backend == BACKEND_XLA and d.forced_xla
    assert metrics.get("serve.degraded.backend_downshift") == 1
    calm = router.route(M=40, N=40, dtype_bytes=8, queue_fraction=0.1)
    assert calm.backend == BACKEND_RESIDENT and not calm.forced_xla


def test_xla_floor_arm_never_demotes():
    vc = VirtualClock()
    router = _router(vc, assume_available=())
    for _ in range(4):
        d = router.route(M=40, N=40, dtype_bytes=8)
        assert d.backend == BACKEND_XLA
        vc.advance(1.0)
        router.grade(d, router.roofline.observe(
            backend=BACKEND_XLA, M=40, N=40, iterations=50,
            seconds=1.0))
    # only the FIRST slow dispatch mispredicts (graded against the
    # cold prior); after that the cohort's expectation has absorbed
    # reality, so a consistently-slow part stops alarming — and the
    # floor arm never demotes regardless
    assert metrics.get("serve.router.mispredictions") == 1
    assert metrics.get("serve.router.demotions") == 0
    assert router.demoted_arms() == ()


def test_fixed_backend_policy_short_circuits():
    vc = VirtualClock()
    router = _router(vc, backend=BACKEND_XLA)
    d = router.route(M=40, N=40, dtype_bytes=8)
    assert d.backend == BACKEND_XLA
    # a fixed arm the part doesn't offer falls back to the floor
    router2 = _router(vc, backend=BACKEND_CA, assume_available=())
    assert router2.route(M=40, N=40, dtype_bytes=8).backend \
        == BACKEND_XLA


# -- the service seam ----------------------------------------------------


def test_router_off_by_default_byte_compat():
    """ServicePolicy().router is None, the historical cohort string is
    unchanged, stats() has no router block, and no router counters
    tick — the default path is indistinguishable from PR 18."""
    assert ServicePolicy().router is None
    svc = SolveService()
    svc.submit(SolveRequest(request_id=0, problem=P40))
    assert svc._cohort(svc._queue[0].request) == "40x40:auto:xla"
    outs = svc.drain()
    assert all(o.converged for o in outs)
    st = svc.stats()
    assert "router" not in st and st["lost"] == 0
    assert metrics.get("serve.router.decisions") == 0


def test_routed_service_splits_cohort_and_serves_all():
    vc = VirtualClock()
    svc = SolveService(
        ServicePolicy(capacity=16, router=RouterPolicy(
            assume_available=(BACKEND_RESIDENT,))),
        clock=vc, sleep=vc.sleep, seed=0)
    svc.submit(SolveRequest(request_id=0, problem=P40))
    # the routed arm is IN the breaker cohort: a melting-down routed
    # backend trips its own breaker, not the xla floor's
    assert svc._cohort(svc._queue[0].request) \
        == f"40x40:auto:{BACKEND_RESIDENT}"
    outs = svc.drain()
    assert all(o.converged for o in outs)
    st = svc.stats()
    assert st["lost"] == 0
    assert st["router"]["decisions"] == 1
    assert st["router"]["chosen"] == {BACKEND_RESIDENT: 1}


def test_routed_service_measures_xla_under_the_real_clock():
    """Under the real clock every dispatch is measurable: an xla-only
    routed service makes one decision per drain, measures a positive
    roofline fraction, and the router/roofline metrics reach the
    scrape."""
    svc = SolveService(ServicePolicy(capacity=16, router=RouterPolicy()),
                       seed=0)
    for k in range(3):
        assert svc.submit(SolveRequest(request_id=f"rt{k}",
                                       problem=P40)) is None
        assert all(o.converged for o in svc.drain())
    st = svc.stats()
    assert st["lost"] == 0 and st["router"]["chosen"] == {"xla": 3}
    assert metrics.get("serve.router.decisions") == 3
    assert metrics.get("obs.roofline.observations") >= 1
    assert svc._roofline.backend_fraction("xla") > 0.0
    parsed = export.parse_text(export.render())
    for name in ("poisson_tpu_serve_router_decisions",
                 "poisson_tpu_serve_router_cold_decisions",
                 "poisson_tpu_serve_router_chosen_xla",
                 "poisson_tpu_obs_roofline_observations",
                 "poisson_tpu_obs_roofline_fraction"):
        assert name in parsed, name


def test_routed_mixed_run_spans_backends_zero_lost():
    """The acceptance shape: a router-on run under an injected slow
    backend draws misprediction + demotion + recovery, spans ≥2
    distinct backends, and loses nothing (the chaos scenario asserts
    the same end to end; this is the in-suite pin)."""
    from poisson_tpu.testing import chaos

    report = chaos.run_scenario("router-mispredict-downshift", seed=0)
    assert report["ok"], report
    assert report["checks"]["traffic_spanned_backends"]
    assert report["checks"]["healthy_probe_recovered"]
    assert report["checks"]["no_lost_requests"]


def test_journal_snapshot_warm_loads_on_recover(tmp_path):
    from poisson_tpu.serve import SolveJournal

    jpath = str(tmp_path / "serve.journal")
    vc0 = VirtualClock()
    svc = SolveService(ServicePolicy(capacity=16),
                       clock=vc0, sleep=vc0.sleep,
                       journal=SolveJournal(jpath, clock=vc0),
                       dispatch_fault=lambda reqs, att: vc0.advance(
                           1e-3))
    for i in range(3):
        svc.submit(SolveRequest(request_id=f"w{i}", problem=P40))
    svc.drain()
    assert os.path.exists(snapshot_path(jpath))
    vc = VirtualClock()
    revived = SolveService.recover(SolveJournal(jpath, clock=vc),
                                   ServicePolicy(capacity=16),
                                   clock=vc, sleep=vc.sleep)
    assert revived._roofline.backend_fraction("xla") is not None


# -- regress cohort split ------------------------------------------------


def _serve_record(value, routed):
    det = {"grid": [40, 40], "dtype": "float32", "platform": "cpu",
           "backend": "xla_serve", "devices": 1,
           "fault_load": "clean"}
    if routed is not None:
        det["routed_backend"] = routed
    return regress.record_from_result(
        {"metric": "serve.sustained_solves_per_sec", "value": value,
         "detail": det}, "r")


def test_regress_routed_backend_splits_the_cohort():
    auto = _serve_record(1.0, "auto")
    off = _serve_record(5.0, "off")
    legacy = _serve_record(5.0, None)
    assert auto["routed_backend"] == "auto"
    assert regress.cohort_key(auto) != regress.cohort_key(off)
    # pre-router artifacts normalize to the "off" cohort — history
    # stays comparable
    assert regress.cohort_key(legacy) == regress.cohort_key(off)
    # an auto-routed run never judges the fixed baseline: a 5x gap
    # across the split raises no alarm, and the direction pin still
    # fires within a cohort
    assert not regress.evaluate([off, off, off, auto])["regressions"]
    slow = _serve_record(1.0, "off")
    verdict = regress.evaluate([off, off, off, slow])
    assert verdict["regressions"]


# -- the scoreboard ------------------------------------------------------


def test_scoreboard_backends_pane_agrees_across_sources():
    vc = VirtualClock()
    router = _router(vc)
    d = router.route(M=40, N=40, dtype_bytes=8)
    vc.advance(1.0)
    router.grade(d, router.roofline.observe(
        backend=BACKEND_RESIDENT, M=40, N=40, iterations=50,
        seconds=1.0))
    router.route(M=40, N=40, dtype_bytes=8)
    snap = metrics.snapshot()
    live = forecast.build_scoreboard(snap)
    wire = forecast.build_scoreboard(export.parse_text(
        export.render(snap)))
    assert live["backends"] == wire["backends"]
    assert live["backends"]["decisions"] == 2
    assert live["backends"]["mispredictions"] == 1
    assert live["backends"]["chosen"]
    text = forecast.render_scoreboard(live)
    assert "backends" in text and "mispred" in text
    # pre-router snapshots still render (dark pane, no crash)
    old = dict(live)
    old.pop("backends", None)
    assert forecast.render_scoreboard(old)
