"""Regression sentinel: platform-grouped, noise-robust bench verdicts.

Naive "is the new number smaller" alerting compares runs that are not
the same experiment: a CPU run against a TPU one, a pallas backend
against xla, a crashed run against a measurement. So:

1. **Group before comparing.** Records are cohorted by
   (metric, grid, dtype, platform, backend, devices): a CPU run is
   never judged against a TPU baseline, and a pallas record is never
   judged against an xla one.
2. **Noise-robust thresholds.** Within a cohort the baseline is the
   median of the *other* records and the alarm line is
   ``median − max(k·1.4826·MAD, rel_tol·median)``: MAD scales with the
   cohort's real run-to-run noise, the relative floor keeps a
   two-record cohort (MAD 0) from alarming on timer jitter. Defaults:
   k=3, rel_tol=0.25 — a genuine 2× slowdown is always over the line,
   a 5% scheduler wobble never is.
3. **Machine-readable verdict, nonzero exit.** One JSON document on
   stdout; exit 1 iff any record classifies as a regression — runnable
   bare in CI (``python benchmarks/regress.py``) and rendered by
   ``summarize_session.py --telemetry``'s forensics report.

Service-mode records (``bench.py --serve``: ``serve.p99_latency``,
``serve.shed_rate``) get two extra rules: they regress *upward* (a p99
that grew is the slowdown), and their injected fault mix
(``detail.fault_load``) is part of the cohort key — a latency percentile
measured under chaos faults is a different experiment from a clean run
and is never judged against its baseline. Open-loop records
(``--serve R --arrival-rate L``: ``serve.sustained_solves_per_sec``,
higher-is-better like MLUPS) additionally carry ``detail.arrival_rate``
in the cohort key: sustained throughput at one offered load never
judges another. Fleet records (``--serve R --workers W``) carry
``detail.workers`` in the cohort key too: a W-worker fleet under churn
is a different experiment from the single-worker service, and its
sustained throughput is never compared against single-worker baselines
(direction-pinned by tests/test_fleet.py). Mixed-geometry records
(``--serve R --geometry-mix K``) carry ``detail.geometry_mix`` in the
cohort key: a K-family mixed load solves K different operators per
bucket, so its sustained number never judges a single-ellipse baseline
(pinned by tests/test_geometry_dsl.py). Integrity-verified records
(``bench.py --verify-every K``) carry ``detail.verify_every`` in the
cohort key — the direction pin for the SDC defense: a solve paying the
in-loop verification probe is a different experiment from an unverified
one, so a verified run can never indict an unverified baseline and an
unverified run can never mask a verified-path slowdown (pinned by
tests/test_integrity.py). Preconditioner records (``bench.py
--preconditioner mg``) carry ``detail.preconditioner`` in the cohort
key: an MG-preconditioned iteration deliberately trades per-iteration
bytes for a near-flat iteration count, so its MLUPS are a different
experiment — MG runs never judge Jacobi baselines, and vice versa
(pinned by tests/test_mg.py). Placement records (``bench.py --serve
--workers W --devices D [--kill-device-at T]``) carry
``detail.device_topology`` (beside ``devices``) in the cohort key with
the metric's own direction pins: throughput spread over D fault-domain
slots — or measured through a device loss (``fault_load``
``kill_device@T``) — never judges a single-device clean baseline
(pinned by tests/test_placement.py).

Stdlib only, no jax import: like the forensics renderer, a post-session
gate must never risk initializing a backend.

Usage:
    python benchmarks/regress.py [--root DIR] [--history FILE ...]
          [--session FILE] [--k F] [--rel-tol F] [--pretty]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
from statistics import median
from typing import Optional

_ROOT = pathlib.Path(__file__).resolve().parents[1]

_METRICS = ("mlups", "batched_solves_per_sec",
            "serve.p99_latency", "serve.shed_rate",
            "serve.sustained_solves_per_sec",
            "session.steps_per_sec",
            "obs.forecast.calibration_err_pct")

# Service metrics regress UPWARD: a p99 latency or a shed rate that grew
# is the slowdown, where MLUPS/solves-per-sec regress downward. The
# alarm line flips sides accordingly (median + guard instead of − guard).
# serve.sustained_solves_per_sec (the open-loop continuous-batching
# throughput) is deliberately NOT here: like MLUPS, a drop is the alarm.
# obs.forecast.calibration_err_pct (the p50 absolute iteration-forecast
# error bench stamps on serve records) also alarms on a RISE: a
# forecaster drifting out of calibration silently mis-admits deadlines.
_LOWER_IS_BETTER = {"serve.p99_latency", "serve.shed_rate",
                    "obs.forecast.calibration_err_pct"}


def _mk_record(source: str, *, value=None, metric=None, platform=None,
               backend=None, grid=None, dtype=None, devices=None,
               failed=False,
               fault_load: Optional[str] = None,
               arrival_rate: Optional[float] = None,
               workers: Optional[int] = None,
               geometry_mix: Optional[int] = None,
               verify_every: Optional[int] = None,
               preconditioner: Optional[str] = None,
               device_topology: Optional[str] = None,
               krylov_mode: Optional[str] = None,
               deflation: Optional[bool] = None,
               repeat_fingerprint: Optional[int] = None,
               session: Optional[bool] = None,
               warm_start: Optional[bool] = None,
               routed_backend: Optional[str] = None,
               tenant_mix: Optional[str] = None,
               note: Optional[str] = None) -> dict:
    return {
        "source": source,
        "value": value,
        "metric": metric,
        "platform": platform,
        "backend": backend,
        "grid": list(grid) if grid else None,
        "dtype": dtype,
        "devices": devices,
        # Service-mode records measured under injected fault load (the
        # chaos/bench fault campaigns) carry the fault mix here; it is
        # part of the cohort key, so a fault-load p99 is never judged
        # against a clean baseline (a latency percentile under injected
        # slow-workers is a different experiment, not a regression).
        "fault_load": fault_load,
        # Open-loop serve records (bench.py --serve --arrival-rate):
        # sustained throughput and percentiles at one offered load are a
        # different experiment from another rate — cohort key too.
        "arrival_rate": arrival_rate,
        # Fleet records (bench.py --serve --workers W): the worker
        # count is experiment identity — multi-worker churn throughput
        # never judges single-worker baselines. Cohort key too.
        "workers": workers,
        # Mixed-geometry records (bench.py --serve --geometry-mix K):
        # the family count is experiment identity — a K-domain mixed
        # load never judges a single-ellipse baseline. Cohort key too.
        "geometry_mix": geometry_mix,
        # Integrity-verified records (bench.py --verify-every K): the
        # probe stride is experiment identity — a verified solve pays
        # for its drift checks by design, so it never indicts an
        # unverified baseline (and cannot hide behind one). Cohort key.
        "verify_every": verify_every,
        # Preconditioner records (bench.py --preconditioner mg): the
        # preconditioner is experiment identity — an MG iteration moves
        # several times the bytes of a Jacobi iteration by design
        # (V-cycle traffic), so its MLUPS live in their own cohort: MG
        # runs never judge Jacobi baselines, and vice versa. Cohort key.
        "preconditioner": preconditioner,
        # Fleet device topology (bench.py --serve --workers --devices):
        # the fault-domain count and device kinds are experiment
        # identity — throughput spread over D devices never judges a
        # single-device baseline, and the direction pins stay the
        # metric's own (sustained solves/sec alarms on a DROP, p99 on a
        # RISE, regardless of topology). Cohort key.
        "device_topology": device_topology,
        # Krylov-memory records (bench.py --krylov-block / --serve
        # --repeat-fingerprint): the batched recurrence mode, the
        # deflation bit, and the repeat-family count are experiment
        # identity — a block iteration searches B directions per step
        # and a warm-dominated repeat-fingerprint load answers mostly
        # from cached bases, so neither may judge (or hide behind) an
        # independent/cold baseline. Cohort key, direction pins stay
        # the metric's own (solves/sec alarms on a DROP either way).
        "krylov_mode": krylov_mode,
        "deflation": deflation,
        "repeat_fingerprint": repeat_fingerprint,
        # Durable-session records (bench.py --session STEPS): a
        # warm-started dependent stream answers most steps from the
        # previous iterate, so its steps/sec is a different experiment
        # from independent cold solves — neither may judge (or hide
        # behind) the other. Cohort key; the direction pin stays the
        # metric's own (steps/sec alarms on a DROP, like MLUPS).
        "session": session,
        "warm_start": warm_start,
        # Router records (bench.py --serve --router): the routing mode
        # is experiment identity — an auto-routed run's cohorts, sticky
        # executables, and sentinel baselines form per routed backend,
        # so it never judges (or hides behind) a hand-picked baseline.
        # "off" (the stamped default) and None (pre-router artifacts)
        # normalize to the same cohort: old baselines stay comparable.
        "routed_backend": routed_backend or "off",
        # Mixed-tenant records (bench.py --serve --tenants SPEC): the
        # canonical tenant mix is experiment identity — a fair-queued
        # a:1,b:4 load's percentiles form under deficit-weighted
        # service, so they never judge (or hide behind) a single-tenant
        # FIFO baseline. "off" (the stamped default) and None
        # (pre-tenancy artifacts) normalize to the same cohort: old
        # baselines stay comparable.
        "tenant_mix": tenant_mix or "off",
        "failed": bool(failed),
        "note": note,
    }


def record_from_result(result: dict, source: str) -> Optional[dict]:
    """A bench result line ({"metric": …, "value": …, "detail": …}) as a
    sentinel record; None when it is not a bench metric.

    Detail keys are picked explicitly, never copied wholesale: the
    flight-recorder attribution serve-mode records carry
    (``p99_exemplar``, ``slowest_requests`` — per-request trace ids and
    latency decompositions) is diagnosis payload, not experiment
    identity, so it must never leak into :func:`cohort_key` and split
    cohorts (pinned by ``tests/test_flight.py``)."""
    if not isinstance(result, dict) or result.get("metric") not in _METRICS:
        return None
    det = result.get("detail") or {}
    return _mk_record(
        source,
        value=result.get("value"),
        metric=result.get("metric"),
        platform=det.get("platform"),
        backend=det.get("backend"),
        grid=det.get("grid"),
        dtype=det.get("dtype"),
        devices=det.get("devices"),
        fault_load=det.get("fault_load"),
        arrival_rate=det.get("arrival_rate"),
        workers=det.get("workers"),
        geometry_mix=det.get("geometry_mix"),
        verify_every=det.get("verify_every"),
        preconditioner=det.get("preconditioner"),
        device_topology=det.get("device_topology"),
        krylov_mode=det.get("krylov_mode"),
        deflation=det.get("deflation"),
        repeat_fingerprint=det.get("repeat_fingerprint"),
        session=det.get("session"),
        warm_start=det.get("warm_start"),
        routed_backend=det.get("routed_backend"),
        tenant_mix=det.get("tenant_mix"),
    )


def records_from_result(result: dict, source: str) -> list[dict]:
    """:func:`record_from_result` plus the calibration lift: a serve-
    mode bench record stamping ``detail["forecast_calibration_err_pct"]``
    (bench.py records it on every --serve run) yields a SECOND record
    under the ``obs.forecast.calibration_err_pct`` metric — the same
    experiment identity, its own metric cohort (metric is part of
    :func:`cohort_key`), with the lower-is-better direction pin: a
    forecaster whose p50 iteration error grew is the regression."""
    rec = record_from_result(result, source)
    if rec is None:
        return []
    out = [rec]
    det = result.get("detail") or {}
    cal = det.get("forecast_calibration_err_pct")
    if cal is not None:
        lifted = dict(rec)
        lifted["source"] = f"{source}:forecast-calibration"
        lifted["metric"] = "obs.forecast.calibration_err_pct"
        lifted["value"] = cal
        out.append(lifted)
    return out


def load_driver_artifact(path) -> list[dict]:
    """One driver snapshot of a bench run ({n, cmd, rc, tail, parsed}).
    A nonzero rc or an unparseable bench line is a failed-run record —
    present in the verdict (a crash is evidence), never in a cohort
    baseline."""
    path = pathlib.Path(path)
    try:
        raw = json.loads(path.read_text())
    except (OSError, ValueError) as e:
        return [_mk_record(path.name, failed=True, note=f"unreadable: {e}")]
    if not isinstance(raw, dict):
        return [_mk_record(path.name, failed=True, note="not an object")]
    parsed = raw.get("parsed")
    if raw.get("rc") not in (0, None) or not isinstance(parsed, dict):
        return [_mk_record(
            path.name, failed=True,
            note=f"rc={raw.get('rc')}, no parsed bench record",
        )]
    return records_from_result(parsed, path.name)


def load_session(path) -> list[dict]:
    """Bench records out of a session.jsonl evidence log (the entries
    whose ``result`` is a bench metric line; probe/sweep steps are not
    comparable measurements and are skipped)."""
    path = pathlib.Path(path)
    try:
        lines = path.read_text().splitlines()
    except OSError:
        return []
    out = []
    for i, line in enumerate(lines):
        line = line.strip()
        if not line:
            continue
        try:
            entry = json.loads(line)
        except ValueError:
            continue
        if not isinstance(entry, dict):
            continue
        out.extend(records_from_result(
            entry.get("result"),
            f"{path.name}:{i + 1} ({entry.get('step', '?')})",
        ))
    return out


def cohort_key(rec: dict):
    """Records are only ever compared inside this key: same metric, same
    grid, same dtype, same platform/backend/device-count — and, for
    service-mode records, the same injected fault load, the same
    open-loop arrival rate, the same fleet worker count, the same
    geometry-mix family count, the same integrity-probe stride, the
    same preconditioner, AND the same Krylov-memory shape — batched
    recurrence mode, deflation bit, repeat-fingerprint family count
    (fault-load runs are never judged against clean baselines;
    throughput at one offered load is a different experiment from
    another; a W-worker fleet never judges a single-worker baseline; a
    K-family mixed-geometry load never judges a single-ellipse one; a
    verified solve never indicts an unverified baseline; an MG run
    never judges a Jacobi one; a block batch never judges the
    independent family; a warm repeat-fingerprint run never judges a
    cold baseline; a warm-started session stream never judges
    independent cold solves; a fair-queued mixed-tenant run never
    judges a single-tenant FIFO baseline — or vice versa, all of
    them)."""
    return (rec.get("metric"), tuple(rec.get("grid") or ()),
            rec.get("dtype"), rec.get("platform"), rec.get("backend"),
            rec.get("devices"), rec.get("fault_load"),
            rec.get("arrival_rate"), rec.get("workers"),
            rec.get("geometry_mix"), rec.get("verify_every"),
            rec.get("preconditioner"), rec.get("device_topology"),
            rec.get("krylov_mode"), rec.get("deflation"),
            rec.get("repeat_fingerprint"),
            rec.get("session"), rec.get("warm_start"),
            rec.get("routed_backend") or "off",
            rec.get("tenant_mix") or "off")


def _threshold(others: list[float], k: float, rel_tol: float,
               lower_is_better: bool = False) -> dict:
    """The cohort's alarm line: guard below the median for
    higher-is-better metrics, above it for lower-is-better ones."""
    med = median(others)
    mad = median(abs(v - med) for v in others)
    guard = max(k * 1.4826 * mad, rel_tol * abs(med))
    return {"median": med, "mad": mad,
            "threshold": med + guard if lower_is_better else med - guard}


def evaluate(records: list[dict], k: float = 3.0,
             rel_tol: float = 0.25) -> dict:
    """Classify every record against its platform-matched cohort.

    Classifications: ``failed_run`` (no measurement), ``no_baseline``
    (first record of its cohort), ``regression`` (past the cohort's
    noise-robust alarm line), ``ok``. The overall verdict is
    ``regression`` iff any record regressed.
    """
    verdicts = []
    for rec in records:
        v = dict(rec)
        if rec["failed"] or rec["value"] is None:
            v["classification"] = "failed_run"
            verdicts.append(v)
            continue
        others = [
            r["value"] for r in records
            if r is not rec and not r["failed"] and r["value"] is not None
            and cohort_key(r) == cohort_key(rec)
        ]
        if not others:
            v["classification"] = "no_baseline"
            verdicts.append(v)
            continue
        lower_better = rec.get("metric") in _LOWER_IS_BETTER
        stats = _threshold(others, k, rel_tol,
                           lower_is_better=lower_better)
        v.update(cohort_n=len(others),
                 cohort_median=round(stats["median"], 2),
                 cohort_mad=round(stats["mad"], 3),
                 threshold=round(stats["threshold"], 2))
        slowed = (rec["value"] > stats["threshold"] if lower_better
                  else rec["value"] < stats["threshold"])
        v["classification"] = "regression" if slowed else "ok"
        verdicts.append(v)
    regressions = [v["source"] for v in verdicts
                   if v["classification"] == "regression"]
    counts: dict[str, int] = {}
    for v in verdicts:
        counts[v["classification"]] = counts.get(v["classification"], 0) + 1
    return {
        "schema": "poisson_tpu.regress/1",
        "k": k,
        "rel_tol": rel_tol,
        "records": verdicts,
        "classification_counts": counts,
        "regressions": regressions,
        "verdict": "regression" if regressions else "ok",
    }


def load_default_history(root=_ROOT) -> list[dict]:
    """The repo's committed evidence set: driver snapshots
    (BENCH_r*.json) and the session log when present."""
    root = pathlib.Path(root)
    records: list[dict] = []
    for path in sorted(root.glob("BENCH_r[0-9]*.json")):
        records.extend(load_driver_artifact(path))
    session = root / "benchmarks" / "results" / "session.jsonl"
    if session.exists():
        records.extend(load_session(session))
    return records


def load_contracts_report(path) -> dict:
    """Summarize a ``python -m poisson_tpu.contracts --json`` artifact
    as a verdict block: ``regression`` on any unsuppressed finding or
    ledger problem (an unreadable artifact is also a regression — a
    gate that silently stopped producing evidence is not a passing
    gate)."""
    try:
        raw = json.loads(pathlib.Path(path).read_text())
        counts = raw["counts"]
        findings = int(counts["findings"]) + int(
            counts.get("ledger_problems", 0))
        return {
            "source": str(path),
            "findings": findings,
            "suppressed": int(counts.get("suppressed", 0)),
            "rules": int(counts.get("rules", 0)),
            "verdict": "ok" if raw.get("ok") and findings == 0
                       else "regression",
        }
    except (OSError, ValueError, KeyError, TypeError) as e:
        return {"source": str(path), "findings": None,
                "note": f"unreadable contracts report: {e!r}",
                "verdict": "regression"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=str(_ROOT),
                    help="repo root to glob BENCH_*.json history from "
                         "(default: this checkout)")
    ap.add_argument("--history", nargs="*", default=None, metavar="FILE",
                    help="explicit history files instead of the --root "
                         "glob (driver snapshots or session .jsonl logs)")
    ap.add_argument("--session", default=None, metavar="JSONL",
                    help="additional session.jsonl evidence log")
    ap.add_argument("--k", type=float, default=3.0,
                    help="MAD multiplier for the alarm line (default 3)")
    ap.add_argument("--rel-tol", type=float, default=0.25,
                    help="relative floor under the median that is never "
                         "an alarm (default 0.25 — run-to-run jitter)")
    ap.add_argument("--pretty", action="store_true",
                    help="indent the JSON verdict")
    ap.add_argument("--contracts-report", default=None, metavar="JSON",
                    help="a `python -m poisson_tpu.contracts --json` "
                         "report to fold into the verdict: any "
                         "unsuppressed finding or ledger problem is a "
                         "regression (contract drift is a regression "
                         "in correctness, judged beside the perf "
                         "cohorts; this stays stdlib-only — the "
                         "checker runs separately, we read its "
                         "artifact)")
    args = ap.parse_args(argv)

    if args.history is not None:
        records = []
        for path in args.history:
            if str(path).endswith(".jsonl"):
                records.extend(load_session(path))
            else:
                records.extend(load_driver_artifact(path))
    else:
        records = load_default_history(args.root)
    if args.session:
        records.extend(load_session(args.session))
    if not records:
        print("regress: no bench records found", file=sys.stderr)
        return 2
    report = evaluate(records, k=args.k, rel_tol=args.rel_tol)
    if args.contracts_report:
        report["contracts"] = load_contracts_report(args.contracts_report)
        if report["contracts"]["verdict"] == "regression":
            report["verdict"] = "regression"
            report["regressions"].append(args.contracts_report)
    print(json.dumps(report, indent=1 if args.pretty else None))
    return 1 if report["verdict"] == "regression" else 0


if __name__ == "__main__":
    sys.exit(main())
