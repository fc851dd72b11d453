"""Summarize a TPU evidence session log as a markdown table.

Reads ``benchmarks/results/session.jsonl`` (or the path given) and
prints one row per step with the numbers that matter for BENCH.md —
backend, MLUPS, iterations vs golden, L2 — plus the layout and
backend-chain decisions. The table is the working draft for the
post-session BENCH.md update; the jsonl stays the ground truth.

Batched throughput records (``bench.py --batch B`` →
``{"metric": "batched_solves_per_sec", …}``) render with the value column
in solves/sec (marked ``sv/s`` — it is NOT an MLUPS figure), the batch
size and sequential speedup next to the backend, and the
passes-at-ceiling column blanked (the per-iteration bandwidth model is a
single-solve model). A record whose per-member iteration counts did not
match the sequential solver is flagged ``ITER-MISMATCH`` in the status —
treat it as a correctness incident, not a throughput number.

``--telemetry DIR`` switches to solve-forensics mode: renders a report
from a unified-telemetry directory (``poisson_tpu.obs`` — what
``python -m poisson_tpu … --trace-dir DIR`` writes): phases and their
durations, restarts/escalations, checkpoint activity, watchdog
beats/stalls, stop verdicts, MLUPS, the streamed convergence curve
summary, the continuous-batching refill counters (``serve.refill.*``
plus any open-loop batch-drain-vs-continuous A/B records), the
solver-session counters (``session.*`` / ``serve.session.*`` plus any
``bench.py --session`` warm-vs-cold A/B records), the
performance-attribution gauges (compiled-program cost vs
the analytic stencil model, achieved-vs-roofline fraction —
``poisson_tpu.obs.costs``), and the regression sentinel's verdict over
the committed bench history (``benchmarks/regress.py``). Reads the files
directly (stdlib only): importing the framework would initialize jax,
which a post-session forensics pass must never risk.

Usage: python benchmarks/summarize_session.py [session.jsonl] [--since ISO]
       python benchmarks/summarize_session.py --telemetry DIR
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parents[1]


def _fmt(v) -> str:
    if v is None:
        return "—"
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def _first(*vals):
    """First value that is present — unlike an ``or`` chain, a legitimate
    0/0.0 is a value, not a missing field."""
    for v in vals:
        if v is not None:
            return v
    return None


# v5e stream ceiling (BENCH.md's physical-consistency model) and each
# backend's canvas-pass model: a measurement whose per-iteration time
# admits FEWER effective array passes than its backend's model moves is
# an overlap/measurement artifact, not throughput (the round-2 failure
# class). xla's effective pass count is fusion-dependent — ~8 is the
# break-even documented in BENCH.md's headline sanity paragraph.
# The ceiling is a *v5e* number: records from any other TPU generation get
# the passes figure printed with no sane/SUSPECT verdict (a v5p session
# judged against the v5e ceiling would mislabel every row).
_STREAM_TBPS = 0.82
_MODEL_PASSES = {"pallas_fused": 14.7, "pallas_ca": 10.1, "xla": 8.0}


def _is_v5e(device_kind) -> bool:
    """True for the device_kind strings libtpu uses for v5e parts
    ('TPU v5e', 'TPU v5 lite', 'TPU v5litepod…')."""
    if not device_kind:
        return False
    kind = str(device_kind).lower()
    return "v5e" in kind or ("v5" in kind and "lite" in kind)


def _passes_budget(det: dict, device_kind=None) -> tuple[str, str]:
    """(passes-at-ceiling, verdict) for a bench detail record.
    ``device_kind`` falls back to the record's own field; the verdict is
    only emitted for v5e records — the ceiling was measured there."""
    grid = det.get("grid")
    secs = det.get("solve_seconds")
    iters = det.get("iterations")
    if not (isinstance(grid, list) and len(grid) == 2 and secs and iters):
        return "—", ""
    array_bytes = (grid[0] + 1) * (grid[1] + 1) * 4
    budget = _STREAM_TBPS * 1e12 * (secs / iters) / array_bytes
    model = _MODEL_PASSES.get(det.get("backend"))
    verdict = ""
    if (model is not None and det.get("platform") == "tpu"
            and _is_v5e(device_kind or det.get("device_kind"))):
        verdict = " SUSPECT(overlap?)" if budget < model else " sane"
    return f"{budget:.1f}", verdict


def _row_from(step: str, e: dict) -> list[str] | None:
    at = e.get("at", "—")
    r = e.get("result")
    if not isinstance(r, dict):
        if "ok" in e:
            status = "ok" if e["ok"] else (
                f"rc={e['rc']}" if "rc" in e else
                str(e.get("error", e.get("skipped", "failed")))
            )
        else:
            # Bookkeeping entries (done/abort) carry neither ok nor a
            # result; show their payload rather than implying failure.
            status = json.dumps(
                {k: v for k, v in e.items() if k not in ("step", "at")}
            )
        return [step, status[:60], "—", "—", "—", "—", at]
    det = r.get("detail") or {}
    backend = _first(det.get("backend"), r.get("backend"), "—")
    platform = _first(det.get("platform"), r.get("platform"),
                      "tpu" if ("device_kind" in r or "kind" in r) else "—")
    mlups = _first(r.get("value"), r.get("mlups"), r.get("flagship_mlups"),
                   r.get("big_mlups"))
    iters = _first(det.get("iterations"), r.get("iterations"),
                   r.get("flagship_iters"))
    l2 = _first(det.get("l2_error_vs_analytic"), r.get("l2"),
                r.get("l2_error"))
    status = "ok" if r.get("ok", e.get("ok")) else "FAILED"
    kind = _first(det.get("device_kind"), r.get("device_kind"),
                  r.get("kind"))
    # Batched throughput records (bench.py --batch): the value column is
    # solves/sec, not MLUPS; say so inline, and show the batch size plus
    # the sequential speedup next to the backend. The per-member parity
    # bit rides in the status so a mismatch is never a quiet "ok".
    if r.get("metric") == "batched_solves_per_sec":
        backend = f"{backend} B={det.get('batch', '?')}"
        if r.get("speedup_vs_sequential") is not None:
            backend += f" ({r['speedup_vs_sequential']}x vs seq)"
        if det.get("iterations_match_sequential") is False:
            status += " ITER-MISMATCH"
        budget, verdict = "—", ""
        value_cell = f"{_fmt(mlups)} sv/s"
    else:
        budget, verdict = _passes_budget(det, kind)
        value_cell = _fmt(mlups)
    return [step, f"{backend} ({platform}) {status}", value_cell,
            _fmt(iters), _fmt(l2), budget + verdict, at]


# -- telemetry forensics mode (poisson_tpu.obs trace directories) -------


def _flatten_event(rec: dict) -> dict:
    """Normalize a JSONL event record across schema generations (the
    stdlib twin of ``obs.trace.normalize_event`` — this module must not
    import the framework): v2 lines carry caller fields under ``attrs``,
    merged flat here where they don't collide with the envelope; v1
    lines pass through unchanged."""
    attrs = rec.get("attrs")
    if not isinstance(attrs, dict):
        return rec
    out = {k: v for k, v in attrs.items() if k not in rec}
    out.update(rec)
    out["attrs"] = attrs
    return out


def _read_jsonl(path: pathlib.Path) -> list[dict]:
    records = []
    try:
        lines = path.read_text().splitlines()
    except OSError:
        return records
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            records.append(_flatten_event(json.loads(line)))
        except ValueError:
            continue        # torn tail line of a killed process
    return records


def _load_telemetry(tdir: pathlib.Path):
    """(events, counters, gauges_by_rank, stream_by_rank) from an obs
    trace directory — local readers on the documented schema; see the
    module docstring for why this does not import poisson_tpu.obs."""
    events, counters, gauges, stream = [], {}, {}, {}
    for p in sorted(tdir.glob("events-rank*.jsonl")):
        events.extend(_read_jsonl(p))
    events.sort(key=lambda r: r.get("at_unix", 0.0))
    for p in sorted(tdir.glob("metrics-rank*.json")):
        try:
            snap = json.loads(p.read_text())
        except (OSError, ValueError):
            continue
        for name, val in (snap.get("counters") or {}).items():
            try:
                counters[name] = counters.get(name, 0) + val
            except TypeError:
                continue
        g = snap.get("gauges") or {}
        if g:
            gauges[str(snap.get("rank", p.stem))] = g
    for p in sorted(tdir.glob("stream-rank*.jsonl")):
        rank = p.stem.replace("stream-rank", "")
        stream[rank] = _read_jsonl(p)
    return events, counters, gauges, stream


def _perf_attribution_section(gauges_by_rank: dict) -> None:
    """Render the cost/roofline gauges (obs.costs) per rank: what the
    compiled program cost vs the analytic model, and the bandwidth
    fraction the run achieved."""
    interesting = ("cost.", "roofline.")
    rows = []
    for rank, gauges in sorted(gauges_by_rank.items()):
        for name in sorted(gauges):
            if any(name.startswith(p) for p in interesting):
                rows.append((rank, name, gauges[name]))
    if not rows:
        return
    print("\n## Performance attribution\n")
    print("| rank | gauge | value |")
    print("|---|---|---|")
    for rank, name, val in rows:
        shown = f"{val:.4g}" if isinstance(val, float) else str(val)
        print(f"| {rank} | {name} | {shown} |")
    for rank, gauges in sorted(gauges_by_rank.items()):
        agree = gauges.get("cost.model_agreement")
        if isinstance(agree, (int, float)):
            verdict = ("agrees with the analytic stencil model"
                       if abs(agree - 1.0) <= 0.25
                       else "DRIFTED from the analytic stencil model "
                            "(solver work or compiler changed)")
            print(f"\nrank {rank}: compiled bytes/iteration = "
                  f"{agree:.2f}x the model — {verdict}.")
        frac = gauges.get("roofline.fraction")
        if isinstance(frac, (int, float)):
            print(f"rank {rank}: achieved {frac:.0%} of the platform "
                  f"bandwidth ceiling.")


def _regress_verdict_section(root: pathlib.Path) -> None:
    """The regression sentinel's verdict over the committed bench
    history, rendered into the forensics report (best-effort: a missing
    or failing sentinel must not sink the post-mortem)."""
    try:
        sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
        import regress

        records = regress.load_default_history(root)
        if not records:
            return
        report = regress.evaluate(records)
        print("\n## Regression sentinel\n")
        counts = ", ".join(f"{k}: {v}" for k, v in
                           sorted(report["classification_counts"].items()))
        print(f"- verdict: **{report['verdict']}** ({counts})")
        for v in report["records"]:
            if v["classification"].endswith("regression"):
                print(f"- REGRESSION {v['source']}: {v['value']} vs "
                      f"cohort median {v.get('cohort_median')} "
                      f"(threshold {v.get('threshold')})")
    except Exception as e:
        print(f"\n(regression sentinel unavailable: {e!r})",
              file=sys.stderr)


def telemetry_report(tdir: pathlib.Path) -> int:
    if not tdir.is_dir():
        print(f"no telemetry directory at {tdir}", file=sys.stderr)
        return 1
    events, counters, gauges_by_rank, stream = _load_telemetry(tdir)
    traces = sorted(tdir.glob("trace-rank*.trace.json"))
    print(f"# Solve forensics: {tdir}")
    print(f"\n{len(events)} events, {len(traces)} rank trace(s)"
          + (f" — open in https://ui.perfetto.dev" if traces else ""))

    # Phases: span_end records carry the span's duration.
    spans: dict[str, list[float]] = {}
    for e in events:
        if e.get("kind") == "span_end" and "seconds" in e:
            spans.setdefault(e["name"], []).append(e["seconds"])
    if spans:
        print("\n## Phases\n")
        print("| span | count | total s | mean s |")
        print("|---|---|---|---|")
        for name, secs in sorted(spans.items(),
                                 key=lambda kv: -sum(kv[1])):
            print(f"| {name} | {len(secs)} | {sum(secs):.4f} "
                  f"| {sum(secs) / len(secs):.4f} |")

    # The headline: what the solve reported about itself.
    reports = [e for e in events
               if e.get("kind") == "event" and e.get("name") == "solve.report"]
    for r in reports:
        stopped = r.get("stopped")
        print(f"\n## Solve {r.get('M')}x{r.get('N')} "
              f"[{r.get('backend', '?')} / {r.get('dtype', '?')}"
              + (f" / {r.get('device_kind')}" if r.get("device_kind")
                 else "") + "]\n")
        print(f"- iterations: {r.get('iterations')}  "
              f"verdict: {stopped if stopped else 'converged'}")
        print(f"- solve: {r.get('solve_seconds', 0):.4f} s   "
              f"compile: {r.get('compile_seconds', 0):.2f} s   "
              f"throughput: {r.get('mlups', 0):.0f} MLUPS")
        if r.get("restarts"):
            print(f"- RECOVERED: {r['restarts']} restart(s): "
                  f"{r.get('recovery')}")

    # Batched throughput records (bench.py --batch / the solve-batched
    # CLI): solves/sec is the headline, with the per-member parity bit
    # surfaced — a mismatch is a correctness incident, not a fast run.
    batched = [e for e in events if e.get("kind") == "event" and e.get(
        "name") in ("bench.batched", "solve_batched.report")]
    if batched:
        print("\n## Batched throughput\n")
        for e in batched:
            grid = e.get("grid") or [e.get("M"), e.get("N")]
            sps = e.get("solves_per_sec")
            speedup = e.get("speedup",
                            e.get("speedup_vs_sequential"))
            match = e.get("iterations_match_sequential",
                          e.get("iterations_match"))
            line = (f"- {grid[0]}x{grid[1]} batch={e.get('batch')}: "
                    f"{sps if sps is not None else '?'} solves/s")
            if speedup is not None:
                line += f", {speedup}x vs sequential"
            if match is False:
                line += " — PER-MEMBER ITERATIONS MISMATCH"
            print(line)

    # Continuous batching (serve.refill.*): the lane table's refill
    # state machine, plus any open-loop A/B records
    # (bench.py --serve --arrival-rate).
    refill_counters = {name: val for name, val in counters.items()
                       if name.startswith("serve.refill.")}
    openloop = [e for e in events if e.get("kind") == "event"
                and e.get("name") == "bench.serve_openloop"]
    if refill_counters or openloop:
        print("\n## Continuous batching\n")
        if refill_counters:
            print("| refill counter | value |")
            print("|---|---|")
            for name in sorted(refill_counters):
                val = refill_counters[name]
                shown = (f"{val:.4f}" if isinstance(val, float)
                         else str(val))
                print(f"| {name} | {shown} |")
            splices = refill_counters.get("serve.refill.splices", 0)
            idle = refill_counters.get("serve.refill.idle_lane_steps", 0)
            print(f"\n{splices} splice(s) into running lane programs, "
                  f"{idle} idle lane-step(s) paid for the open seats.")
        for e in openloop:
            grid = e.get("grid") or ["?", "?"]
            verdict = ("continuous beat batch-drain at equal p99"
                       if e.get("continuous_beats_drain")
                       else "batch-drain held its own at this load "
                            "(see the regime note in BENCH.md)")
            print(f"- {grid[0]}x{grid[1]} @ {e.get('arrival_rate')}/s: "
                  f"continuous {e.get('sustained_solves_per_sec')} sv/s "
                  f"(p99 {e.get('p99_seconds')} s) vs drain "
                  f"{e.get('drain_solves_per_sec')} sv/s (p99 "
                  f"{e.get('drain_p99_seconds')} s) — {verdict}")

    # Krylov memory (poisson_tpu.krylov): block-mode dispatch traffic,
    # basis-cache arithmetic, iterations saved by warm starts, and the
    # repeat-fingerprint bench's cold-vs-warm latency split (gauges
    # stamped by bench.py --serve --repeat-fingerprint).
    krylov_counters = {name: val for name, val in counters.items()
                       if name.startswith(("krylov.", "serve.krylov."))}
    repeat_fp = [e for e in events if e.get("kind") == "event"
                 and e.get("name") == "bench.serve_repeat_fingerprint"]
    if krylov_counters or repeat_fp:
        print("\n## Krylov memory\n")
        if krylov_counters:
            print("| krylov counter | value |")
            print("|---|---|")
            for name in sorted(krylov_counters):
                val = krylov_counters[name]
                shown = (f"{val:.4f}" if isinstance(val, float)
                         and val != int(val) else str(int(val)))
                print(f"| {name} | {shown} |")
            hits = krylov_counters.get("krylov.cache.hits", 0)
            misses = krylov_counters.get("krylov.cache.misses", 0)
            saved = krylov_counters.get("krylov.iterations_saved", 0)
            total = hits + misses
            rate = (hits / total) if total else 0.0
            print(f"\nbasis cache hit rate {rate:.0%} "
                  f"({int(hits)} hit(s) / {int(misses)} miss(es)); "
                  f"{int(saved)} iteration(s) saved by warm starts; "
                  f"{int(krylov_counters.get('krylov.fallbacks', 0))} "
                  f"stale-basis fallback(s) (each audible, never a "
                  f"wrong answer).")
        for e in repeat_fp:
            grid = e.get("grid") or ["?", "?"]
            print(f"- {grid[0]}x{grid[1]} @ {e.get('arrival_rate')}/s, "
                  f"{e.get('repeat_fingerprint')} families "
                  f"(Zipf repeats): cold p50 "
                  f"{e.get('cold_p50_seconds')} s "
                  f"({e.get('cold_requests')} request(s)) vs warm p50 "
                  f"{e.get('warm_p50_seconds')} s "
                  f"({e.get('warm_requests')} request(s)), hit rate "
                  f"{e.get('krylov_hit_rate')} — the repeat-operator "
                  f"warm-start win, measured.")

    # Solver sessions (serve.session): durable stream lifecycles, the
    # warm-start hit/fallback arithmetic, recovery activity, and the
    # open-loop session bench's warm-vs-cold verdict (bench.py
    # --session).
    session_counters = {name: val for name, val in counters.items()
                        if name.startswith(("session.",
                                            "serve.session."))}
    session_bench = [e for e in events if e.get("kind") == "event"
                     and e.get("name") == "bench.session"]
    if session_counters or session_bench:
        print("\n## Solver sessions\n")
        if session_counters:
            print("| session counter | value |")
            print("|---|---|")
            for name in sorted(session_counters):
                val = session_counters[name]
                shown = (f"{val:.4f}" if isinstance(val, float)
                         and val != int(val) else str(int(val)))
                print(f"| {name} | {shown} |")
            steps = session_counters.get("session.steps", 0)
            hits = session_counters.get("session.warm.hits", 0)
            falls = session_counters.get("session.warm.fallbacks", 0)
            rate = (hits / steps) if steps else 0.0
            print(f"\nwarm hit rate {rate:.0%} ({int(hits)} warm of "
                  f"{int(steps)} step(s)); {int(falls)} stale-warm "
                  f"fallback(s) (each an audible "
                  f"``session.warm.fallback`` event, never a silent "
                  f"wrong start); "
                  f"{int(session_counters.get('session.recovered', 0))} "
                  f"session(s) recovered from the journal at the "
                  f"committed step boundary; "
                  f"{int(session_counters.get('session.step.deadline_misses', 0))} "
                  f"step deadline miss(es).")
        for e in session_bench:
            grid = e.get("grid") or ["?", "?"]
            verdict = ("warm stream beat cold solves"
                       if e.get("session_beats_cold")
                       else "cold solves held their own (warm starts "
                            "not paying on this schedule)")
            print(f"- {grid[0]}x{grid[1]} x {e.get('steps')} steps: "
                  f"session {e.get('steps_per_sec')} steps/s vs cold "
                  f"{e.get('cold_solves_per_sec')} sv/s "
                  f"(speedup {e.get('speedup')}x, warm hit rate "
                  f"{e.get('warm_hit_rate')}, "
                  f"{e.get('iterations_saved')} iteration(s) saved) — "
                  f"{verdict}")

    # Forecasting (obs.forecast): the convergence observatory's feedback
    # loop — predictions made, cold-vs-calibrated split, the p50
    # absolute iteration error, predicted-deadline sheds (admission and
    # re-forecast preemption), and snapshot persistence activity.
    forecast_counters = {
        name: val for name, val in counters.items()
        if name.startswith(("obs.forecast.", "serve.forecast."))
        or name in ("serve.shed.predicted_deadline",
                    "serve.degraded.backlog_driven")}
    forecast_gauges: dict = {}
    for _rank in sorted(gauges_by_rank):
        for name, val in (gauges_by_rank[_rank] or {}).items():
            # calibration_pct is a histogram (a dict of buckets) — the
            # scalar gauges are the readable summary; skip non-numerics.
            if (name.startswith(("obs.forecast.", "serve.forecast."))
                    and isinstance(val, (int, float))):
                forecast_gauges.setdefault(name, val)
    if forecast_counters or forecast_gauges:
        print("\n## Forecasting\n")
        merged = dict(forecast_counters)
        merged.update(forecast_gauges)
        print("| forecast metric | value |")
        print("|---|---|")
        for name in sorted(merged):
            val = merged[name]
            shown = (f"{val:.4f}" if isinstance(val, float)
                     and val != int(val) else str(int(val)))
            print(f"| {name} | {shown} |")
        preds = forecast_counters.get("obs.forecast.predictions", 0)
        cold = forecast_counters.get("obs.forecast.cold_cohorts", 0)
        calib = forecast_gauges.get("obs.forecast.calibration_err_pct")
        shed = forecast_counters.get("serve.shed.predicted_deadline", 0)
        preempt = forecast_counters.get("serve.forecast.preempted", 0)
        calib_txt = (f"p50 absolute iteration error {calib:.1f}%"
                     if calib is not None
                     else "no calibration figure yet (no completed "
                          "observations)")
        print(f"\n{int(preds)} prediction(s), {int(cold)} cold-seeded "
              f"cohort(s); {calib_txt}. "
              f"{int(shed)} request(s) shed as predicted-deadline "
              f"(typed, zero compute burned), {int(preempt)} of those "
              f"preempted mid-flight by a lane-boundary re-forecast; "
              f"{int(forecast_counters.get('obs.forecast.snapshot.saves', 0))} "
              f"snapshot save(s), "
              f"{int(forecast_counters.get('obs.forecast.snapshot.torn', 0))} "
              f"torn-snapshot event(s) (each audible, model falls back "
              f"to cold seeds).")

    # Backend router (serve.router + obs.roofline): the decision mix,
    # per-arm measured-vs-model roofline fractions, and every sentinel
    # action (misprediction → demotion → half-open → recovery) as a
    # timeline of typed events.
    router_counters = {
        name: val for name, val in counters.items()
        if name.startswith("serve.router.")
        or name == "serve.degraded.backend_downshift"}
    roofline_gauges: dict = {}
    for _rank in sorted(gauges_by_rank):
        for name, val in (gauges_by_rank[_rank] or {}).items():
            # calibration_pct is a histogram dict — the scalar gauges
            # are the readable summary; skip non-numerics.
            if (name.startswith("obs.roofline.")
                    and isinstance(val, (int, float))):
                roofline_gauges.setdefault(name, val)
    router_events = [e for e in events if e.get("kind") == "event"
                     and str(e.get("name", "")).startswith(
                         "serve.router.")]
    if router_counters or roofline_gauges:
        print("\n## Backend router\n")
        merged = dict(router_counters)
        merged.update(roofline_gauges)
        print("| router metric | value |")
        print("|---|---|")
        for name in sorted(merged):
            val = merged[name]
            shown = (f"{val:.4f}" if isinstance(val, float)
                     and val != int(val) else str(int(val)))
            print(f"| {name} | {shown} |")
        decisions = router_counters.get("serve.router.decisions", 0)
        cold = router_counters.get("serve.router.cold_decisions", 0)
        warm = router_counters.get("serve.router.warm_decisions", 0)
        chosen = {name[len("serve.router.chosen."):]: val
                  for name, val in router_counters.items()
                  if name.startswith("serve.router.chosen.")}
        if chosen:
            # The decision table: per-arm picks next to their measured
            # roofline evidence (running p50 fraction of peak) — the
            # measured-vs-model comparison the router graduates on.
            print("\n| backend arm | decisions | measured p50 "
                  "fraction of peak |")
            print("|---|---|---|")
            for arm in sorted(chosen):
                frac = roofline_gauges.get(
                    f"obs.roofline.fraction.{arm}")
                print(f"| {arm} | {int(chosen[arm])} | "
                      f"{_fmt(frac) if frac is not None else '-'} |")
        calib = roofline_gauges.get("obs.roofline.calibration_err_pct")
        calib_txt = (f"p50 measured-vs-model fraction error "
                     f"{calib:.1f}%" if calib is not None
                     else "no measured observations yet")
        print(f"\n{int(decisions)} routing decision(s) "
              f"({int(cold)} cold from the analytic table, {int(warm)} "
              f"warm from measured evidence) across "
              f"{max(1, len(chosen))} arm(s); {calib_txt}; "
              f"{int(router_counters.get('serve.router.mispredictions', 0))} "
              f"misprediction(s) → "
              f"{int(router_counters.get('serve.router.demotions', 0))} "
              f"demotion(s), "
              f"{int(router_counters.get('serve.router.recoveries', 0))} "
              f"half-open recovery(ies); "
              f"{int(router_counters.get('serve.degraded.backend_downshift', 0))} "
              f"backend-downshift rung engagement(s).")
        sentinel = [e for e in router_events
                    if e.get("name") in ("serve.router.misprediction",
                                         "serve.router.demote",
                                         "serve.router.half_open",
                                         "serve.router.recover")]
        for e in sentinel[:20]:
            attrs = e.get("attrs") if isinstance(e.get("attrs"), dict) \
                else e
            name = str(e.get("name"))[len("serve.router."):]
            line = (f"- {name}: {attrs.get('backend')} on device "
                    f"{attrs.get('device')}")
            if e.get("name") == "serve.router.misprediction":
                line += (f" — measured fraction "
                         f"{attrs.get('fraction')} vs expected "
                         f"{attrs.get('expected')} (threshold "
                         f"{attrs.get('threshold')})")
            print(line)

    # Tenant fairness (serve.tenancy): per-tenant shares, quota/retry
    # budgets, outcome tallies, and the fair-queue/quota sentinel
    # counters — the section a noisy-neighbor post-mortem starts from.
    tenant_counters = {name: val for name, val in counters.items()
                       if name.startswith("serve.tenant.")}
    tenant_gauges: dict = {}
    for _rank in sorted(gauges_by_rank):
        for name, val in (gauges_by_rank[_rank] or {}).items():
            if (name.startswith("serve.tenant.")
                    and isinstance(val, (int, float))):
                tenant_gauges.setdefault(name, val)
    if tenant_counters or tenant_gauges:
        print("\n## Tenant fairness\n")

        def _per_tenant(prefix, source):
            return {name[len(prefix) + 1:]: val
                    for name, val in source.items()
                    if name.startswith(prefix + ".")}

        shares = _per_tenant("serve.tenant.share", tenant_gauges)
        quota_tok = _per_tenant("serve.tenant.quota_tokens",
                                tenant_gauges)
        retry_tok = _per_tenant("serve.tenant.retry_tokens",
                                tenant_gauges)
        slo_burn = _per_tenant("serve.tenant.slo_burn", tenant_gauges)
        admitted = _per_tenant("serve.tenant.admitted", tenant_counters)
        completed = _per_tenant("serve.tenant.completed",
                                tenant_counters)
        shed = _per_tenant("serve.tenant.shed", tenant_counters)
        errors = _per_tenant("serve.tenant.errors", tenant_counters)
        retries = _per_tenant("serve.tenant.retries", tenant_counters)
        names = sorted(set(shares) | set(admitted) | set(completed))
        if names:
            print("| tenant | share | admitted | completed | errors "
                  "| shed | retries | quota tokens | retry budget "
                  "| SLO burn |")
            print("|---|---|---|---|---|---|---|---|---|---|")
            for t in names:
                rt = retry_tok.get(t)
                rt_txt = ("off" if rt is not None and rt < 0
                          else _fmt(rt) if rt is not None else "-")
                print(f"| {t} | {_fmt(shares.get(t))} "
                      f"| {int(admitted.get(t, 0))} "
                      f"| {int(completed.get(t, 0))} "
                      f"| {int(errors.get(t, 0))} "
                      f"| {int(shed.get(t, 0))} "
                      f"| {int(retries.get(t, 0))} "
                      f"| {_fmt(quota_tok.get(t)) if t in quota_tok else '-'} "
                      f"| {rt_txt} "
                      f"| {_fmt(slo_burn.get(t)) if t in slo_burn else '-'} |")
        print(f"\n{int(tenant_counters.get('serve.tenant.quota_sheds', 0))} "
              f"quota shed(s) (typed quota_exceeded, zero compute), "
              f"{int(tenant_counters.get('serve.tenant.promotions', 0))} "
              f"fair-queue promotion(s), "
              f"{int(tenant_counters.get('serve.tenant.lane_deferred', 0))} "
              f"lane-share deferral(s), "
              f"{int(tenant_counters.get('serve.tenant.retry_exhausted', 0))} "
              f"retry-budget exhaustion(s), "
              f"{int(tenant_counters.get('serve.tenant.degraded_offender', 0))} "
              f"offender-first degradation(s) vs "
              f"{int(tenant_counters.get('serve.tenant.degraded_spared', 0))} "
              f"spared.")

    # Flight recorder (obs.flight): per-request causal traces and their
    # latency decompositions — render the aggregate view plus ONE
    # request's end-to-end timeline (the slowest, the request a p99
    # post-mortem starts from).
    def _fa(rec, key, default=None):
        attrs = rec.get("attrs")
        if isinstance(attrs, dict) and key in attrs:
            return attrs[key]
        return rec.get(key, default)

    flight_outcomes = [e for e in events if e.get("kind") == "event"
                       and e.get("name") == "flight.outcome"]
    if flight_outcomes:
        print("\n## Flight recorder\n")
        admits = sum(1 for e in events if e.get("name") == "flight.admit")
        print(f"{admits} request trace(s), {len(flight_outcomes)} "
              f"typed outcome leaf(s).")
        ranked = sorted(flight_outcomes,
                        key=lambda e: -(_fa(e, "wall_s", 0.0) or 0.0))
        print("\n| request | outcome | wall s | queue | compute "
              "| lane wait | backoff | overhead | trace id |")
        print("|---|---|---|---|---|---|---|---|---|")
        for e in ranked[:5]:
            print(f"| {_fa(e, 'request_id')} "
                  f"| {_fa(e, 'kind')}:{_fa(e, 'type')} "
                  f"| {_fmt(_fa(e, 'wall_s'))} "
                  f"| {_fmt(_fa(e, 'queue_s'))} "
                  f"| {_fmt(_fa(e, 'compute_s'))} "
                  f"| {_fmt(_fa(e, 'lane_wait_s'))} "
                  f"| {_fmt(_fa(e, 'backoff_s'))} "
                  f"| {_fmt(_fa(e, 'overhead_s'))} "
                  f"| {_fa(e, 'trace_id')} |")
        slowest_tid = _fa(ranked[0], "trace_id")
        trace_evs = [e for e in events
                     if str(e.get("name", "")).startswith("flight.")
                     and _fa(e, "trace_id") == slowest_tid]
        trace_evs.sort(key=lambda r: (
            _fa(r, "t", _fa(r, "t0", 0.0)) or 0.0,
            r.get("at_unix", 0.0)))
        print(f"\nSlowest request timeline (trace {slowest_tid} — "
              f"`python -m poisson_tpu trace "
              f"{_fa(ranked[0], 'request_id')} --telemetry {tdir}`):\n")
        t_admit = next((_fa(e, "t", 0.0) for e in trace_evs
                        if e.get("name") == "flight.admit"), 0.0)
        for e in trace_evs:
            t = _fa(e, "t", _fa(e, "t0", 0.0)) or 0.0
            if e.get("name") == "flight.admit":
                print(f"- +{max(0.0, t - t_admit):.4f}s admit")
            elif e.get("name") == "flight.span":
                print(f"- +{max(0.0, t - t_admit):.4f}s "
                      f"{_fa(e, 'span')} [{_fa(e, 'seconds', 0.0)}s]")
            elif e.get("name") == "flight.point":
                print(f"- +{max(0.0, t - t_admit):.4f}s · "
                      f"{_fa(e, 'point')}")
            elif e.get("name") == "flight.outcome":
                print(f"- +{max(0.0, t - t_admit):.4f}s outcome "
                      f"{_fa(e, 'kind')}:{_fa(e, 'type')}")
        slo_counters = {k: v for k, v in counters.items()
                        if k.startswith("serve.slo.")}
        if slo_counters:
            good = slo_counters.get("serve.slo.good", 0)
            bad = slo_counters.get("serve.slo.bad", 0)
            total = good + bad
            if total:
                print(f"\nSLO: {good}/{total} good "
                      f"({good / total:.1%} of outcomes met the "
                      "objective).")

    # Incidents: everything that is not routine liveness.
    incidents = [e for e in events if e.get("kind") == "event" and e.get(
        "name") in ("resilient.restart", "watchdog.stall",
                    "checkpoint.crc_failure", "checkpoint.corrupt",
                    "checkpoint.generation_fallback", "multihost.init_retry",
                    "multihost.degraded")]
    if incidents:
        print("\n## Incidents\n")
        for e in incidents:
            detail = {k: v for k, v in e.items()
                      if k not in ("at_unix", "at_mono", "kind", "name",
                                   "rank")}
            print(f"- rank {e.get('rank', '?')} `{e['name']}`: "
                  f"{json.dumps(detail, default=str)[:200]}")

    if counters:
        print("\n## Counters (all ranks summed)\n")
        print("| counter | value |")
        print("|---|---|")
        for name in sorted(counters):
            val = counters[name]
            shown = f"{val:.4f}" if isinstance(val, float) else str(val)
            print(f"| {name} | {shown} |")

    if stream:
        print("\n## Streamed convergence\n")
        for rank, samples in sorted(stream.items()):
            if not samples:
                continue
            first, last = samples[0], samples[-1]
            print(f"- rank {rank}: {len(samples)} samples, "
                  f"iter {first.get('k')} ||dw|| {first.get('diff'):.3e} "
                  f"→ iter {last.get('k')} ||dw|| {last.get('diff'):.3e}")

    _perf_attribution_section(gauges_by_rank)
    _regress_verdict_section(_ROOT)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("log", nargs="?", default=str(
        _ROOT / "benchmarks" / "results" / "session.jsonl"))
    ap.add_argument("--since", default=None, metavar="ISO_UTC",
                    help="only entries at/after this UTC timestamp")
    ap.add_argument("--telemetry", default=None, metavar="DIR",
                    help="render a solve-forensics report from a unified-"
                         "telemetry directory (--trace-dir output) instead "
                         "of a session log")
    args = ap.parse_args()
    if args.telemetry:
        return telemetry_report(pathlib.Path(args.telemetry))
    path = pathlib.Path(args.log)
    if not path.exists():
        print(f"no session log at {path}", file=sys.stderr)
        return 1
    rows, decisions = [], []
    for line in path.read_text().splitlines():
        try:
            e = json.loads(line)
        except ValueError:
            continue
        if args.since and e.get("at", "") < args.since:
            continue
        step = e.get("step", "?")
        if step in ("layout_decision", "backend_chain"):
            decisions.append((e.get("at"), step, e))
            continue
        row = _row_from(step, e)
        if row:
            rows.append(row)
    print("| step | backend/status | MLUPS | iters | L2 | passes@0.82TB/s | at |")
    print("|---|---|---|---|---|---|---|")
    for row in rows:
        print("| " + " | ".join(row) + " |")
    print("\npasses@0.82TB/s = effective array passes/iteration the "
          "measurement admits at the v5e stream ceiling; below the "
          "backend's pass model ⇒ overlap artifact (BENCH.md rule 2).")
    for at, step, e in decisions:
        body = {k: v for k, v in e.items() if k not in ("step", "at")}
        print(f"\n**{step}** ({at}): {json.dumps(body)[:400]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
