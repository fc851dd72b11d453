"""Unified telemetry: spans, counters, and streamed convergence.

One subsystem replaces the four ad-hoc sinks that grew around the solve
stack (PhaseTimer dicts, watchdog heartbeat JSON, restart history inside
``DivergenceError``, bench session.jsonl):

- **spans** (:mod:`poisson_tpu.obs.trace`) — nestable timed regions of
  host time. Every span is a ``jax.profiler.TraceAnnotation``, so under
  a profiler session it sits in the ``.xplane.pb`` on the device trace's
  clock. When configured, spans are also emitted as Chrome/Perfetto
  trace JSON plus a structured JSONL event log, with rank attribution so
  multihost runs merge into one timeline;
- **counters** (:mod:`poisson_tpu.obs.metrics`) — an always-on process-
  wide registry (restarts, CRC failures, watchdog beats, iterations by
  stop-flag, …) snapshotted to JSON at exit and merged per rank;
- **streamed convergence** (:mod:`poisson_tpu.obs.stream`) — opt-in
  per-iteration residuals out of the fused ``lax.while_loop`` via
  ``jax.debug.callback`` (off by default; golden counts stay bit-exact);
- **performance attribution** (:mod:`poisson_tpu.obs.costs`) —
  compiled-executable FLOPs/bytes vs the analytic 5-point-stencil cost
  model, and achieved-vs-roofline fractions on bench records and solve
  reports;
- **profiler capture** (:mod:`poisson_tpu.obs.profile`) — fenced
  programmatic ``jax.profiler.trace`` regions on the span rails,
  env-driven like every other knob (``POISSON_TPU_PROFILE_DIR``);
- **Prometheus exposition** (:mod:`poisson_tpu.obs.export`) — the
  counter/gauge registry as scrape-able text: a textfile snapshot at
  finalize (``POISSON_TPU_PROM_OUT``) and an opt-in live ``/metrics``
  endpoint (``POISSON_TPU_METRICS_PORT``) for long multi-solve
  sessions;
- **flight recording** (:mod:`poisson_tpu.obs.flight`) — per-request
  causal span trees for the solve service on the JSONL rails
  (``trace_id``/``request_id`` attribution), latency decomposition on
  every outcome (components summing to measured wall), and SLO
  accounting (good/bad counters, a real latency histogram, multi-window
  burn rates) — rendered by ``python -m poisson_tpu trace`` and the
  forensics report.

Usage (the CLI wires this from ``--trace-dir``/``--metrics-out``/
``--stream-every``; ``bench.py`` from ``POISSON_TPU_TRACE_DIR`` etc.):

    from poisson_tpu import obs
    obs.configure(trace_dir="tm", metrics_path="m.json", stream_every=50)
    with obs.span("solve"):
        result = pcg_solve(problem, stream_every=50)
    obs.finalize()

Everything degrades to near-zero-cost no-ops when unconfigured:
``obs.span`` is a bare profiler annotation (a check while no profiler
session runs; it writes no file), ``obs.event`` drops the record,
counters still count (a locked dict add), streaming is not even traced
into the program.
"""

from __future__ import annotations

import atexit
from typing import Optional

from poisson_tpu.obs import metrics, profile, stream, trace
from poisson_tpu.obs.metrics import gauge, inc
from poisson_tpu.obs.trace import (
    TraceRecorder,
    load_events,
    merge_trace_dir,
)

_RECORDER: Optional[TraceRecorder] = None
_METRICS_PATH: Optional[str] = None
_STREAM_EVERY: int = 0
_PROM_PATH: Optional[str] = None
_HTTP_SERVER = None
_ATEXIT_REGISTERED = False


def configure(trace_dir: Optional[str] = None,
              metrics_path: Optional[str] = None,
              stream_every: int = 0,
              stream_live: bool = False,
              rank: Optional[int] = None,
              profile_dir: Optional[str] = None,
              prom_path: Optional[str] = None,
              metrics_port: Optional[int] = None) -> TraceRecorder:
    """Install the process-wide telemetry configuration.

    ``trace_dir``: spans/events land in ``trace-rank{R}.trace.json`` +
    ``events-rank{R}.jsonl`` there (plus ``metrics-rank{R}.json`` and
    ``stream-rank{R}.jsonl`` at finalize). ``metrics_path``: additional
    single-file counters snapshot. ``stream_every``: installs a
    :class:`~poisson_tpu.obs.stream.StreamSink`; the value must ALSO be
    passed to the solver (it is a static compile flag — ``configure``
    only sets up the host side). ``profile_dir``: enables
    :func:`poisson_tpu.obs.profile.capture` regions. ``prom_path``:
    Prometheus textfile snapshot written at finalize. ``metrics_port``:
    serve a live ``GET /metrics`` endpoint on 127.0.0.1:port for the
    lifetime of the configuration (0 = OS-assigned; the bound port lands
    on the ``export.http_port`` gauge). Finalization runs at interpreter
    exit; call :func:`finalize` earlier for deterministic artifact
    timing.
    """
    global _RECORDER, _METRICS_PATH, _STREAM_EVERY, _ATEXIT_REGISTERED
    global _PROM_PATH, _HTTP_SERVER
    shutdown()
    _RECORDER = TraceRecorder(trace_dir=trace_dir, rank=rank)
    _METRICS_PATH = metrics_path
    _STREAM_EVERY = max(0, int(stream_every))
    _PROM_PATH = prom_path
    profile.configure(profile_dir)
    if metrics_port is not None:
        from poisson_tpu.obs import export

        try:
            _HTTP_SERVER = export.start_http_server(metrics_port)
        except Exception as e:
            # Taken port, out-of-range port (OverflowError), anything —
            # a broken metrics endpoint must not kill the solve; say so
            # and move on.
            import sys

            print(f"obs: /metrics endpoint unavailable on port "
                  f"{metrics_port}: {e}", file=sys.stderr)
            _HTTP_SERVER = None
    if _STREAM_EVERY > 0:
        path = None
        if trace_dir:
            import os

            path = os.path.join(trace_dir,
                                f"stream-rank{_RECORDER.rank}.jsonl")
        stream.set_sink(stream.StreamSink(path=path, live=stream_live))
    if not _ATEXIT_REGISTERED:
        atexit.register(finalize)
        _ATEXIT_REGISTERED = True
    return _RECORDER


def configure_from_env() -> Optional[TraceRecorder]:
    """Configure from ``POISSON_TPU_TRACE_DIR`` / ``POISSON_TPU_METRICS_OUT``
    / ``POISSON_TPU_STREAM_EVERY`` / ``POISSON_TPU_PROFILE_DIR`` /
    ``POISSON_TPU_PROM_OUT`` / ``POISSON_TPU_METRICS_PORT`` — the
    env-driven path for harnesses (``bench.py``) whose argv is already
    spoken for. No-op (returns None) when none of the variables are
    set."""
    import os

    trace_dir = os.environ.get("POISSON_TPU_TRACE_DIR") or None
    metrics_path = os.environ.get("POISSON_TPU_METRICS_OUT") or None
    profile_dir = os.environ.get("POISSON_TPU_PROFILE_DIR") or None
    prom_path = os.environ.get("POISSON_TPU_PROM_OUT") or None
    try:
        stream_every = int(os.environ.get("POISSON_TPU_STREAM_EVERY", "0"))
    except ValueError:
        stream_every = 0
    metrics_port: Optional[int] = None
    try:
        raw_port = os.environ.get("POISSON_TPU_METRICS_PORT")
        if raw_port:
            metrics_port = int(raw_port)
    except ValueError:
        metrics_port = None
    if not (trace_dir or metrics_path or stream_every > 0 or profile_dir
            or prom_path or metrics_port is not None):
        return None
    return configure(trace_dir=trace_dir, metrics_path=metrics_path,
                     stream_every=stream_every, profile_dir=profile_dir,
                     prom_path=prom_path, metrics_port=metrics_port)


def recorder() -> Optional[TraceRecorder]:
    """The active recorder, or None when telemetry is unconfigured."""
    return _RECORDER


def stream_every() -> int:
    """The configured streaming stride (0 = off) — what the CLI passes
    into the solver entry points."""
    return _STREAM_EVERY


def span(name: str, **args):
    """A span on the profiler's clock (``jax.profiler.TraceAnnotation``
    of ``name``; ``args`` stay off it), also recorded to the active
    recorder when telemetry is configured. Call sites never guard. Spans
    belong in host code, never inside a jitted function."""
    if _RECORDER is not None:
        return _RECORDER.span(name, **args)
    return trace.annotation(name)


def event(name: str, **fields) -> None:
    """An instant event on the active recorder (dropped when off)."""
    if _RECORDER is not None:
        _RECORDER.event(name, **fields)


def recent_events() -> list:
    """Last N events (for stall diagnostics); [] when unconfigured."""
    if _RECORDER is not None:
        return _RECORDER.recent_events()
    return []


def finalize() -> None:
    """Flush every artifact: the Chrome trace, the metrics snapshot(s),
    the stream sink. Idempotent; safe with no configuration."""
    import os

    stream.drain()
    sink = stream.get_sink()
    if sink is not None:
        sink.finish()
    rec = _RECORDER
    if rec is not None:
        rec.flush()
        if rec.trace_dir:
            metrics.write_snapshot(
                os.path.join(rec.trace_dir,
                             f"metrics-rank{rec.rank}.json"),
                rank=rec.rank,
            )
    if _METRICS_PATH:
        metrics.write_snapshot(_METRICS_PATH,
                               rank=rec.rank if rec else None)
    if _PROM_PATH:
        from poisson_tpu.obs import export

        export.write_textfile(_PROM_PATH)


def shutdown() -> None:
    """Finalize and tear down the configuration (tests; back-to-back
    runs in one process)."""
    global _RECORDER, _METRICS_PATH, _STREAM_EVERY, _PROM_PATH
    global _HTTP_SERVER
    if (_RECORDER is not None or _METRICS_PATH or _PROM_PATH
            or stream.get_sink()):
        finalize()
    rec, _RECORDER = _RECORDER, None
    if rec is not None:
        rec.close()
    stream.set_sink(None)
    if _HTTP_SERVER is not None:
        from poisson_tpu.obs import export

        export.stop_http_server(_HTTP_SERVER)
        _HTTP_SERVER = None
    profile.configure(None)
    _METRICS_PATH = None
    _STREAM_EVERY = 0
    _PROM_PATH = None
