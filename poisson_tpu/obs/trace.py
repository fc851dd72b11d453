"""Spans: the unified timeline pillar of the telemetry subsystem.

The reference instrumented stage4 by hand — five ``MPI_Wtime``
accumulators and a rank-0 table (``poisson_mpi_cuda_f.cu:956-980``).
This framework's equivalents were scattered across four sinks with four
schemas (PhaseTimer dicts, watchdog heartbeat JSON, restart history
inside ``DivergenceError``, bench session.jsonl) — no way to reconstruct
what a long solve actually did. This module replaces them with ONE
nestable span API that emits two views of the same record:

- ``trace-rank{R}.trace.json`` — Chrome/Perfetto trace-event JSON
  (``{"traceEvents": [{"ph": "X", "ts": …, "dur": …, "name": …,
  "pid": rank, "tid": thread}]}``): open it at https://ui.perfetto.dev
  or ``chrome://tracing``. ``ts`` is wall-clock microseconds, so traces
  from different hosts of a multihost run merge into one timeline
  (:func:`merge_trace_dir`).
- ``events-rank{R}.jsonl`` — a structured event log, one JSON object per
  line, appended and flushed as events happen, so a post-mortem of a
  hung or killed solve has evidence on disk up to the last event.
  Every record carries both wall
  (``at_unix``) and monotonic (``at_mono``) timestamps: wall for
  cross-host alignment, monotonic for stall arithmetic a clock jump
  cannot fake.

Every span is also a ``jax.profiler.TraceAnnotation`` of its name, so
that under a profiler session it lands in the ``.xplane.pb`` on the
device trace's clock, nested under the caller's annotations on the same
thread. With no session running an annotation costs a check. Span exit
does not fence device work: a span marks what the host did, and the
profiler's device lines say when the device did it. The recorder holds
no JAX state and all jax use is lazy: importing this module (e.g. from
``bench.py`` before its backend probe) must not initialize a backend.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Optional

# JSONL event-log schema version. v1 (PR 2–6) laid caller fields flat
# next to the reserved keys (at_unix/at_mono/rank/kind/name) — a caller
# field that collided with a reserved key was silently dropped, and
# there was no place for structured per-request attribution. v2 carries
# every caller field under an ``attrs`` block (so ``trace_id``/
# ``request_id`` flow through verbatim, collisions included) while the
# reserved envelope stays flat; :func:`load_events` normalizes both
# generations to one readable shape, so PR 2–6 artifacts keep loading.
EVENTS_SCHEMA = 2


_TRACE_ANNOTATION = None


def annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)``, imported on first use (a
    recorder must be usable before — or entirely without — a backend).
    No per-call arguments: formatting them would cost time on the hot
    path, and the profiler would fold them into the name."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _TRACE_ANNOTATION = TraceAnnotation
    return _TRACE_ANNOTATION(name)


def default_rank() -> int:
    """Process index for event attribution, without initializing a
    backend: the distributed runtime's index when one formed, else the
    JAX_PROCESS_INDEX env (pod launchers set it), else 0."""
    try:
        import jax

        from poisson_tpu.parallel import multihost

        if multihost._initialized:
            return jax.process_index()
    except Exception:
        pass
    try:
        return int(os.environ.get("JAX_PROCESS_INDEX", "0"))
    except ValueError:
        return 0


class _Span:
    """Context manager for one span; created via :meth:`TraceRecorder.span`."""

    __slots__ = ("_rec", "name", "args", "_annotation", "_t0", "_wall0",
                 "seconds")

    def __init__(self, rec: "TraceRecorder", name: str, args):
        self._rec = rec
        self.name = name
        self.args = args
        self.seconds: Optional[float] = None

    def __enter__(self) -> "_Span":
        self._annotation = annotation(self.name)
        self._annotation.__enter__()
        self._rec._push(self.name)
        self._t0 = time.perf_counter()
        self._wall0 = time.time()
        self._rec._emit_jsonl("span_begin", self.name, self.args)
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        path = self._rec._pop()
        self._rec._add_trace_event({
            "ph": "X",
            "name": self.name,
            "cat": "span",
            "ts": self._wall0 * 1e6,
            "dur": self.seconds * 1e6,
            "pid": self._rec.rank,
            "tid": threading.get_ident() % 2**31,
            "args": dict(self.args),
        })
        fields = dict(self.args)
        fields["seconds"] = round(self.seconds, 6)
        fields["span_path"] = path
        if exc and exc[0] is not None:
            fields["error"] = getattr(exc[0], "__name__", str(exc[0]))
        self._rec._emit_jsonl("span_end", self.name, fields)


class TraceRecorder:
    """One process's telemetry recorder: spans, instant events, a recent-
    events ring (for watchdog stall diagnostics), and the two output
    files described in the module docstring.

    ``trace_dir=None`` records in memory only (the ring and the trace
    event list still work — useful for tests and for the watchdog's
    recent-events capture without any disk configuration).
    """

    def __init__(self, trace_dir: Optional[str] = None,
                 rank: Optional[int] = None, recent: int = 64):
        self.trace_dir = trace_dir
        self.rank = default_rank() if rank is None else int(rank)
        self._trace_events: list[dict] = []
        self._recent = collections.deque(maxlen=recent)
        self._lock = threading.Lock()
        self._stack = threading.local()
        self._jsonl = None
        self._closed = False
        if trace_dir:
            os.makedirs(trace_dir, exist_ok=True)

    # -- span nesting (per-thread) ------------------------------------

    def _push(self, name: str) -> None:
        stack = getattr(self._stack, "names", None)
        if stack is None:
            stack = self._stack.names = []
        stack.append(name)

    def _pop(self) -> str:
        stack = getattr(self._stack, "names", [])
        path = "/".join(stack)
        if stack:
            stack.pop()
        return path

    # -- public API ----------------------------------------------------

    def span(self, name: str, **args) -> _Span:
        """Nestable timed region of host time, recorded here and in the
        profiler's trace (see the module docstring)."""
        return _Span(self, name, args)

    def event(self, name: str, **fields) -> None:
        """Instant event: a point on the timeline plus a JSONL record."""
        self._add_trace_event({
            "ph": "i",
            "name": name,
            "cat": "event",
            "s": "p",
            "ts": time.time() * 1e6,
            "pid": self.rank,
            "tid": threading.get_ident() % 2**31,
            "args": dict(fields),
        })
        self._emit_jsonl("event", name, fields)

    def recent_events(self) -> list[dict]:
        """Last N JSONL records (newest last) — the watchdog embeds these
        in its stall diagnostics file."""
        with self._lock:
            return [dict(e) for e in self._recent]

    @property
    def events_path(self) -> Optional[str]:
        if not self.trace_dir:
            return None
        return os.path.join(self.trace_dir, f"events-rank{self.rank}.jsonl")

    @property
    def trace_path(self) -> Optional[str]:
        if not self.trace_dir:
            return None
        return os.path.join(self.trace_dir,
                            f"trace-rank{self.rank}.trace.json")

    def trace_events(self) -> list[dict]:
        with self._lock:
            return [dict(e) for e in self._trace_events]

    def flush(self) -> None:
        """Write the Chrome trace file (atomic replace) with everything
        recorded so far; the JSONL log is already on disk."""
        path = self.trace_path
        if not path:
            return
        with self._lock:
            payload = {
                "traceEvents": list(self._trace_events),
                "displayTimeUnit": "ms",
                "otherData": {"rank": self.rank, "pid": os.getpid(),
                              "tool": "poisson_tpu.obs"},
            }
        tmp = f"{path}.{os.getpid()}.tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, path)
        except OSError:
            # Telemetry must never take the solve down with it.
            try:
                if os.path.exists(tmp):
                    os.remove(tmp)
            except OSError:
                pass

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.flush()
        with self._lock:
            if self._jsonl is not None:
                try:
                    self._jsonl.close()
                except OSError:
                    pass
                self._jsonl = None

    # -- internals -----------------------------------------------------

    def _add_trace_event(self, ev: dict) -> None:
        with self._lock:
            if not self._closed:
                self._trace_events.append(ev)

    def _emit_jsonl(self, kind: str, name: str, fields: dict) -> None:
        rec = {
            "schema": EVENTS_SCHEMA,
            "at_unix": time.time(),
            "at_mono": time.monotonic(),
            "rank": self.rank,
            "kind": kind,
            "name": name,
            # v2: caller fields ride the attrs block verbatim — a field
            # named "kind" or "rank" is preserved instead of silently
            # dropped, and request attribution (trace_id/request_id)
            # has a structured home.
            "attrs": dict(fields),
        }
        with self._lock:
            if self._closed:
                return
            # The ring holds the normalized shape (attrs also merged
            # flat where they don't collide) so existing readers of
            # recent_events() — watchdog stall diagnostics — keep
            # working unchanged.
            self._recent.append(normalize_event(rec))
            path = self.events_path
            if path is None:
                return
            try:
                if self._jsonl is None:
                    self._jsonl = open(path, "a")
                self._jsonl.write(json.dumps(rec, default=str) + "\n")
                self._jsonl.flush()
            except (OSError, ValueError, TypeError):
                pass


# -- multihost/multi-rank merging --------------------------------------


def normalize_event(rec: dict) -> dict:
    """One JSONL record in the canonical readable shape, whichever
    schema generation wrote it: v2's ``attrs`` are merged flat where
    they do not collide with the reserved envelope (so v1-era readers
    like ``summarize_session`` keep one access path) AND kept intact
    under ``attrs`` (so a caller field that shadowed a reserved key —
    the v1 silent-drop bug — is still reachable). v1 records pass
    through unchanged."""
    attrs = rec.get("attrs")
    if not isinstance(attrs, dict):
        return rec
    out = {k: v for k, v in attrs.items() if k not in rec}
    out.update(rec)
    out["attrs"] = attrs
    return out


def load_events(trace_dir: str) -> list[dict]:
    """Every rank's JSONL records under ``trace_dir``, normalized
    (:func:`normalize_event` — v1 and v2 lines both load), merged and
    sorted by wall time (the cross-host ordering; per-rank order is
    preserved for ties)."""
    records = []
    for fname in sorted(os.listdir(trace_dir)):
        if not (fname.startswith("events-rank") and fname.endswith(".jsonl")):
            continue
        with open(os.path.join(trace_dir, fname)) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    records.append(normalize_event(json.loads(line)))
                except ValueError:
                    continue        # torn tail line of a killed process
    records.sort(key=lambda r: r.get("at_unix", 0.0))
    return records


def merge_trace_dir(trace_dir: str,
                    out_path: Optional[str] = None) -> dict:
    """Merge every rank's Chrome trace under ``trace_dir`` into one
    trace document (ranks stay separate rows via their ``pid``).

    Every event kind is preserved — complete spans (``ph: X``), instant
    events (``ph: i``), and anything a future recorder adds — with the
    per-kind tally recorded in ``otherData.event_kinds`` so a merge
    that lost a kind is visible, not silent. A rank file that fails to
    parse (torn write of a killed process) is skipped audibly via
    ``otherData.skipped`` instead of sinking the whole merge. Writes
    ``trace-merged.trace.json`` when ``out_path`` is not given."""
    merged: list[dict] = []
    ranks = []
    skipped = []
    for fname in sorted(os.listdir(trace_dir)):
        if not (fname.startswith("trace-rank")
                and fname.endswith(".trace.json")):
            continue
        try:
            with open(os.path.join(trace_dir, fname)) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            skipped.append({"file": fname, "error": str(e)[:200]})
            continue
        merged.extend(doc.get("traceEvents", []))
        ranks.append(doc.get("otherData", {}).get("rank"))
    merged.sort(key=lambda e: e.get("ts", 0.0))
    kinds: dict = {}
    for ev in merged:
        ph = str(ev.get("ph", "?"))
        kinds[ph] = kinds.get(ph, 0) + 1
    doc = {"traceEvents": merged, "displayTimeUnit": "ms",
           "otherData": {"ranks": ranks, "tool": "poisson_tpu.obs",
                         "event_kinds": kinds, "skipped": skipped}}
    if out_path is None:
        out_path = os.path.join(trace_dir, "trace-merged.trace.json")
    with open(out_path, "w") as f:
        json.dump(doc, f)
    return doc
