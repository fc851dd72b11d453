"""The multigrid solve's names in a run's profiler trace.

The program tags every op of V-cycle level l with the frontend attribute
``mg_level="<l>"`` (``poisson_tpu/mg/cycle.py``; the coarsest solve carries
the last level's), which a TPU op event carries in its name, the HLO
instruction text. Its single-solve entry runs under the host spans
``pcg_solve`` and ``pcg_solve.{prepare,launch,finish}``. This module reads
both from the ``.xplane.pb`` that ``perf/run.py`` reduced, clipped to the
harness's ``perf.window`` span, and reduces them to:

- per device, the union of the intervals of the ops of each level;
- the intervals of each ``pcg_solve`` phase.

A trace of a program without these tags or spans reads as empty, and the
metrics built on it then report nothing.

    python -m perf.mg_trace    # the last traced run: time by level, idle by phase
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Sequence

from perf import spans, trace
from perf.trace import Event, Interval

ENTRY = "pcg_solve"
PHASES = ("prepare", "launch", "finish")
SPAN_NAMES = frozenset({ENTRY} | {f"{ENTRY}.{p}" for p in PHASES})
_LEVEL = re.compile(r'mg_level="(\d+)"')


def level_of(event_name: str) -> Optional[int]:
    """The V-cycle level an op event ran for, or None (untagged: the CG
    recurrence, or a program without the tags)."""
    # A substring test first: a trace holds many thousands of op events.
    m = _LEVEL.search(event_name) if "mg_level" in event_name else None
    return None if m is None else int(m.group(1))


@dataclasses.dataclass
class MGTrace:
    window: Interval
    host: List[Event]                       # the pcg_solve spans
    levels: Dict[str, Dict[int, List[Interval]]]   # device -> level -> busy

    def phase(self, name: str) -> List[Interval]:
        """Where the host was in ``pcg_solve.<name>``, disjoint."""
        return trace.merge((s, e) for n, s, e in self.host
                           if n == f"{ENTRY}.{name}")

    def level_ns(self, device: str, lo: int = 0,
                 hi: Optional[int] = None) -> float:
        """Device time of the ops of levels lo..hi (all from lo when hi is
        None), each nanosecond counted once."""
        found = self.levels.get(device, {})
        return trace.total(trace.merge(
            iv for lvl, ivs in found.items()
            if lvl >= lo and (hi is None or lvl <= hi) for iv in ivs))

    def tagged(self) -> bool:
        return any(self.levels.values())


def summarize(device_events: Dict[str, Sequence[Event]],
              host_spans: Sequence[Event], window: Interval) -> MGTrace:
    """The reduction of op events and host spans, clipped to ``window``;
    control flow that encloses other ops is left out, as in
    ``perf/trace.py``."""
    lo, hi = window
    levels: Dict[str, Dict[int, List[Interval]]] = {}
    for device, events in device_events.items():
        by_level: Dict[int, List[Interval]] = {}
        for name, s, e in events:
            lvl = level_of(name)
            if (lvl is None or min(e, hi) <= max(s, lo)
                    or trace.op_key(name)[1] in trace.CONTAINER_OPCODES):
                continue
            by_level.setdefault(lvl, []).append((max(s, lo), min(e, hi)))
        levels[device] = {k: trace.merge(v) for k, v in by_level.items()}
    host = [(n, max(s, lo), min(e, hi)) for n, s, e in host_spans
            if n in SPAN_NAMES and min(e, hi) > max(s, lo)]
    return MGTrace(window=window, host=host, levels=levels)


def read(path: str, devices: Optional[Sequence[int]] = None) -> MGTrace:
    """The tagged op events and ``pcg_solve`` spans of one ``.xplane.pb``
    (only the TPU planes of ``devices`` when given)."""
    from jax.profiler import ProfileData

    device_events: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            index = int(plane.name[len(trace.DEVICE_PLANE_PREFIX):])
            if devices is not None and index not in devices:
                continue
            found = device_events.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    found.extend((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                                 for e in line.events
                                 if "mg_level" in e.name)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPAN_NAMES or e.name == trace.WINDOW_SPAN:
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    windows = [(s, e) for n, s, e in host if n == trace.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{path}: {len(windows)} {trace.WINDOW_SPAN} "
                         "spans, expected exactly one")
    return summarize(device_events, host, windows[0])


def load(run) -> Optional[MGTrace]:
    """The MG names of ``run``'s trace (read once, then kept on the run),
    or None where the run has no device trace."""
    if run.trace is None or not run.trace.devices:
        return None
    if getattr(run, "_mg_trace", None) is None:
        from perf import run as harness

        path = trace.find_xspace(str(harness.TRACE_DIR))
        run._mg_trace = read(path, [d.id for d in run.devices])
    return run._mg_trace


def coarse_from(run) -> int:
    """The first level at or below the configuration's ``coarse_below``
    size: from there down the cycle is bound by launches and latency."""
    from perf import work_mg

    p, (cm, cn) = run.config["problem"], run.config["mg"]["coarse_below"]
    dims = work_mg.levels(p["M"], p["N"])
    return next((i for i, (m, n) in enumerate(dims) if m <= cm and n <= cn),
                len(dims) - 1)


def level_pct(run, coarse: bool = False) -> Optional[float]:
    """Device time of the V-cycle's ops (with ``coarse``, of the coarse
    levels' only: ``coarse_from``) ÷ device busy time, mean over the
    cell's chips; None where no op carries a tag."""
    found = load(run)
    if found is None or not found.tagged():
        return None
    lo = coarse_from(run) if coarse else 0
    shares = [found.level_ns(d.name, lo) / d.busy_ns
              for d in run.trace.devices if d.busy_ns > 0]
    return 100.0 * sum(shares) / len(shares) if shares else None


def prepare_idle_pct(run) -> Optional[float]:
    """Device idle under ``pcg_solve.prepare`` ÷ the traced window, mean
    over the cell's chips; None where the trace holds no such span."""
    found = load(run)
    if found is None:
        return None
    prepare = found.phase("prepare")
    if not prepare:
        return None
    idle = spans.idle_under(run.trace, prepare)
    lo, hi = run.trace.window
    return 100.0 * sum(idle) / len(idle) / (hi - lo)


def report(summary: trace.Summary, found: MGTrace) -> dict:
    """Seconds, mean over devices: the device time of each level, the
    busy time no level claims (the CG recurrence), and the device idle
    under each ``pcg_solve`` phase and under ``perf.dispatch``/``fetch``."""
    n = len(summary.devices) or 1
    levels = sorted({lvl for d in found.levels.values() for lvl in d})
    by_level = {str(lvl): sum(found.level_ns(d.name, lvl, lvl)
                              for d in summary.devices) * 1e-9 / n
                for lvl in levels}
    tagged = sum(found.level_ns(d.name) for d in summary.devices) * 1e-9 / n
    idle = {p: sum(spans.idle_under(summary, found.phase(p))) * 1e-9 / n
            for p in PHASES}
    for name in ("perf.dispatch", "perf.fetch"):
        covered = [(s, e) for m, s, e in summary.host_spans if m == name]
        idle[name] = sum(spans.idle_under(summary, covered)) * 1e-9 / n
    return {"window_s": summary.window_s, "busy_s": summary.mean_busy_s(),
            "level_s": by_level, "untagged_s": summary.mean_busy_s() - tagged,
            "idle_s": idle}


def main() -> int:
    from perf import run as harness

    path = trace.find_xspace(str(harness.TRACE_DIR))
    print(json.dumps(report(trace.load(str(harness.TRACE_DIR)), read(path))))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
