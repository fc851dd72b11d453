"""The program's own spans and kernel names in a run's profiler trace.

The program marks the layer boundaries of its solve entries with host
spans (``poisson_tpu.obs.span``, a profiler annotation): a span named for
the entry, with the children ``<entry>.prepare``, ``<entry>.launch`` and
``<entry>.finish``. Its Pallas kernels carry a stable name in their
custom call's ``kernel_metadata={"kernel":"<name>"}``, which a TPU op
event carries in its name (the HLO instruction text). This module reads
both from the ``.xplane.pb`` that ``perf/run.py`` reduced, once per run,
clipped to the harness's ``perf.window`` span, and finds kernels by that
name, never by HLO instruction name.

A trace of a program without these spans or names reads as empty: the
metrics built on them then report nothing.

    python -m perf.spans    # the last traced run: kernels, idle by phase
"""

from __future__ import annotations

import dataclasses
import json
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence

from perf import trace
from perf.trace import Event, Interval

ENTRIES = ("pallas_cg_solve", "pallas_cg_solve_sharded", "solve_batched")
PHASES = ("prepare", "launch", "finish")
SPAN_NAMES = frozenset(ENTRIES) | {f"{e}.{p}" for e in ENTRIES
                                   for p in PHASES}
# The name in a kernel's custom call (its JSON may span lines).
_KERNEL = re.compile(r'kernel_metadata=\{\s*"kernel"\s*:\s*"([^"]+)"')


def kernel_of(event_name: str) -> Optional[str]:
    """The stable name of the Pallas kernel an op event ran, or None:
    only a custom call's own ``kernel_metadata`` counts (the instructions
    that take its results apart may carry the attribute too)."""
    # A substring test first: a trace holds millions of op events.
    m = _KERNEL.search(event_name) if '"kernel"' in event_name else None
    if m is None or trace.op_key(event_name)[1] != "custom-call":
        return None
    return m.group(1)


@dataclasses.dataclass
class Spans:
    window: Interval
    host: List[Event]                  # the program's spans
    kernels: Dict[str, List[Event]]    # device plane -> (kernel, s, e)

    def intervals(self, phase: str, entries: Sequence[str] = ENTRIES
                  ) -> List[Interval]:
        """Where the host was in ``phase`` of any of ``entries``, as
        disjoint intervals."""
        names = {f"{e}.{phase}" for e in entries}
        return trace.merge((s, e) for n, s, e in self.host if n in names)

    def kernel_ns(self, device: str) -> float:
        return sum(e - s for _, s, e in self.kernels.get(device, ()))

    def kernel_ns_by_name(self, device: str) -> Dict[str, float]:
        out: Dict[str, float] = defaultdict(float)
        for name, s, e in self.kernels.get(device, ()):
            out[name] += e - s
        return dict(out)


def _clip(events, lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if min(e, hi) > max(s, lo)]


def read(path: str, devices: Optional[Sequence[int]] = None) -> Spans:
    """The program's spans and named kernels in one ``.xplane.pb``
    (only the TPU planes of ``devices`` when given), clipped to the
    window its ``perf.window`` span marks."""
    from jax.profiler import ProfileData

    kernels: Dict[str, List[Event]] = {}
    host: List[Event] = []
    windows: List[Interval] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            index = int(plane.name[len(trace.DEVICE_PLANE_PREFIX):])
            if devices is not None and index not in devices:
                continue
            found = kernels.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != trace.OPS_LINE:
                    continue
                for e in line.events:
                    name = kernel_of(e.name)
                    if name is not None:
                        found.append((name, e.start_ns,
                                      e.start_ns + e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if e.name in SPAN_NAMES:
                        host.append(span)
                    elif e.name == trace.WINDOW_SPAN:
                        windows.append(span[1:])
    if len(windows) != 1:
        raise ValueError(f"{path}: {len(windows)} {trace.WINDOW_SPAN} "
                         "spans, expected exactly one")
    lo, hi = windows[0]
    return Spans(window=(lo, hi), host=_clip(host, lo, hi),
                 kernels={d: _clip(k, lo, hi) for d, k in kernels.items()})


def load(run) -> Optional[Spans]:
    """The spans of ``run``'s trace (read once, then kept on the run), or
    None where the run has no device trace."""
    if run.trace is None or not run.trace.devices:
        return None
    if getattr(run, "_program_spans", None) is None:
        from perf import run as harness

        path = trace.find_xspace(str(harness.TRACE_DIR))
        run._program_spans = read(path, [d.id for d in run.devices])
    return run._program_spans


def idle_under(summary: trace.Summary, intervals: Sequence[Interval]
               ) -> List[float]:
    """Per device, the idle ns that ``intervals`` cover. They are
    disjoint, so no idle nanosecond counts twice."""
    spans = [("covered", s, e) for s, e in trace.merge(intervals)]
    return [trace.attribute(d.idle, spans).get("covered", 0.0)
            for d in summary.devices]


def prepare_idle_pct(run, entries: Sequence[str]) -> Optional[float]:
    """Device idle time under the ``prepare`` spans of ``entries``, mean
    over the cell's chips, as a share of the traced window; None where
    the trace holds no such span."""
    spans = load(run)
    if spans is None:
        return None
    prepare = spans.intervals("prepare", entries)
    if not prepare:
        return None
    idle = idle_under(run.trace, prepare)
    lo, hi = run.trace.window
    return 100.0 * sum(idle) / len(idle) / (hi - lo)


def report(summary: trace.Summary, spans: Spans) -> dict:
    """Seconds, mean over devices: each named kernel, every custom call,
    and the device idle under each phase and under ``perf.dispatch``."""
    n = len(summary.devices) or 1
    kernels: Dict[str, float] = defaultdict(float)
    custom_calls = 0.0
    for d in summary.devices:
        for name, ns in spans.kernel_ns_by_name(d.name).items():
            kernels[name] += ns * 1e-9 / n
        custom_calls += sum(ns for key, ns in d.op_ns.items()
                            if key.endswith(" custom-call")) * 1e-9 / n
    idle = {p: sum(idle_under(summary, spans.intervals(p))) * 1e-9 / n
            for p in PHASES}
    dispatch = [(s, e) for name, s, e in summary.host_spans
                if name == "perf.dispatch"]
    idle["perf.dispatch"] = sum(idle_under(summary, dispatch)) * 1e-9 / n
    return {"window_s": summary.window_s, "kernels_s": dict(kernels),
            "custom_call_s": custom_calls, "idle_s": idle}


def kernel_texts(path: str) -> Dict[str, str]:
    """One op event's text per named kernel: its operands' and results'
    layouts say which memory space each lives in (``S(1)``: VMEM)."""
    from jax.profiler import ProfileData

    out: Dict[str, str] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                for e in line.events:
                    name = kernel_of(e.name)
                    if name is not None:
                        out.setdefault(name, e.name)
    return out


def main() -> int:
    from perf import run as harness

    path = trace.find_xspace(str(harness.TRACE_DIR))
    found = report(trace.load(str(harness.TRACE_DIR)), read(path))
    found["kernel_texts"] = kernel_texts(path)
    print(json.dumps(found))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
