"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: per device, the union of busy intervals, the device time of each
operation, and the time of the collective operations (all from the
device's ``XLA Ops`` line, leaving out the control flow that encloses
other ops); and the idle gaps of the devices, each attributed to the
harness's own host span that covered it (``perf.generate``,
``perf.dispatch``, ``perf.fetch``, ``perf.wait``).

Everything is clipped to the measured window, which the harness marks with
the host span ``perf.window``. The arithmetic works on plain
``(name, start_ns, end_ns)`` tuples so that the tests can check it on
intervals made by hand as well as on a trace recorded on the chip.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "perf.window"
HOST_SPAN_PREFIX = "perf."
UNATTRIBUTED = "host:other"
# The device line that holds one event per executed operation.
OPS_LINE = "XLA Ops"
DEVICE_PLANE_PREFIX = "/device:TPU:"
# On the TPU an op event's name is its HLO instruction,
# "%<name> = <shape> <opcode>(<operands>), ...": the opcode, not the text,
# says what it is (an operand may well be named all-reduce).
_HLO = re.compile(r"^%(\S+) = .*?\s([a-z][a-z0-9-]*)\(")
COLLECTIVE_OPCODES = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|send|recv)(-start|-done)?$")
# Control flow whose event encloses the ops it runs: counting it as busy
# would hide every gap inside a loop.
CONTAINER_OPCODES = {"while", "conditional", "call"}

Interval = Tuple[float, float]
Event = Tuple[str, float, float]  # (name, start_ns, end_ns)


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as sorted, disjoint intervals: overlapping or
    nested operations are counted once."""
    out: List[Interval] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of merged ``busy`` intervals within [lo, hi]."""
    out, cursor = [], lo
    for s, e in busy:
        if s > cursor:
            out.append((cursor, min(s, hi)))
        cursor = max(cursor, e)
        if cursor >= hi:
            break
    if cursor < hi:
        out.append((cursor, hi))
    return [(s, e) for s, e in out if e > s]


def op_key(event_name: str) -> tuple:
    """(short name, opcode) of an op event: ("body.6", "custom-call") from
    an HLO instruction, (name, name) from anything else."""
    m = _HLO.match(event_name)
    if m is None:
        return event_name, event_name
    return f"{m.group(1)} {m.group(2)}", m.group(2)


def is_collective(opcode: str) -> bool:
    return COLLECTIVE_OPCODES.match(opcode) is not None


def attribute(idle: Sequence[Interval], spans: Sequence[Event]
              ) -> Dict[str, float]:
    """Split each idle interval among the host spans that cover it; time
    no span covers goes to ``host:other``. Returns ns per span name."""
    spans = sorted(spans, key=lambda s: s[1])
    out: Dict[str, float] = defaultdict(float)
    for g0, g1 in idle:
        covered = 0.0
        for name, s0, s1 in spans:
            if s0 >= g1:
                break
            overlap = min(g1, s1) - max(g0, s0)
            if overlap > 0:
                out[name] += overlap
                covered += overlap
        if g1 - g0 - covered > 0:
            out[UNATTRIBUTED] += g1 - g0 - covered
    return dict(out)


@dataclasses.dataclass
class Device:
    name: str
    busy_ns: float
    op_ns: Dict[str, float]
    collective_ns: float
    idle: List[Interval]


@dataclasses.dataclass
class Summary:
    window: Interval
    devices: List[Device]
    host_spans: List[Event]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> List[float]:
        return [d.busy_ns * 1e-9 for d in self.devices]

    def mean_busy_s(self) -> float:
        return sum(self.busy_s()) / len(self.devices)

    def collective_s(self) -> List[float]:
        return [d.collective_ns * 1e-9 for d in self.devices]

    def device_ops(self, top: int = 10) -> List[list]:
        """The operations that took most device time, mean over devices."""
        acc: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for name, ns in d.op_ns.items():
                acc[name] += ns / len(self.devices)
        ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns * 1e-9] for name, ns in ranked]

    def idle_gaps(self, top: int = 10) -> List[list]:
        """Device idle time by what the host was doing, mean over
        devices."""
        acc: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for name, ns in attribute(d.idle, self.host_spans).items():
                acc[name] += ns / len(self.devices)
        ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns * 1e-9] for name, ns in ranked]


def summarize(device_events: Dict[str, Sequence[Event]],
              host_spans: Sequence[Event], window: Interval) -> Summary:
    """The summary of per-device operation events and host spans, all
    clipped to ``window``."""
    lo, hi = window
    devices = []
    for name in sorted(device_events):
        events = []
        for n, s, e in device_events[name]:
            key, opcode = op_key(n)
            if opcode not in CONTAINER_OPCODES and min(e, hi) > max(s, lo):
                events.append((key, opcode, max(s, lo), min(e, hi)))
        busy = merge((s, e) for _, _, s, e in events)
        op_ns: Dict[str, float] = defaultdict(float)
        for key, _, s, e in events:
            op_ns[key] += e - s
        coll = merge((s, e) for _, opcode, s, e in events
                     if is_collective(opcode))
        devices.append(Device(name=name, busy_ns=total(busy),
                              op_ns=dict(op_ns), collective_ns=total(coll),
                              idle=gaps(busy, lo, hi)))
    spans = [(n, s, e) for n, s, e in host_spans
             if n != WINDOW_SPAN and min(e, hi) > max(s, lo)]
    return Summary(window=window, devices=devices, host_spans=spans)


def read_xspace(path: str, devices: Optional[Sequence[int]] = None):
    """(device_events, host_spans, window) from one ``.xplane.pb``:
    the ``XLA Ops`` events of each TPU plane (only the planes of
    ``devices`` when given), the harness's ``perf.*`` host spans, and the
    window its ``perf.window`` span marks."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_events: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            index = int(plane.name[len(DEVICE_PLANE_PREFIX):])
            if devices is not None and index not in devices:
                continue
            events = device_events.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                events.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                              for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith(HOST_SPAN_PREFIX))
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{path}: {len(windows)} {WINDOW_SPAN} spans, "
                         "expected exactly one")
    return device_events, host, windows[0]


def find_xspace(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if len(found) != 1:
        raise ValueError(f"{trace_dir}: {len(found)} .xplane.pb files, "
                         "expected exactly one")
    return found[0]


def load(trace_dir: str, devices: Optional[Sequence[int]] = None) -> Summary:
    events, host, window = read_xspace(find_xspace(trace_dir), devices)
    return summarize(events, host, window)
