"""The reduction from a profiler trace to busy time, per-operation time,
collective time and attributed idle gaps: on intervals made by hand, and
on small traces recorded on the chip (``perf/tools/record_trace.py``)."""

import pathlib

import pytest

from perf import trace

DATA = pathlib.Path(__file__).with_name("data")


def test_busy_union_counts_overlap_once():
    busy = trace.merge([(0, 10), (5, 15), (20, 30), (22, 25), (30, 31)])
    assert busy == [(0, 15), (20, 31)]
    assert trace.total(busy) == 26


def test_gaps_are_the_complement_within_the_window():
    busy = trace.merge([(10, 20), (30, 40)])
    assert trace.gaps(busy, 0, 50) == [(0, 10), (20, 30), (40, 50)]
    assert trace.gaps(busy, 12, 35) == [(20, 30)]
    assert trace.gaps([], 0, 5) == [(0, 5)]


def _hlo(name, opcode):
    return f"%{name} = f32[8,128]{{1,0:T(8,128)}} {opcode}(f32[8,128] %x)"


def test_ops_are_named_and_classified_by_opcode():
    assert trace.op_key(_hlo("body.6", "custom-call")) == (
        "body.6 custom-call", "custom-call")
    assert trace.op_key("jit_multiply(123)") == ("jit_multiply(123)",) * 2
    for opcode in ("all-reduce", "all-reduce-start", "collective-permute",
                   "collective-permute-done", "all-gather",
                   "reduce-scatter"):
        assert trace.is_collective(opcode), opcode
    for opcode in ("fusion", "custom-call", "copy", "while", "reduce"):
        assert not trace.is_collective(opcode), opcode
    # A fusion that reads an all-reduce's result is no collective.
    fused = "%fusion.2 = f32[] fusion(f32[] %all-reduce.3), kind=kLoop"
    assert not trace.is_collective(trace.op_key(fused)[1])


def test_gaps_go_to_the_host_span_that_covers_them():
    idle = [(0, 10), (20, 30), (40, 50)]
    spans = [("perf.fetch", 0, 5), ("perf.wait", 5, 12),
             ("perf.dispatch", 25, 45)]
    got = trace.attribute(idle, spans)
    assert got == {"perf.fetch": 5, "perf.wait": 5, "perf.dispatch": 10,
                   trace.UNATTRIBUTED: 10}
    assert sum(got.values()) == trace.total(idle)


def test_summary_clips_to_the_window_and_sums_per_op():
    events = {"/device:TPU:0": [
        (_hlo("while.1", "while"), 0, 60),
        (_hlo("f.1", "fusion"), 0, 10), (_hlo("f.1", "fusion"), 12, 20),
        (_hlo("ar.1", "all-reduce"), 15, 25),
        (_hlo("k.1", "custom-call"), 30, 60)]}
    spans = [("perf.window", 5, 50), ("perf.fetch", 25, 30)]
    s = trace.summarize(events, spans, (5, 50))
    (dev,) = s.devices
    # The enclosing while loop is not busy time of its own.
    assert dev.busy_ns == 5 + 13 + 20          # [5,10] [12,25] [30,50]
    assert dev.op_ns == {"f.1 fusion": 13, "ar.1 all-reduce": 10,
                         "k.1 custom-call": 20}
    assert dev.collective_ns == 10
    assert dev.idle == [(10, 12), (25, 30)]
    assert s.window_s == pytest.approx(45e-9)
    gaps = dict(s.idle_gaps())
    assert gaps["perf.fetch"] == pytest.approx(5e-9)
    assert gaps[trace.UNATTRIBUTED] == pytest.approx(2e-9)


def _raw(path, devices=None):
    """Plain per-device op events read straight from the file, with no
    use of the module's arithmetic."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    out.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
    return out


RECORDED = sorted(DATA.glob("*.xplane.pb"))


@pytest.mark.parametrize("path", RECORDED, ids=[p.stem for p in RECORDED])
def test_recorded_trace(path):
    events, host, window = trace.read_xspace(str(path))
    s = trace.summarize(events, host, window)
    raw = _raw(path)
    assert s.devices and len(s.devices) == len(raw)
    lo, hi = window
    for dev in s.devices:
        inside = []
        for n, t, d in raw[dev.name]:
            key, opcode = trace.op_key(n)
            if opcode != "while" and min(t + d, hi) > max(t, lo):
                inside.append((key, opcode, max(t, lo), min(t + d, hi)))
        # Per-op sums are the plain sums of the clipped durations.
        sums = {}
        for key, _, a, b in inside:
            sums[key] = sums.get(key, 0) + (b - a)
        assert dev.op_ns == pytest.approx(sums)
        # The busy union never exceeds the plain sum, nor the window, and
        # every instant of it lies under some operation.
        assert dev.busy_ns <= sum(sums.values()) + 1
        assert 0 < dev.busy_ns <= hi - lo
        assert dev.busy_ns + trace.total(dev.idle) == pytest.approx(hi - lo)
        # Collective time is the union of exactly the collective ops.
        coll = [(a, b) for _, op, a, b in inside if trace.is_collective(op)]
        assert dev.collective_ns == pytest.approx(
            trace.total(trace.merge(coll)))
        # Every idle nanosecond is attributed once.
        got = trace.attribute(dev.idle, s.host_spans)
        assert sum(got.values()) == pytest.approx(trace.total(dev.idle))
    # The recorder's host spans are all there, and the pauses between
    # solves show as idle time under perf.wait.
    names = {n for n, _, _ in s.host_spans}
    assert {"perf.dispatch", "perf.fetch", "perf.wait"} <= names
    assert dict(s.idle_gaps()).get("perf.wait", 0) > 0
    if "4chip" in path.stem:
        assert all(d.collective_ns > 0 for d in s.devices)
