"""The program's spans and kernel names read from a profiler trace
(``perf/spans.py``) and the readers built on them: on intervals made by
hand, and on small traces recorded on the chip with
``perf/tools/record_trace.py``."""

import pathlib
import types

import pytest

from perf import run as harness
from perf import spans, trace

DATA = pathlib.Path(__file__).with_name("data")


def _load(name):
    return harness.load_module(harness.PERF / "metrics" / f"{name}.py")


def _hlo(name, opcode, kernel=None):
    attrs = ("" if kernel is None else
             f', frontend_attributes={{kernel_metadata={{\n"kernel":"{kernel}"\n}}}}')
    return f"%{name} = f32[8,128]{{1,0}} {opcode}(f32[8,128] %x){attrs}"


def test_kernels_are_found_by_their_name_on_custom_calls_only():
    assert spans.kernel_of(_hlo("body.6", "custom-call",
                                "fused_update")) == "fused_update"
    assert spans.kernel_of(_hlo("body.6", "custom-call")) is None
    # An instruction that takes the kernel's results apart carries the
    # attribute too; it is no kernel.
    assert spans.kernel_of(_hlo("get-tuple-element.3", "get-tuple-element",
                                "fused_update")) is None


def _made(window=(0, 100)):
    """Two devices, host spans of two entries, named kernels."""
    events = {
        "/device:TPU:0": [(_hlo("k.1", "custom-call", "a"), 10, 30),
                          (_hlo("f.1", "fusion"), 30, 40),
                          (_hlo("k.2", "custom-call", "b"), 60, 90)],
        "/device:TPU:1": [(_hlo("k.1", "custom-call", "a"), 20, 30),
                          (_hlo("k.2", "custom-call", "b"), 60, 95)],
    }
    host = [("perf.window",) + window, ("perf.dispatch", 0, 12),
            ("perf.dispatch", 45, 62)]
    program = [
        ("solve_batched", 0, 12), ("solve_batched.prepare", 0, 8),
        ("solve_batched.launch", 8, 12),
        # A second entry whose prepare overlaps the first's: the idle
        # under both counts once.
        ("pallas_cg_solve.prepare", 5, 10),
        ("pallas_cg_solve.prepare", 45, 55), ("pallas_cg_solve", 45, 62),
    ]
    summary = trace.summarize(events, host, window)
    found = spans.Spans(
        window=window, host=program,
        kernels={d: [(spans.kernel_of(n), s, e) for n, s, e in evs
                     if spans.kernel_of(n)] for d, evs in events.items()})
    return summary, found


def test_prepare_idle_counts_each_idle_nanosecond_once():
    summary, found = _made()
    # Device 0 idles on [0, 10), [40, 60), [90, 100); device 1 on
    # [0, 20), [30, 60), [95, 100).
    prepare = found.intervals("prepare")
    assert prepare == [(0, 10), (45, 55)]
    assert spans.idle_under(summary, prepare) == [10 + 10, 10 + 10]
    only_batch = found.intervals("prepare", ["solve_batched"])
    assert spans.idle_under(summary, only_batch) == [8, 8]
    # Never more than the idle time itself, nor than perf.dispatch's.
    dispatch = [(s, e) for n, s, e in summary.host_spans
                if n == "perf.dispatch"]
    for under_prepare, under_dispatch, dev in zip(
            spans.idle_under(summary, prepare),
            spans.idle_under(summary, dispatch), summary.devices):
        assert under_prepare <= under_dispatch <= trace.total(dev.idle)


def _run(summary, found, records, **info):
    run = types.SimpleNamespace(trace=summary, records=records, info=info,
                                devices=[None] * len(summary.devices))
    run._program_spans = found
    return run


def test_readers_on_made_intervals():
    summary, found = _made()
    run = _run(summary, found, [{"iterations": 2}, {"iterations": 3}])
    # Kernel time per chip: 20 + 30 and 10 + 35 ns, mean 47.5 ns over
    # 5 iterations; the fusion is no named kernel.
    assert _load("kernel_us_per_iter.solve").read(run) == pytest.approx(
        47.5e-3 / 5)
    # Only the traced solves' iterations count.
    sliced = _run(summary, found, [{"iterations": 2}, {"iterations": 3},
                                   {"iterations": 10**6}], traced=2)
    assert _load("kernel_us_per_iter.solve").read(sliced) == \
        _load("kernel_us_per_iter.solve").read(run)
    assert _load("prep_idle_pct.solve").read(run) == pytest.approx(
        100 * (5 + 10) / 100)
    assert _load("prep_idle_pct.batch").read(run) == pytest.approx(8)


def test_readers_find_nothing_without_the_programs_names():
    summary, _ = _made()
    bare = spans.Spans(window=(0, 100), host=[], kernels={})
    run = _run(summary, bare, [{"iterations": 5}])
    for name in ("kernel_us_per_iter.solve", "prep_idle_pct.solve",
                 "prep_idle_pct.batch"):
        assert _load(name).read(run) is None
        no_trace = types.SimpleNamespace(trace=None, records=[], info={})
        assert _load(name).read(no_trace) is None


NAMED = sorted(p for p in DATA.glob("*.xplane.pb")
               if p.stem.startswith("spans-"))


def _raw_kernels(path):
    """Per device, the clipped durations of the op events whose text
    names a kernel, read straight from the file."""
    from jax.profiler import ProfileData

    _, _, (lo, hi) = trace.read_xspace(str(path))
    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            for e in line.events:
                if ("custom-call(" in e.name
                        and '"kernel":' in e.name.split("kernel_metadata")[-1]):
                    a = max(e.start_ns, lo)
                    b = min(e.start_ns + e.duration_ns, hi)
                    if b > a:
                        out[plane.name] = out.get(plane.name, 0) + (b - a)
    return out


@pytest.mark.parametrize("path", NAMED, ids=[p.stem for p in NAMED])
def test_recorded_named_kernels(path):
    found = spans.read(str(path))
    summary = trace.summarize(*trace.read_xspace(str(path)))
    raw = _raw_kernels(path)
    assert summary.devices and set(raw) == {d.name for d in summary.devices}
    for dev in summary.devices:
        # The named kernels' time is the plain sum of the matching events,
        # and every custom call of these solves is a named kernel.
        assert found.kernel_ns(dev.name) == pytest.approx(raw[dev.name])
        custom = sum(ns for key, ns in dev.op_ns.items()
                     if key.endswith(" custom-call"))
        assert found.kernel_ns(dev.name) == pytest.approx(custom)
        assert set(found.kernel_ns_by_name(dev.name)) == {
            "direction_and_stencil", "fused_update"}


@pytest.mark.parametrize("path", NAMED, ids=[p.stem for p in NAMED])
def test_recorded_entry_spans(path):
    found = spans.read(str(path))
    summary = trace.summarize(*trace.read_xspace(str(path)))
    entry = ("pallas_cg_solve_sharded" if "4chip" in path.stem
             else "pallas_cg_solve")
    parents = sorted(s for s in found.host if s[0] == entry)
    assert parents, [s[0] for s in found.host]
    phases = ("prepare", "launch") + (("finish",) if entry ==
                                       "pallas_cg_solve" else ())
    for _, p0, p1 in parents:
        inside = sorted((s, n) for n, s, e in found.host
                        if n.startswith(entry + ".") and p0 <= s <= e <= p1)
        assert [n for _, n in inside] == [f"{entry}.{p}" for p in phases]
    # The idle under prepare is part of the idle under perf.dispatch.
    dispatch = [(s, e) for n, s, e in summary.host_spans
                if n == "perf.dispatch"]
    for under_prepare, under_dispatch in zip(
            spans.idle_under(summary, found.intervals("prepare")),
            spans.idle_under(summary, dispatch)):
        assert 0 <= under_prepare <= under_dispatch
