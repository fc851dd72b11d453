"""Spans on the profiler's clock (``poisson_tpu.obs.span``).

Under a ``jax.profiler`` session every span is a host event of the
``.xplane.pb``, on the clock of the device lines, nested under whatever
annotation its caller holds on the same thread. The three entries the
chip benchmark drives each leave one span named for the entry, with
``prepare``/``launch``(/``finish``) children in that order. With
telemetry unconfigured a span still writes no file.
"""

from __future__ import annotations

import glob
import os

import jax
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from poisson_tpu import obs
from poisson_tpu.config import Problem

pytestmark = pytest.mark.obs

PROBLEM = Problem(M=40, N=40)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    obs.shutdown()
    yield
    obs.shutdown()


def _profile(tmp_path, fn):
    """Run ``fn`` under a profiler session (no Python tracer); return the
    host events of the trace as ``(line, name, start_ns, end_ns)``."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    out = str(tmp_path / "xplane")
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events.extend((f"{plane.name}/{line.name}", e.name,
                               e.start_ns, e.start_ns + e.duration_ns)
                              for e in line.events)
    return events


def _one(events, name):
    found = [e for e in events if e[1] == name]
    assert len(found) == 1, (name, found)
    return found[0]


def _inside(child, parent):
    return (child[0] == parent[0] and parent[2] <= child[2]
            and child[3] <= parent[3])


@pytest.mark.parametrize("configured", [False, True],
                         ids=["unconfigured", "recorder"])
def test_span_nests_under_the_callers_annotation(tmp_path, configured):
    if configured:
        obs.configure()     # in-memory recorder, no files

    def body():
        with TraceAnnotation("caller"):
            with obs.span("callee", k=1):
                jax.numpy.ones(3).block_until_ready()

    events = _profile(tmp_path, body)
    caller, callee = _one(events, "caller"), _one(events, "callee")
    # The span's arguments stay off the annotation: its name is the name.
    assert _inside(callee, caller)
    if configured:
        assert [e["name"] for e in obs.recorder().trace_events()] == [
            "callee"]


def test_unconfigured_span_writes_no_file(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    assert obs.recorder() is None
    with obs.span("outer"):
        with obs.span("inner", k=1):
            pass
    obs.finalize()
    assert os.listdir(tmp_path) == []


def _pallas():
    from poisson_tpu.ops.pallas_cg import pallas_cg_solve

    return lambda: pallas_cg_solve(PROBLEM, rhs_gate=1.0)


def _pallas_sharded():
    from poisson_tpu.parallel import make_solver_mesh
    from poisson_tpu.parallel.pallas_sharded import pallas_cg_solve_sharded

    mesh = make_solver_mesh(jax.devices()[:4], grid=(2, 2))
    return lambda: pallas_cg_solve_sharded(PROBLEM, mesh, rhs_gate=1.0)


def _batched():
    from poisson_tpu.solvers.batched import solve_batched

    return lambda: solve_batched(PROBLEM, rhs_gates=[1.0, 0.5, 2.0])


def _xla(preconditioner):
    from poisson_tpu.solvers.pcg import pcg_solve

    return lambda: pcg_solve(PROBLEM, dtype="float32", rhs_gate=1.0,
                             preconditioner=preconditioner)


ENTRIES = [
    ("pallas_cg_solve", _pallas, ("prepare", "launch", "finish")),
    ("pallas_cg_solve_sharded", _pallas_sharded, ("prepare", "launch")),
    ("solve_batched", _batched, ("prepare", "launch", "finish")),
    ("pcg_solve", lambda: _xla("jacobi"), ("prepare", "launch", "finish")),
    ("pcg_solve", lambda: _xla("mg"), ("prepare", "launch", "finish")),
]
IDS = ["pallas_cg_solve", "pallas_cg_solve_sharded", "solve_batched",
       "pcg_solve-jacobi", "pcg_solve-mg"]


@pytest.mark.parametrize("entry,make,phases", ENTRIES, ids=IDS)
def test_entry_spans_its_phases_in_order(tmp_path, entry, make, phases):
    solve = make()
    jax.block_until_ready(solve().w)       # compile outside the trace
    events = _profile(tmp_path,
                      lambda: jax.block_until_ready(solve().w))
    parent = _one(events, entry)
    children = [_one(events, f"{entry}.{phase}") for phase in phases]
    for child in children:
        assert _inside(child, parent), (child, parent)
    # One after the other, in the order of the phases.
    for before, after in zip(children, children[1:]):
        assert before[3] <= after[2], (before, after)
    # No other phase of this entry was recorded.
    assert {e[1] for e in events if e[1].startswith(entry + ".")} == {
        f"{entry}.{phase}" for phase in phases}
