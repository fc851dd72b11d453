"""The multigrid cell over a mesh, ``mg-mesh2x2-12800x19200``, on the CPU:
its driver, check and control rehearsed on four virtual devices at a grid
that coarsens to the same 50x75 coarsest level (400x600: 200x300 blocks),
the mesh reference against the one-device reference, the entry's refusal
of a program whose CLI does not split MG over the mesh, and the reader of
``halo_pct.mg`` on intervals made by hand and on a recorded chip trace
without the MG names."""

import json
import types
from unittest import mock

import numpy as np
import pytest

from perf import control_mg_mesh, entry, entry_mg_mesh, mg_trace, trace
from perf import run as harness
from perf.tests.conftest import rehearse
from perf.tests.test_mg_cell import DATA, _hlo, _load

CELL = "mg-mesh2x2-12800x19200"
GRID = {"M": 400, "N": 600}


@pytest.mark.parametrize("traced", [False, True])
def test_mesh_records(traced):
    result = rehearse(CELL, trace=traced, **GRID)
    info = result["info"]
    assert info["backend"] == "sharded" and info["window_compiles"] == 0
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["device"]["count"] == 4
    mg = info["mg"]
    assert mg["mg.levels"] in (None, 4) and mg["mg.coarse_dense"] in (None, 1)
    # No strip kernel on the shards; the cycle leaves them at level 1.
    assert mg["mg.pallas_levels"] == 0 and mg["mg.replicated_from"] == 1
    assert mg["mg.solves"] == result["attempted"] + 1
    # Device-trace metrics have nothing to read off the TPU.
    want = {"iters.solve"} if traced else {"setup_s", "solve_s"}
    assert set(result["metrics"]) == want


def test_mesh_control_is_not_correct():
    with control_mg_mesh.in_place():
        result = rehearse(CELL, **GRID)
    assert result["info"]["backend"] == "control-bfloat16"
    assert not result["correct"], result["checks"]


def test_mesh_reference_agrees_with_the_one_device_reference():
    """The same arithmetic, laid out by XLA's partitioner over 2x2: the
    iteration count equal, the field within 1e-4 of max|w| (the
    partitioner splits each dot into four partial sums, added in another
    order: 1.5e-5 read at 400x600)."""
    import jax

    from poisson_tpu.parallel import make_solver_mesh

    config = json.loads((harness.PERF / "configs" /
                         "ellipse-12800x19200-mg-mesh2x2.json").read_text())
    problem = dict(config["problem"], **GRID)
    module = entry.load_module(harness.PERF / "reference" /
                               "ellipse_mgpcg_mesh.py")
    mesh = make_solver_mesh(jax.devices()[:4], grid=(2, 2))
    one = module.Reference(problem, 60, "float32")
    split = module.Reference(problem, 60, "float32", mesh=mesh)
    for gate in (0.95, 1.05):
        w1, k1, _ = one.solve(gate)
        w4, k4, _ = split.solve(gate)
        assert k1 == k4
        assert np.abs(w4 - w1).max() <= 1e-4 * np.abs(w1).max()


def test_the_entry_refuses_a_cli_that_keeps_mg_on_one_device():
    """The parent of the mesh MG program sends ``--preconditioner mg`` to
    the one-device solve whatever the mesh: the entry stops the run
    before any set-up."""
    run = types.SimpleNamespace(
        config={"problem": {"M": 400, "N": 600}, "preconditioner": "mg",
                "mesh": [2, 2], "dtype": "float32"}, devices=[None] * 4)
    with mock.patch.object(entry_mg_mesh, "pick_backend",
                           lambda run: "xla"):
        with pytest.raises(SystemExit, match="'xla'"):
            entry_mg_mesh.solve_entry(run)


def _permute(name, level=None):
    attrs = ("" if level is None
             else f', frontend_attributes={{mg_level="{level}"}}')
    return (f"%{name} = (f32[9602]{{0}}, f32[9602]{{0}}) "
            f"collective-permute-start(f32[9602]{{0}} %x){attrs}")


def test_halo_reader_from_intervals():
    window = (0, 100)
    events = {
        "/device:TPU:0": [
            (_hlo("f.1", "fusion", 0), 0, 40),
            (_permute("cp.1", 0), 40, 50),
            (_permute("cp.2", 1), 45, 55),           # overlaps cp.1
            (_permute("cp.3"), 55, 60),              # the CG's own
            (_hlo("ag.1", "all-gather", 3), 60, 70),  # not a permute
            (_permute("cp.4", 2), 95, 130),          # clipped
        ],
        "/device:TPU:1": [(_hlo("f.2", "fusion", 0), 0, 50)],
    }
    host = [("perf.window",) + window]
    summary = trace.summarize(events, host, window)
    reader = _load("halo_pct.mg")
    assert reader.halo_intervals(events["/device:TPU:0"], window) == [
        (40, 55), (95, 100)]
    busy0, busy1 = (d.busy_ns for d in summary.devices)
    assert (busy0, busy1) == (75, 50)
    assert reader.share(events, summary) == pytest.approx(
        100 * (20 / 75 + 0 / 50) / 2)
    untagged = {d: [e for e in ev if mg_trace.level_of(e[0]) is None]
                for d, ev in events.items()}
    assert reader.share(untagged, summary) is None


def test_halo_reader_reads_none_without_the_names():
    """The four-chip trace of the Jacobi mesh solve: permutes, but no
    ``mg_level`` tag on any of them."""
    path = str(DATA / "spans-4chip.xplane.pb")
    events, host, window = trace.read_xspace(path)
    summary = trace.summarize(events, host, window)
    assert any(trace.op_key(n)[1].startswith("collective-permute")
               for ev in events.values() for n, _, _ in ev)
    assert _load("halo_pct.mg").share(events, summary) is None
    assert _load("halo_pct.mg").read(types.SimpleNamespace(trace=None)) is None
