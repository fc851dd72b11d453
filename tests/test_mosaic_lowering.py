"""Chip-free Mosaic-lowering regression tier (VERDICT r3 item 3).

``jax.export.export(jax.jit(fn), platforms=['tpu'])`` performs the full
Pallas→Mosaic *IR* lowering on any host platform. It does NOT run the
Mosaic machine-code compiler — a kernel can lower cleanly here and still
abort on the chip (the round-3 failure class) — but it is the only
chip-free guard available: every trace/lowering-class regression in a
kernel variant × reduction-layout combination is caught in the CPU suite
before any driver or TPU session becomes the first Mosaic contact.

Coverage: the fused 2-sweep kernels (full-width, column-blocked, parallel
tile grid), the communication-avoiding s=2 kernels (single-device, and
sharded with the ±2 band + column mask under ``shard_map``), and the masked
sharded fused kernels under ``shard_map`` (1×1 — the exact driver-session
configuration — and 2×2 with halo exchange), each in both reduction-partial
layouts
(per-strip ``(nb, 1)`` partials vs serial-Kahan) where the combination is
legal (the parallel tile grid requires the partial layout;
``_resolve_serial`` raises on the contradiction).

Reference analog: the stage4 Makefile was the reference's "does the kernel
build" gate (``/root/reference/stage4-mpi+cuda/Makefile:1-30``); this tier
is ours, minus the machine-code stage the chip keeps to itself.
"""

from __future__ import annotations

import jax
import pytest

from poisson_tpu.config import Problem
from poisson_tpu.ops import pallas_ca, pallas_cg
from poisson_tpu.parallel import make_solver_mesh
from poisson_tpu.parallel import pallas_ca_sharded, pallas_sharded

@pytest.fixture(autouse=True)
def _x64_off():
    """Lower in the hardware dtype regime. The suite enables x64 for
    oracle parity (conftest), but no TPU entry point does — and under x64
    Python-float promotion plants f64→f32 casts inside the kernels that
    Mosaic (correctly) refuses to lower, which are not present in the
    configuration that meets the chip."""
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", old)


# (parallel tile grid, serial-Kahan reduction layout): serial=True with
# parallel=True is rejected by _resolve_serial, so it is not a case here.
LAYOUTS = [
    pytest.param(False, False, id="partials"),
    pytest.param(False, True, id="serial-kahan"),
    pytest.param(True, False, id="parallel-grid"),
]


def _export_tpu(fn, *args):
    """Lower for the TPU platform; any lowering failure raises here."""
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    assert exported.platforms == ("tpu",)
    return exported


@pytest.mark.parametrize("parallel,serial", LAYOUTS)
def test_fused_full_width_lowers(parallel, serial):
    # bm=8 forces nb=5 strips: multi-strip partial outputs are the shape
    # class that failed on hardware in round 3 (an auto bm at 40×40 gives
    # nb=1, whose degenerate partials lower even with per-cell maps).
    p = Problem(M=40, N=40)
    cv, cs, cw, g, rhs, sc2, _ = pallas_cg.build_canvases(
        p, 8, "float32", None
    )
    assert cv.nb > 1
    _export_tpu(
        lambda cs, cw, g, rhs, sc2: pallas_cg._fused_solve(
            p, cv, False, parallel, serial, cs, cw, g, rhs, sc2
        ),
        cs, cw, g, rhs, sc2,
    )


@pytest.mark.parametrize("parallel,serial", LAYOUTS)
def test_fused_column_blocked_lowers(parallel, serial):
    # bn=128 on a 40×300 grid: 3 column blocks, the blocked kernel variant
    # (_make_blocked_stencil_kernel) with its inter-block halo columns.
    p = Problem(M=40, N=300)
    cv, cs, cw, g, rhs, sc2, _ = pallas_cg.build_canvases(
        p, None, "float32", 128
    )
    assert cv.cg > 0, "expected the column-blocked geometry"
    _export_tpu(
        lambda cs, cw, g, rhs, sc2: pallas_cg._fused_solve(
            p, cv, False, parallel, serial, cs, cw, g, rhs, sc2
        ),
        cs, cw, g, rhs, sc2,
    )


@pytest.mark.parametrize("parallel,serial", LAYOUTS)
def test_ca_pair_iteration_lowers(parallel, serial):
    # bm=8 → nb=5: multi-strip Gram/partial outputs (see the fused test).
    p = Problem(M=40, N=40)
    cv, cs, cw, g, rhs, sc2, _ = pallas_cg.build_canvases(
        p, 8, "float32", None
    )
    assert cv.nb > 1
    _export_tpu(
        lambda cs, cw, g, rhs, sc2: pallas_ca._ca_solve(
            p, cv, False, parallel, serial, cs, cw, g, rhs, sc2
        ),
        cs, cw, g, rhs, sc2,
    )


@pytest.mark.parametrize("serial", [False, True],
                         ids=["partials", "serial-kahan"])
@pytest.mark.parametrize("grid", [(1, 1), (2, 2)],
                         ids=["mesh1x1", "mesh2x2"])
def test_sharded_masked_lowers(grid, serial):
    # (1, 1) is the exact configuration benchmarks/tpu_session.py
    # Mosaic-compiles on a single chip; (2, 2) adds the
    # ppermute halo exchange to the lowered module. Arrays travel as
    # explicit jit arguments (a nullary export whose operands are all
    # closure constants trips jit-cache pytree bookkeeping when the same
    # canvases are exported twice).
    p = Problem(M=40, N=40)
    px, py = grid
    mesh = make_solver_mesh(jax.devices()[: px * py], grid=grid)
    spec = pallas_sharded.shard_spec(p, px, py, bm=8)  # multi-strip shards
    assert spec.cv.nb > 1
    cs, cw, g, rhs, sc2, sc_int, colmask = pallas_sharded._shard_canvases(
        p, px, py, spec, "float32"
    )
    _export_tpu(
        lambda cs, cw, g, rhs, sc2, sc_int, colmask:
        pallas_sharded._fused_solve_sharded(
            p, mesh, spec, False, cs, cw, g, rhs, sc2, sc_int, colmask,
            False, serial,
        ),
        cs, cw, g, rhs, sc2, sc_int, colmask,
    )


@pytest.mark.parametrize("serial", [False, True],
                         ids=["partials", "serial-kahan"])
@pytest.mark.parametrize("grid", [(1, 1), (2, 2)],
                         ids=["mesh1x1", "mesh2x2"])
def test_ca_sharded_masked_lowers(grid, serial):
    # The CA kernels with band widened ±2 and the column mask, under
    # shard_map with the width-2 ring exchange — the sharded-CA
    # configuration × both reduction layouts.
    p = Problem(M=40, N=40)
    px, py = grid
    mesh = make_solver_mesh(jax.devices()[: px * py], grid=grid)
    spec = pallas_ca_sharded.ca_shard_spec(p, px, py, bm=8)  # multi-strip
    assert spec.cv.nb > 1
    (cs, cw, g, rhs, sc2, sc_int,
     colmask) = pallas_ca_sharded._ca_shard_canvases(
        p, px, py, spec, "float32"
    )
    _export_tpu(
        lambda cs, cw, g, rhs, sc2, sc_int, colmask:
        pallas_ca_sharded._ca_solve_sharded(
            p, mesh, spec, False, cs, cw, g, rhs, sc2, sc_int, colmask,
            False, serial,
        ),
        cs, cw, g, rhs, sc2, sc_int, colmask,
    )


@pytest.mark.parametrize("grid", [(40, 40), (400, 600)],
                         ids=["40x40", "400x600"])
def test_resident_persistent_kernel_lowers(grid):
    # The whole-solve in-kernel while_loop with VMEM scratch state — the
    # persistent-kernel path at both grids it serves (400x600 is the
    # capacity target and the largest whole-array reduce).
    from poisson_tpu.ops import pallas_resident

    p = Problem(M=grid[0], N=grid[1])
    cv = pallas_resident.resident_canvas(p)
    _, cs, cw, g, rhs, sc2, _ = pallas_cg.build_canvases(
        p, cv.bm, "float32", 0
    )
    _export_tpu(
        lambda cs, cw, g, rhs, sc2: pallas_resident._resident_solve(
            p, cv, False, cs, cw, g, rhs, sc2
        ),
        cs, cw, g, rhs, sc2,
    )


@pytest.mark.slow
def test_flagship_geometry_lowers_both_layouts():
    """The shipping flagship configuration (800×1200, auto bm) — the
    geometry the driver's bench and the TPU session actually compile on
    hardware — must lower in BOTH reduction layouts. This is the chip-free
    shadow of the session's kernel_probe layout A/B gate."""
    p = Problem(M=800, N=1200)
    cv, cs, cw, g, rhs, sc2, _ = pallas_cg.build_canvases(
        p, None, "float32", None
    )
    for serial in (False, True):
        _export_tpu(
            lambda cs, cw, g, rhs, sc2: pallas_cg._fused_solve(
                p, cv, False, False, serial, cs, cw, g, rhs, sc2
            ),
            cs, cw, g, rhs, sc2,
        )
