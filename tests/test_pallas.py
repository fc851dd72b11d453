"""Fused Pallas kernel tests (SURVEY §7 step 5).

The pure-JAX ops are the framework's reference implementation — the role
stage4's retained CPU fallbacks played (``stage4:…cu:198-226``); these tests
A/B the Pallas path against them, on CPU via interpret mode (the kernels
themselves are what runs on TPU — same trace, different executor).
"""

import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from poisson_tpu.config import Problem
from poisson_tpu.ops import pallas_cg
from poisson_tpu.ops.pallas_cg import HALO, build_canvases, pallas_cg_solve
from poisson_tpu.ops.stencil import apply_A
from poisson_tpu.solvers.pcg import host_fields64, pcg_solve


@pytest.mark.parametrize(
    "M,N,bm",
    [
        (40, 40, 16),     # square, interior 39 not divisible by bm
        (80, 120, 16),    # rectangular
        (40, 40, None),   # auto bm (larger than the grid)
    ],
)
def test_full_solve_parity_vs_xla_f32(M, N, bm):
    p = Problem(M=M, N=N)
    r_ref = pcg_solve(p, dtype=jnp.float32)
    r_pal = pallas_cg_solve(p, bm=bm)
    assert int(r_pal.iterations) == int(r_ref.iterations)
    np.testing.assert_allclose(
        np.asarray(r_pal.w), np.asarray(r_ref.w), atol=1e-6
    )


def test_canvases_zero_outside_interior():
    p = Problem(M=40, N=40)
    cv, cs, cw, g, rhs, sc2, sc64 = build_canvases(p, 16)
    band = slice(HALO, HALO + p.M - 1)
    for name, arr, interior_cols in [
        ("rhs", rhs, slice(1, p.N)),
        ("sc2", sc2, slice(1, p.N)),
    ]:
        a = np.asarray(arr)
        mask = np.zeros_like(a, bool)
        mask[band, interior_cols] = True
        assert (a[~mask] == 0).all(), name
    # Coefficient canvases: every edge touching ring/guard/pad is zero, so
    # the kernels need no interior masking (module docstring invariant).
    for name, arr in [("cs", cs), ("cw", cw)]:
        a = np.asarray(arr)
        assert np.isfinite(a).all(), name
        assert (a[:HALO] == 0).all(), name              # guard band
        assert (a[HALO + p.M :] == 0).all(), name       # guard/pad rows
        assert (a[HALO:, p.N + 1 :] == 0).all(), name   # pad columns
        assert a[HALO:].any(), name                     # real coefficients exist
    # Edges touching the Dirichlet ring vanish because sc is zero there:
    # row HALO of cs is the i=1 south edge (neighbour is the ring), and
    # column 1 of cw is the j=1 west edge.
    assert (np.asarray(cs)[HALO] == 0).all()
    assert (np.asarray(cw)[:, 1] == 0).all()
    # …while the next edge inward is genuinely nonzero.
    assert np.asarray(cs)[HALO + 1].any()
    assert np.asarray(cw)[:, 2].any()


def test_kernel_a_matches_scaled_operator():
    """Kernel A's stencil (folded-coefficient form, 4 MACs/pt) against the
    flux-form scaled operator sc·A(sc·y) built from ops.stencil."""
    p = Problem(M=24, N=40)
    cv, cs, cw, g, rhs, sc2, sc64 = build_canvases(p, 8)
    rng = np.random.RandomState(0)

    y_grid = np.zeros((p.M + 1, p.N + 1))
    y_grid[1:-1, 1:-1] = rng.rand(p.M - 1, p.N - 1)

    z = np.zeros((cv.rows, cv.cols), np.float32)
    z[HALO : HALO + p.M - 1, : p.N + 1] = y_grid[1 : p.M, :]
    z = jnp.asarray(z)
    zero = jnp.zeros_like(z)
    beta = jnp.zeros((1, 1), jnp.float32)

    pn, ap, denom = pallas_cg.direction_and_stencil(
        cv, beta, z, zero, cs, cw, g, interpret=True
    )

    a64, b64, _, sc = host_fields64(p, True)
    want = sc * apply_A(sc * y_grid, a64, b64, p.h1, p.h2)
    got = np.asarray(ap)[HALO : HALO + p.M - 1, : p.N + 1]
    np.testing.assert_allclose(got, want[1:-1, :], atol=1e-5)
    # and the per-strip dot partials sum to ⟨Ap, p⟩ (unweighted)
    assert denom.shape == (cv.nb, 1)
    np.testing.assert_allclose(
        float(denom.sum()), float((want[1:-1] * y_grid[1:-1]).sum()), rtol=1e-5
    )


def test_degenerate_direction_stops_cleanly():
    """Zero RHS ⇒ zr=0, first denom=0 ⇒ degenerate guard: solver must stop
    after one iteration with w=0, not NaN."""
    p = Problem(M=16, N=16, max_iter=5)
    cv, cs, cw, g, rhs, sc2, sc64 = build_canvases(p, 8)
    s = pallas_cg._fused_solve(
        p, cv, True, False, False, cs, cw, g, jnp.zeros_like(rhs), sc2
    )
    assert int(s.k) == 1
    assert bool(s.done)
    assert np.isfinite(np.asarray(s.w)).all()
    assert (np.asarray(s.w) == 0).all()


@pytest.mark.parametrize(
    "M,N,bm,bn",
    [
        (40, 40, 16, 128),    # ncb=1: guards exercised, single block
        (40, 300, 16, 128),   # ncb=3: interior columns cross block seams
        (80, 300, None, 256), # auto bm, uneven last block (301 into 2x256)
    ],
)
def test_column_blocked_solve_parity(M, N, bm, bn):
    """The column-blocked (2D-grid) canvas must reproduce the full-width
    fused path: same iteration count, same solution to fp32 tolerance
    (partial-sum tree shape differs, so bitwise equality is not expected)."""
    p = Problem(M=M, N=N)
    r_full = pallas_cg_solve(p)
    r_blk = pallas_cg_solve(p, bm=bm, bn=bn)
    assert int(r_blk.iterations) == int(r_full.iterations)
    np.testing.assert_allclose(
        np.asarray(r_blk.w), np.asarray(r_full.w), atol=1e-6
    )


def test_column_blocked_golden_40x40():
    r = pallas_cg_solve(Problem(M=40, N=40), bm=16, bn=128)
    assert int(r.iterations) == 50


def test_auto_blocking_on_degenerate_width():
    """A canvas too wide for sane full-width strips auto-selects column
    blocking; explicit bm, explicit bn, and the bn=0 force-full-width
    sentinel all win over the auto pick."""
    from poisson_tpu.ops.pallas_cg import canvas_spec

    wide = Problem(M=64, N=20000)
    cv = canvas_spec(wide)
    assert cv.cg == 128 and cv.bm >= 64, cv
    assert canvas_spec(wide, bm=8).cg == 0          # explicit bm: full width
    assert canvas_spec(wide, bn=1024).bn == 1024    # explicit bn honored
    assert canvas_spec(wide, bn=0).cg == 0          # sentinel: full width
    # Published grids keep their proven full-width geometry.
    assert canvas_spec(Problem(M=2400, N=3200)).cg == 0
    # Small-M grids: bm is capped by owned rows, not width — no blocking.
    assert canvas_spec(Problem(M=16, N=40)).cg == 0


def test_checkpoint_layout_survives_auto_blocking():
    """The portable checkpoint path hard-codes the full-width column
    layout; it must keep working (and round-trip) on a grid whose default
    solve auto-blocks."""
    import tempfile

    from poisson_tpu.ops.pallas_cg import (
        canvas_spec, pallas_cg_solve, pallas_cg_solve_checkpointed,
    )

    wide = Problem(M=24, N=17000, max_iter=6)
    assert canvas_spec(wide).cg == 128              # default solve blocks
    with tempfile.TemporaryDirectory() as d:
        got = pallas_cg_solve_checkpointed(wide, f"{d}/ck.npz", chunk=3)
    ref = pallas_cg_solve(wide, bn=0)
    assert int(got.iterations) == int(ref.iterations)
    np.testing.assert_allclose(
        np.asarray(got.w), np.asarray(ref.w), atol=1e-6
    )


def test_checkpoint_portable_across_canvas_geometries(tmp_path):
    """A checkpoint written from a column-blocked canvas resumes on the
    full-width canvas and matches the one-shot solve: the portable format
    is the full-grid state, independent of canvas geometry."""
    import dataclasses

    from poisson_tpu.ops.pallas_cg import pallas_cg_solve_checkpointed

    p = Problem(M=40, N=300)
    capped = dataclasses.replace(p, max_iter=20)
    ck = str(tmp_path / "ck.npz")
    part = pallas_cg_solve_checkpointed(capped, ck, chunk=7, bn=256)
    assert int(part.iterations) == 20
    got = pallas_cg_solve_checkpointed(p, ck, chunk=7, bn=0)
    ref = pallas_cg_solve(p, bn=0)
    assert int(got.iterations) == int(ref.iterations)
    np.testing.assert_allclose(
        np.asarray(got.w), np.asarray(ref.w), atol=1e-6
    )


@pytest.mark.slow
def test_column_blocked_golden_400x600():
    """Blocked path at a published grid with real multi-block seams
    (601 content cols → 3 × bn=256): golden count exact."""
    r = pallas_cg_solve(Problem(M=400, N=600), bn=256)
    assert int(r.iterations) == 546


def test_parallel_grid_matches_sequential():
    """The parallel strip-grid option must be a pure scheduling hint: same
    iterate sequence, bit-identical solution (per-strip partials are
    tree-summed the same way either way). On non-megacore devices (this
    CPU run included) it must stay silent — the megacore caveat warning
    is device-gated (round-4 advisor finding + review)."""
    p = Problem(M=40, N=40)
    r_seq = pallas_cg_solve(p)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        r_par = pallas_cg_solve(p, parallel=True)
    assert int(r_par.iterations) == int(r_seq.iterations) == 50
    np.testing.assert_array_equal(np.asarray(r_par.w), np.asarray(r_seq.w))


def test_megacore_predicate():
    """The caveat warning fires exactly on megacore parts: two TensorCores
    fused behind one device (v4, v5p) — not on single-core lite parts, not
    on per-core-device v2/v3, not off-TPU. Real libtpu device_kind strings
    include the bare 'TPU v4'/'TPU v5' spellings (v5p has been reported as
    'TPU v5', with no 'p') and the lite parts' 'TPU v5 lite'/'TPU v5e'."""
    from poisson_tpu.ops.pallas_cg import _is_megacore
    assert _is_megacore("tpu", "TPU v4")
    assert _is_megacore("tpu", "TPU v5p")
    assert _is_megacore("tpu", "TPU v5")       # how libtpu reports v5p
    assert not _is_megacore("tpu", "TPU v5 lite")
    assert not _is_megacore("tpu", "TPU v5e")
    assert not _is_megacore("tpu", "TPU v5litepod-8")
    assert not _is_megacore("tpu", "TPU v6e")
    assert not _is_megacore("tpu", "TPU v3")
    assert not _is_megacore("cpu", "cpu")


def test_megacore_parallel_partials_warns(monkeypatch):
    """On a (faked) megacore device the parallel-grid + partial-output
    combination announces the unverified cross-core write-back. Exercised
    at the _resolve_serial unit — a full solve may hit the jit cache from
    an earlier parallel=True trace and never re-run the resolution."""
    monkeypatch.setattr(pallas_cg, "_is_megacore_device", lambda: True)
    with pytest.warns(RuntimeWarning, match="megacore"):
        assert pallas_cg._resolve_serial(None, True) is False
    with warnings.catch_warnings():  # serial path never uses partials
        warnings.simplefilter("error")
        with pytest.raises(ValueError):
            pallas_cg._resolve_serial(True, True)


def test_gate_is_bit_exact():
    p = Problem(M=40, N=40)
    r1 = pallas_cg_solve(p)
    r2 = pallas_cg_solve(p, rhs_gate=jnp.float32(1.0))
    assert int(r1.iterations) == int(r2.iterations)
    assert np.array_equal(np.asarray(r1.w), np.asarray(r2.w))


@pytest.mark.slow
def test_serial_kahan_reduce_layout_matches_partials():
    """POISSON_TPU_SERIAL_REDUCE=1 switches the reduction partials from
    per-strip (nb, 1) SMEM rows to one Kahan-compensated SMEM cell (the
    layout hardware-proven in round 2). Import-frozen, so the variant runs
    in a subprocess; it must reproduce the golden counts and the default
    layout's L2 on the single-device, column-blocked, sharded-fused, and
    sharded-CA paths."""
    import json
    import os
    import pathlib
    import subprocess
    import sys

    code = r"""
import json
from poisson_tpu.config import Problem
from poisson_tpu.ops.pallas_cg import pallas_cg_solve, SERIAL_REDUCE
from poisson_tpu.analysis import l2_error_host
assert SERIAL_REDUCE
out = {}
p = Problem(M=400, N=600)
r = pallas_cg_solve(p)
out["single"] = [int(r.iterations), l2_error_host(p, r.w)]
r = pallas_cg_solve(p, bn=256)
out["blocked"] = [int(r.iterations), l2_error_host(p, r.w)]
import jax
from poisson_tpu.parallel import make_solver_mesh
from poisson_tpu.parallel.pallas_sharded import pallas_cg_solve_sharded
from poisson_tpu.parallel.pallas_ca_sharded import ca_cg_solve_sharded
mesh = make_solver_mesh(jax.devices()[:4], grid=(2, 2))
r = pallas_cg_solve_sharded(Problem(M=40, N=40), mesh)
out["sharded_2x2"] = [int(r.iterations)]
r = ca_cg_solve_sharded(Problem(M=40, N=40), mesh)
out["ca_sharded_2x2"] = [int(r.iterations)]
print(json.dumps(out))
"""
    env = dict(os.environ)
    env["POISSON_TPU_SERIAL_REDUCE"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
    root = pathlib.Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root)] + [p for p in [env.get("PYTHONPATH", "")] if p]
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=root, env=env,
        capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["single"][0] == 546
    assert got["blocked"][0] == 546
    assert got["sharded_2x2"][0] == 50
    assert got["ca_sharded_2x2"][0] == 50
    assert got["single"][1] < 4e-4 and got["blocked"][1] < 4e-4


def test_serial_reduce_param_in_process():
    """The threaded ``serial`` knob: in-process A/B against the default
    layout (distinct jit keys), and the contradictory serial+parallel
    combination raises instead of silently preferring one."""
    p = Problem(M=40, N=40)
    r_def = pallas_cg_solve(p, serial=False)   # explicit: env could say 1
    r_ser = pallas_cg_solve(p, serial=True)
    assert int(r_ser.iterations) == int(r_def.iterations) == 50
    np.testing.assert_allclose(
        np.asarray(r_ser.w), np.asarray(r_def.w), rtol=0, atol=5e-6
    )
    with pytest.raises(ValueError, match="parallel"):
        pallas_cg_solve(p, serial=True, parallel=True)
