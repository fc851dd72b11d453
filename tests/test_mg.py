"""Geometric multigrid preconditioning (``poisson_tpu.mg``).

The contract under test, layer by layer:

- **off means off** — ``preconditioner="jacobi"`` (the default) lowers
  to the byte-identical historical solve program and keeps the golden
  iteration counts bit-for-bit;
- **the cycle works** — two-grid contraction < 0.2 on the literature's
  model problem, and the V-cycle *apply* is bit-identical under vmap
  (the parity contract the batched/lane drivers rest on);
- **the iteration wall breaks** — MG counts stay ~flat (within 2×)
  across 100×150 → 200×300 → 400×600 where Jacobi's roughly double;
- **every geometry family gates** — the manufactured-solution L2 floor
  holds under MG for each closed-form family (the PR 9 rule verbatim);
- **the rails hold** — batched/lane/chunked/resilient parity, verified
  clean solves with zero false alarms at the MG-calibrated guard
  ratios, bit-flip detection + verified restart, serve cohort split,
  and sentinel cohort/direction pins.
"""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poisson_tpu.config import Problem
from poisson_tpu.mg import (
    DEFAULT_MG,
    MGConfig,
    coarsen_a,
    coarsen_b,
    device_hierarchy,
    plan_levels,
    reset_hierarchy_cache,
    v_cycle,
    validate_mg_problem,
)
from poisson_tpu.solvers.pcg import (
    FLAG_CONVERGED,
    host_setup,
    pcg_solve,
)

jax.config.update("jax_enable_x64", True)

pytestmark = pytest.mark.mg


# -- level planning and coefficient coarsening --------------------------


def test_plan_levels_bench_grids_share_coarsest():
    """Every published bench grid bottoms out at the SAME 50×75
    coarsest level — what makes their iteration counts comparable."""
    for M, N in ((400, 600), (800, 1200), (1600, 2400), (3200, 4800)):
        assert plan_levels(M, N)[-1] == (50, 75)
    assert plan_levels(400, 600) == (
        (400, 600), (200, 300), (100, 150), (50, 75))


def test_validate_mg_problem_rejects_uncoarsenable():
    with pytest.raises(ValueError, match="coarsens"):
        validate_mg_problem(Problem(M=33, N=33))
    with pytest.raises(ValueError, match="coarsens"):
        validate_mg_problem(Problem(M=10, N=10))
    assert len(validate_mg_problem(Problem(M=40, N=40))) >= 2


def test_coarsen_constant_fields_exactly():
    a = np.full((65, 97), 3.5)
    ac = coarsen_a(a)
    assert ac.shape == (33, 49)
    np.testing.assert_array_equal(ac, 3.5)
    bc = coarsen_b(np.full((65, 97), 0.25))
    np.testing.assert_array_equal(bc, 0.25)


def test_coarsening_keeps_penalty_stiff():
    """The fictitious region's ~1/ε blend must survive coarsening, or
    the coarse correction would let the solution leak through the
    boundary: outside-the-ellipse coarse faces stay within 2× of the
    fine penalty scale."""
    p = Problem(M=64, N=64)
    from poisson_tpu.solvers.pcg import host_fields64

    a64, _, _, _ = host_fields64(p, False)
    ac = coarsen_a(np.asarray(a64))
    # Node far outside the ellipse on the coarse grid (corner region).
    assert ac[3, 3] > 0.5 / p.eps


# -- off means off: the default path is untouched -----------------------


def test_default_jacobi_path_hlo_byte_identical():
    """``pcg_solve``'s default path must still compile the EXACT
    historical program: the jitted ``_solve`` internals vs a verbatim
    local reconstruction, compiled HLO equal byte-for-byte (debug
    metadata aside) — with the mg module imported and used first, so
    nothing about loading the subsystem can perturb the default."""
    import poisson_tpu.solvers.pcg as pcg_mod
    from poisson_tpu.solvers.pcg import (
        PCGResult,
        pcg_loop,
        single_device_ops,
    )

    p = Problem(M=20, N=24)
    pcg_solve(p, preconditioner="mg")   # mg traffic first, on purpose
    a, b, rhs, aux = host_setup(p, "float64", False)

    current_txt = pcg_mod._solve.lower(
        p, False, 0, 0, 0.0, False, 0,
        a, b, rhs, aux).compile().as_text()

    # Named ``_solve`` so both lowerings produce the same HLO module
    # name ("jit__solve") and with it identical instruction numbering.
    def _solve(a, b, rhs, aux):
        ops = single_device_ops(p, a, b, aux)
        s = pcg_loop(
            ops, rhs, delta=p.delta, max_iter=p.iteration_cap,
            weighted_norm=p.weighted_norm, h1=p.h1, h2=p.h2,
            stream_every=0, verify_every=0, verify_tol=0.0,
            verify_abft=False,
        )
        return PCGResult(w=s.w, iterations=s.k, diff=s.diff,
                         residual_dot=s.zr, flag=s.flag)

    historical_txt = jax.jit(_solve).lower(
        a, b, rhs, aux).compile().as_text()

    from poisson_tpu.contracts.hlo import strip_hlo_metadata

    assert strip_hlo_metadata(current_txt) \
        == strip_hlo_metadata(historical_txt)


@pytest.mark.parametrize("M,N,weighted,expected", [
    (10, 10, False, 17), (20, 20, False, 31), (40, 40, True, 50),
])
def test_golden_counts_bit_for_bit_with_explicit_jacobi(M, N, weighted,
                                                        expected):
    r = pcg_solve(Problem(M=M, N=N, weighted_norm=weighted),
                  preconditioner="jacobi")
    assert int(r.iterations) == expected
    default = pcg_solve(Problem(M=M, N=N, weighted_norm=weighted))
    assert bool(jnp.all(default.w == r.w))


def test_unknown_preconditioner_is_loud():
    with pytest.raises(ValueError, match="unknown preconditioner"):
        pcg_solve(Problem(M=20, N=20), preconditioner="amg")


# -- the cycle itself ---------------------------------------------------


def test_two_grid_convergence_factor_under_020():
    """The satellite check: smoothing + coarse correction contract by
    < 0.2 per cycle on the isotropic model problem (exact dense coarse
    solve — the two-grid operator of the textbooks)."""
    from poisson_tpu.mg.selfcheck import two_grid_factor

    assert two_grid_factor(64, 64, max_levels=2) < 0.2


def test_deep_vcycle_factor_stays_bounded():
    from poisson_tpu.mg.selfcheck import two_grid_factor

    assert two_grid_factor(64, 64, max_levels=16) < 0.25


def _max_ulps(x, y) -> int:
    xi = np.asarray(x, np.float32).view(np.int32).astype(np.int64)
    yi = np.asarray(y, np.float32).view(np.int32).astype(np.int64)
    return int(np.abs(xi - yi).max())


def test_vcycle_apply_bit_parity_under_vmap():
    """The MG APPLY parity contract: one V-cycle vmapped over a batch
    follows the solo application — the reduction-order guarantee the
    batched/lane drivers' per-member trajectories rest on (the coarse
    dense matvec is a broadcast-multiply + trailing-axis reduce for
    exactly this reason). A batch of one is bit-identical; in a wider
    batch XLA:CPU (JAX 0.9) vectorizes the elementwise passes over the
    batch axis and rounds a few of them differently, so each member
    stays within a few fp32 ulps of its solo application (measured: 3)
    — a reduction reordered by the batch would be far off that."""
    p = Problem(M=64, N=64)
    a, b, rhs, aux = host_setup(p, "float32", True)
    reset_hierarchy_cache()
    hier = device_hierarchy(p, "float32", True)
    assert hier.coarse_inv is not None   # the risky reduction is live

    f = lambda r: v_cycle(hier, r, p.h1, p.h2, DEFAULT_MG)
    solo = jax.jit(f)(rhs)
    assert bool(jnp.all(jax.jit(jax.vmap(f))(rhs[None])[0] == solo))
    gates = (1.0, 1.3, 0.2)
    stacked = jax.jit(jax.vmap(f))(jnp.stack([rhs * g for g in gates]))
    for member, g in zip(stacked, gates):
        assert _max_ulps(member, jax.jit(f)(rhs * g)) <= 4


# -- the transfer pair --------------------------------------------------


def _full_weighting64(r):
    """The 9-point 1/16·[1 2 1; 2 4 2; 1 2 1] stencil at every interior
    coarse node (fine node (2I, 2J)), in NumPy float64, ring zero."""
    r = np.asarray(r, np.float64)
    m, n = (r.shape[-2] - 1) // 2, (r.shape[-1] - 1) // 2
    out = np.zeros(r.shape[:-2] + (m + 1, n + 1))
    weights = np.outer([1.0, 2.0, 1.0], [1.0, 2.0, 1.0]) / 16.0
    for di in (-1, 0, 1):
        for dj in (-1, 0, 1):
            out[..., 1:m, 1:n] += weights[di + 1, dj + 1] * r[
                ..., 2 + di:2 * m - 1 + di:2, 2 + dj:2 * n - 1 + dj:2]
    return out


def _interior_field(shape, rng, dtype=np.float64, low=-1.0):
    """A random field on ``shape`` (…, rows, cols) with a zero ring."""
    u = np.zeros(shape, dtype)
    u[..., 1:-1, 1:-1] = rng.uniform(low, 1.0, shape[:-2] + (
        shape[-2] - 2, shape[-1] - 2))
    return u


@pytest.mark.parametrize("m,n", [(6, 9), (13, 8)])
def test_restriction_is_nine_point_full_weighting(m, n):
    """``restrict_full_weighting`` on a stack of non-square grids is the
    9-point full-weighting stencil: to 1e-14 relative in float64, and
    within a few float32 ulps in float32 (positive data, so no sum
    cancels)."""
    from poisson_tpu.mg import restrict_full_weighting

    rng = np.random.default_rng(m * 100 + n)
    r = _interior_field((2, 2 * m + 1, 2 * n + 1), rng)
    got = np.asarray(restrict_full_weighting(jnp.asarray(r)))
    want = _full_weighting64(r)
    assert got.shape == want.shape == (2, m + 1, n + 1)
    assert np.abs(got - want).max() <= 1e-14 * np.abs(want).max()

    r32 = _interior_field((2, 2 * m + 1, 2 * n + 1), rng, np.float32,
                          low=0.5)
    got32 = restrict_full_weighting(jnp.asarray(r32))
    assert got32.dtype == jnp.float32
    assert _max_ulps(got32, _full_weighting64(r32)) <= 3


@pytest.mark.parametrize("m,n", [(6, 9), (13, 8)])
def test_restriction_is_quarter_prolongation_transpose(m, n):
    """R = ¼·Pᵀ against ``prolong_bilinear``: ⟨R r, e⟩ = ¼⟨r, P e⟩ over
    the interiors, in float64 — the symmetry the V-cycle's SPD property
    rests on."""
    from poisson_tpu.mg import prolong_bilinear, restrict_full_weighting

    rng = np.random.default_rng(m * 100 + n + 1)
    for _ in range(3):
        r = _interior_field((2, 2 * m + 1, 2 * n + 1), rng)
        e = _interior_field((2, m + 1, n + 1), rng)
        lhs = np.sum(np.asarray(restrict_full_weighting(jnp.asarray(r)))
                     * e, axis=(-2, -1))
        rhs = 0.25 * np.sum(r * np.asarray(prolong_bilinear(jnp.asarray(e))),
                            axis=(-2, -1))
        np.testing.assert_allclose(lhs, rhs, rtol=1e-12, atol=0)


def test_restriction_lowers_to_two_strided_slices():
    """The restriction is separable: the lowered program of a (2, 201,
    301) float32 stack holds no gather (jnp step indexing; a v5e runs
    one at 0.24 GB/s) and at most two slices with a stride (the
    unfactored stencil took nine stride-(2, 2) slices), none of them
    along the row axis, which XLA puts on a TPU's vector lanes."""
    from poisson_tpu.mg import restrict_full_weighting

    text = jax.jit(restrict_full_weighting).lower(
        jax.ShapeDtypeStruct((2, 201, 301), jnp.float32)).as_text()
    assert "gather" not in text
    slices = [line for line in text.splitlines() if "stablehlo.slice" in line]
    assert slices
    strided = []         # per strided slice, the axes it strides
    for line in slices:
        spans = re.search(r"\[([^\]]*)\]", line).group(1).split(",")
        axes = [i - len(spans) for i, s in enumerate(spans)
                if s.count(":") == 2 and int(s.split(":")[2]) != 1]
        if axes:
            strided.append(axes)
    assert 1 <= len(strided) <= 2, slices
    assert all(axes == [-1] for axes in strided), strided


def test_mg_solves_same_problem_as_jacobi():
    p = Problem(M=64, N=96)
    rj = pcg_solve(p)
    rm = pcg_solve(p, preconditioner="mg")
    assert int(rm.flag) == FLAG_CONVERGED
    assert float(rm.diff) < p.delta
    assert int(rm.iterations) * 3 <= int(rj.iterations)
    np.testing.assert_allclose(np.asarray(rm.w), np.asarray(rj.w),
                               atol=5e-5)


# -- iteration flatness: the wall actually breaks -----------------------


def test_iteration_counts_flat_across_resolutions():
    """Acceptance criterion: MG counts within 2× across
    100×150 → 200×300 → 400×600 while Jacobi's grow ~2× per step."""
    mg_counts, jac_counts = [], []
    for M, N in ((100, 150), (200, 300), (400, 600)):
        p = Problem(M=M, N=N)
        jac_counts.append(int(pcg_solve(p, dtype=jnp.float32).iterations))
        rm = pcg_solve(p, dtype=jnp.float32, preconditioner="mg")
        assert int(rm.flag) == FLAG_CONVERGED
        mg_counts.append(int(rm.iterations))
    assert max(mg_counts) <= 2 * min(mg_counts), mg_counts
    assert jac_counts[1] >= 1.7 * jac_counts[0]
    assert jac_counts[2] >= 1.7 * jac_counts[1]
    assert mg_counts[-1] * 10 <= jac_counts[-1]


# -- geometry families gate at the floor --------------------------------


@pytest.mark.parametrize("family", [
    "ellipse", "ellipse-offset", "rectangle", "polygon", "union",
    "intersection", "difference", "sdf",
])
def test_manufactured_floor_per_family_under_mg(family):
    """The PR 9 gating rule generalized verbatim: each family's
    manufactured-solution L2 must land at (essentially) the same floor
    under MG as under Jacobi — the preconditioner changes the path to
    the answer, never the answer."""
    from poisson_tpu.geometry.manufactured import (
        case_by_name,
        manufactured_error,
    )

    case = case_by_name(family)
    ej = manufactured_error(case, 64, 96)
    em = manufactured_error(case, 64, 96, preconditioner="mg")
    assert em["flag"] == FLAG_CONVERGED
    assert em["rel"] <= ej["rel"] * 1.1 + 1e-12
    assert em["iterations"] < ej["iterations"]


def test_mg_geometry_solo_solve():
    from poisson_tpu.geometry import Ellipse

    p = Problem(M=64, N=64)
    g = Ellipse(cx=0.1, cy=0.0, rx=0.7, ry=0.4)
    rm = pcg_solve(p, preconditioner="mg", geometry=g)
    rj = pcg_solve(p, geometry=g)
    assert int(rm.flag) == FLAG_CONVERGED
    np.testing.assert_allclose(np.asarray(rm.w), np.asarray(rj.w),
                               atol=5e-5)


# -- batched / lane / chunked / resilient parity ------------------------


def test_batched_mg_members_match_solo():
    """Iteration counts and flags exactly; iterates to a few ULPs (the
    FMA-contraction caveat documented on ``solve_batched``); and the MG
    bucket is its own executable family in the bucket cache."""
    from poisson_tpu.obs import metrics
    from poisson_tpu.solvers.batched import (
        reset_bucket_cache,
        solve_batched,
    )

    metrics.reset()
    reset_bucket_cache()
    p = Problem(M=64, N=64)
    gates = [1.0, 1.3, 0.7]
    solo = [pcg_solve(p, dtype=jnp.float32, preconditioner="mg",
                      rhs_gate=g) for g in gates]
    bat = solve_batched(p, rhs_gates=gates, dtype=jnp.float32,
                        preconditioner="mg")
    for i, s in enumerate(solo):
        assert int(bat.iterations[i]) == int(s.iterations)
        assert int(bat.flag[i]) == int(s.flag) == FLAG_CONVERGED
        np.testing.assert_allclose(np.asarray(bat.w[i]),
                                   np.asarray(s.w), atol=1e-5)
    # Same bucket, jacobi arm: a DIFFERENT executable family (both
    # counted as misses — the mg marker is part of the key).
    solve_batched(p, rhs_gates=gates, dtype=jnp.float32)
    assert metrics.get("batched.bucket_cache.misses") == 2
    # Re-dispatching the mg bucket is a hit.
    solve_batched(p, rhs_gates=[2.0, 0.5, 1.1], dtype=jnp.float32,
                  preconditioner="mg")
    assert metrics.get("batched.bucket_cache.hits") == 1


def test_lanes_mg_splice_step_retire():
    from poisson_tpu.solvers.lanes import LaneBatch

    p = Problem(M=64, N=64)
    solo = {g: pcg_solve(p, dtype=jnp.float32, preconditioner="mg",
                         rhs_gate=g) for g in (1.0, 1.3)}
    lb = LaneBatch(p, 2, dtype=jnp.float32, chunk=3,
                   preconditioner="mg")
    lb.splice("a", 1.0)
    lb.step()                      # "b" joins a RUNNING program
    lb.splice("b", 1.3)
    results = {}
    while lb.occupied():
        for v in lb.lane_view():
            if v["member_id"] is not None and v["done"]:
                res = lb.retire(v["lane"])
                results[res.member_id] = res
        if lb.occupied():
            lb.step()
    ref = {"a": solo[1.0], "b": solo[1.3]}
    for mid, res in results.items():
        assert res.iterations == int(ref[mid].iterations)
        assert res.flag == FLAG_CONVERGED
        np.testing.assert_allclose(np.asarray(res.w),
                                   np.asarray(ref[mid].w), atol=1e-5)


def test_lanes_mg_rejects_multi_geometry():
    from poisson_tpu.solvers.lanes import LaneBatch

    with pytest.raises(ValueError, match="per-lane"):
        LaneBatch(Problem(M=64, N=64), 2, preconditioner="mg",
                  multi_geometry=True)


def test_batched_mg_rejects_geometries():
    from poisson_tpu.geometry import Ellipse
    from poisson_tpu.solvers.batched import solve_batched

    with pytest.raises(ValueError, match="co-batch"):
        solve_batched(Problem(M=64, N=64), rhs_gates=[1.0],
                      preconditioner="mg",
                      geometries=[Ellipse(cx=0, cy=0, rx=0.5, ry=0.3)])


def test_chunked_and_resilient_mg_bitwise_vs_one_shot():
    from poisson_tpu.solvers.checkpoint import pcg_solve_chunked
    from poisson_tpu.solvers.resilient import pcg_solve_resilient

    p = Problem(M=64, N=64)
    one = pcg_solve(p, dtype=jnp.float32, preconditioner="mg")
    ch = pcg_solve_chunked(p, chunk=3, dtype=jnp.float32,
                           preconditioner="mg")
    assert bool(jnp.all(ch.w == one.w))
    assert int(ch.iterations) == int(one.iterations)
    rs = pcg_solve_resilient(p, chunk=4, dtype=jnp.float32,
                             preconditioner="mg")
    assert bool(jnp.all(rs.w == one.w))
    assert rs.restarts == 0


def test_checkpoint_fingerprint_refuses_cross_preconditioner_resume(
        tmp_path):
    """A Jacobi-written state must never resume under MG (two different
    Krylov recurrences): the fingerprint carries the preconditioner."""
    from poisson_tpu.solvers.checkpoint import pcg_solve_checkpointed

    p = Problem(M=40, N=40)
    path = str(tmp_path / "ck.npz")
    pcg_solve_checkpointed(p, path, chunk=10, keep_checkpoint=True)
    with pytest.raises(ValueError, match="different problem"):
        pcg_solve_checkpointed(p, path, chunk=10, keep_checkpoint=True,
                               preconditioner="mg")


# -- integrity: re-measured guard ratios --------------------------------


def test_mg_verified_clean_solve_no_false_alarms():
    """The MG-calibrated collapse/jump ratios: a clean verified MG
    solve keeps its unverified iteration count with zero integrity
    verdicts — the Jacobi-calibrated ratios WOULD false-alarm here
    (clean MG one-step drops measure up to ~29×, see
    integrity.probe.DEFAULT_VERIFY_COLLAPSE_MG)."""
    p = Problem(M=100, N=150)   # the worst measured clean collapse grid
    plain = pcg_solve(p, dtype=jnp.float32, preconditioner="mg")
    ver = pcg_solve(p, dtype=jnp.float32, preconditioner="mg",
                    verify_every=3)
    assert int(ver.flag) == FLAG_CONVERGED
    assert int(ver.iterations) == int(plain.iterations)


def test_jacobi_ratios_would_false_alarm_on_clean_mg():
    """The re-measurement mattered: the same clean solve run with the
    Jacobi collapse ratio trips the guard — direction pin that the
    preconditioner-specific calibration is load-bearing."""
    from poisson_tpu.integrity.probe import (
        DEFAULT_VERIFY_COLLAPSE,
        DEFAULT_VERIFY_COLLAPSE_MG,
        default_verify_collapse,
    )

    assert default_verify_collapse("mg") == DEFAULT_VERIFY_COLLAPSE_MG
    assert default_verify_collapse("jacobi") == DEFAULT_VERIFY_COLLAPSE
    assert DEFAULT_VERIFY_COLLAPSE_MG > 28.6   # the measured clean max
    assert DEFAULT_VERIFY_COLLAPSE < 28.6      # jacobi's line is below


def test_mg_resilient_detects_bitflip_and_recovers():
    import warnings

    from poisson_tpu.obs import metrics
    from poisson_tpu.solvers.resilient import pcg_solve_resilient
    from poisson_tpu.testing.faults import bitflip_per_solve_hook

    metrics.reset()
    p = Problem(M=64, N=64)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        r = pcg_solve_resilient(
            p, chunk=2, verify_every=1, preconditioner="mg",
            on_chunk=bitflip_per_solve_hook(4, buffer="w", seed=1))
    assert int(r.flag) == FLAG_CONVERGED
    assert r.restarts >= 1
    assert metrics.get("integrity.detections") >= 1
    assert metrics.get("integrity.verified_restarts") >= 1
    assert metrics.get("resilient.escalations") == 0


# -- hierarchy cache + cost model ---------------------------------------


def test_hierarchy_cache_counters():
    from poisson_tpu.obs import metrics

    metrics.reset()
    reset_hierarchy_cache()
    p = Problem(M=40, N=40)
    device_hierarchy(p, "float32", True)
    device_hierarchy(p, "float32", True)
    device_hierarchy(p.with_(f_val=2.0), "float32", True)  # normalized
    assert metrics.get("mg.hierarchy_cache.misses") == 1
    assert metrics.get("mg.hierarchy_cache.hits") == 2


def test_mg_setup_builds_the_fine_canvases_once(monkeypatch):
    """``mg_solve_setup`` derives the solve's fields and the hierarchy
    from one host fp64 build of the fine canvases (a grid no other test
    solves, so no cache holds it yet), and the hierarchy is the one
    ``build_hierarchy64`` makes from those canvases."""
    from poisson_tpu.mg import build_hierarchy64
    from poisson_tpu.mg.preconditioner import mg_solve_setup
    from poisson_tpu.solvers import pcg as pcg_module

    builds = []
    real = pcg_module.build_fields
    monkeypatch.setattr(pcg_module, "build_fields",
                        lambda *a, **k: builds.append(a[0]) or real(*a, **k))
    p = Problem(M=60, N=92)
    a, b, rhs, aux, hier = mg_solve_setup(p, "float32", True)
    assert builds == [p]
    a64, b64, _, _ = pcg_module.host_fields64(p, True)
    host = build_hierarchy64(p, a64, b64)
    for dev, ref in zip(hier.levels, host["levels"], strict=True):
        for x, y in zip(dev, ref):
            assert np.array_equal(np.asarray(x), np.asarray(y, np.float32))


def test_mg_counters_survive_exposition():
    from poisson_tpu.obs import export, metrics

    metrics.reset()
    reset_hierarchy_cache()
    p = Problem(M=40, N=40)
    pcg_solve(p, preconditioner="mg")
    pcg_solve(p, preconditioner="mg")      # the second build is a hit
    parsed = export.parse_text(export.render())
    assert parsed["poisson_tpu_mg_solves"] == {"type": "counter",
                                               "value": 2.0}
    assert parsed["poisson_tpu_mg_hierarchy_cache_misses"]["value"] == 1
    assert parsed["poisson_tpu_mg_hierarchy_cache_hits"]["value"] >= 1
    assert parsed["poisson_tpu_mg_levels"]["type"] == "gauge"


def test_mg_vcycle_cost_model():
    from poisson_tpu.obs import metrics
    from poisson_tpu.obs.costs import mg_vcycle_cost

    metrics.reset()
    small = mg_vcycle_cost(100, 150, 4)
    large = mg_vcycle_cost(400, 600, 4)
    assert small["coarse_dense"] and large["coarse_dense"]
    assert large["bytes"] > small["bytes"]
    assert large["levels"] == 4
    assert metrics.snapshot()["gauges"]["cost.mg.bytes_per_cycle"] \
        == large["bytes"]
    # The dense coarse matvec is a constant term: fine-equivalent
    # passes SHRINK with resolution (the win grows at the large end).
    assert large["passes_fine_equivalent"] < small["passes_fine_equivalent"]


# -- serve: cohort split and outcomes -----------------------------------


@pytest.mark.parametrize("scheduling", ["drain", "continuous"])
def test_serve_mg_and_jacobi_cohorts_split(scheduling):
    from poisson_tpu.serve import (
        ServicePolicy,
        SolveRequest,
        SolveService,
    )

    p = Problem(M=32, N=32)
    svc = SolveService(ServicePolicy(capacity=16, max_batch=4,
                                     scheduling=scheduling), seed=0)
    for i in range(3):
        svc.submit(SolveRequest(request_id=f"m{i}", problem=p,
                                rhs_gate=1.0 + i / 10,
                                preconditioner="mg"))
        svc.submit(SolveRequest(request_id=f"j{i}", problem=p,
                                rhs_gate=1.0 + i / 10))
    outs = svc.drain()
    stats = svc.stats()
    assert stats["lost"] == 0
    by_id = {o.request_id: o for o in outs}
    for i in range(3):
        assert by_id[f"m{i}"].converged and by_id[f"j{i}"].converged
        # MG requests converge in far fewer iterations — and the split
        # cohort is visible in the breaker registry.
        assert by_id[f"m{i}"].iterations * 3 <= by_id[f"j{i}"].iterations
    assert "32x32:auto:xla:mg" in stats["breakers"]
    assert "32x32:auto:xla" in stats["breakers"]


def test_serve_submit_validates_mg_grid_loudly():
    from poisson_tpu.serve import (
        ServicePolicy,
        SolveRequest,
        SolveService,
    )

    svc = SolveService(ServicePolicy(), seed=0)
    with pytest.raises(ValueError, match="coarsens"):
        svc.submit(SolveRequest(request_id="bad", problem=Problem(M=33, N=33),
                                preconditioner="mg"))
    with pytest.raises(ValueError, match="unknown preconditioner"):
        svc.submit(SolveRequest(request_id="bad2", problem=Problem(M=32, N=32),
                                preconditioner="amg"))
    assert svc.stats()["admitted"] == 0   # rejected, never admitted


def test_serve_policy_default_preconditioner():
    from poisson_tpu.serve import (
        ServicePolicy,
        SolveRequest,
        SolveService,
    )

    p = Problem(M=32, N=32)
    svc = SolveService(ServicePolicy(capacity=8, max_batch=4,
                                     preconditioner="mg"), seed=0)
    svc.submit(SolveRequest(request_id="r0", problem=p))
    outs = svc.drain()
    assert outs[0].converged and outs[0].iterations <= 12
    assert "32x32:auto:xla:mg" in svc.stats()["breakers"]


# -- sentinel: cohort and direction pins --------------------------------


def _rec(value, preconditioner=None):
    detail = {"grid": [400, 600], "dtype": "float32", "platform": "cpu",
              "backend": "xla", "devices": 1}
    if preconditioner is not None:
        detail["preconditioner"] = preconditioner
    return {"metric": "mlups", "value": value, "detail": detail}


def test_sentinel_cohorts_split_by_preconditioner():
    """MG records never judge Jacobi baselines and vice versa: a slow
    MG run beside fast Jacobi history classifies no_baseline (its own
    cohort), never regression against the Jacobi records."""
    import benchmarks.regress as regress

    records = [regress.record_from_result(_rec(500.0), f"jac{i}")
               for i in range(3)]
    records.append(regress.record_from_result(_rec(4.0, "mg"), "mg0"))
    report = regress.evaluate(records)
    verdicts = {v["source"]: v["classification"] for v in report["records"]}
    assert verdicts["mg0"] == "no_baseline"
    assert report["verdict"] == "ok"


def test_sentinel_direction_pin_within_mg_cohort():
    """A genuinely slowed MG run IS caught — inside the MG cohort."""
    import benchmarks.regress as regress

    records = [regress.record_from_result(_rec(4.0, "mg"), f"mg{i}")
               for i in range(3)]
    records.append(regress.record_from_result(_rec(1.0, "mg"), "slow"))
    report = regress.evaluate(records)
    verdicts = {v["source"]: v["classification"] for v in report["records"]}
    assert verdicts["slow"] == "regression"
    assert report["verdict"] == "regression"


def test_bench_ab_detail_shape():
    """The A/B record contract bench.py emits: both arms present, the
    preconditioner in detail (the cohort key), never in the top level."""
    rec = _rec(4.0, "mg")
    rec["detail"]["preconditioner_ab"] = {
        "jacobi": {"iterations": 546}, "mg": {"iterations": 14}}
    import benchmarks.regress as regress

    out = regress.record_from_result(rec, "x")
    assert out["preconditioner"] == "mg"
    # The AB payload is diagnosis, not identity — it must not leak into
    # the cohort key (same rule as the flight-recorder exemplars).
    assert "preconditioner_ab" not in out
    key = regress.cohort_key(out)
    assert "mg" in key


# -- CLI validation (fast failure paths only) ---------------------------


def test_cli_rejects_mg_on_odd_grid():
    import subprocess
    import sys as _sys

    proc = subprocess.run(
        [_sys.executable, "-m", "poisson_tpu", "33", "33",
         "--preconditioner", "mg", "--backend", "xla"],
        capture_output=True, text=True,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert "coarsens" in proc.stderr


def test_cli_rejects_mg_on_pallas_backend():
    import subprocess
    import sys as _sys

    proc = subprocess.run(
        [_sys.executable, "-m", "poisson_tpu", "64", "64",
         "--preconditioner", "mg", "--backend", "pallas"],
        capture_output=True, text=True,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert "mg" in proc.stderr


@pytest.mark.slow
def test_mg_selfcheck_cli_smoke():
    import subprocess
    import sys as _sys

    proc = subprocess.run(
        [_sys.executable, "-m", "poisson_tpu.mg.selfcheck"],
        capture_output=True, text=True,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "mg selfcheck OK" in proc.stdout
