"""The V-cycle's Pallas strip kernels (``ops.pallas_mg``), interpreted.

Each kernel is held to the XLA composition it replaces, on the
fictitious-domain coefficients of a real grid: ``mg_presmooth_residual``
to ``smooth_jacobi`` from zero and ``r − apply_A(x)``, ``mg_postsmooth``
to ``x + e`` and ``smooth_jacobi``. The arithmetic is the same and only
the order of fp32 rounding may differ, so the gap is held to 1e-5 of the
largest value, and the Dirichlet ring to exactly zero. The engagement
rule (``mg.hierarchy.kernel_levels``) is a pure function of platform,
dtype and level shapes, so it is tested here for every case; the solo
program is driven onto the kernels by pretending the platform is a TPU
and lowering the size threshold.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poisson_tpu.config import Problem
from poisson_tpu.mg import (
    DEFAULT_MG,
    MGConfig,
    device_hierarchy,
    hierarchy,
    plan_levels,
    reset_hierarchy_cache,
    smooth_jacobi,
    v_cycle,
)
from poisson_tpu.mg.hierarchy import kernel_levels, with_strips
from poisson_tpu.mg.preconditioner import (
    _member_init_mg,
    _solve_batched_mg,
    _solve_mg,
    _step_lanes_mg,
    mg_solve_setup,
)
from poisson_tpu.obs import metrics
from poisson_tpu.ops import pallas_mg
from poisson_tpu.ops.pallas_cg import HALO
from poisson_tpu.ops.stencil import apply_A, diag_D
from poisson_tpu.solvers.pcg import host_fields64, pcg_solve

pytestmark = pytest.mark.mg

OMEGA = DEFAULT_MG.omega
# (M, N, strip height): one strip; four strips of 16 rows over the 64
# grid columns; 71 grid columns that 24-row strips do not divide.
SHAPES = [(40, 60, None), (40, 63, 16), (36, 70, 24)]
IDS = ["one-strip", "four-strips", "ragged-last-strip"]
TOL = 1e-5


def _level(M, N, seed=0):
    """fp32 (a, b, dinv, r, e) of the M×N ellipse canvases, with a
    residual r and a correction e that vanish on the ring."""
    problem = Problem(M=M, N=N)
    a64, b64, _, _ = host_fields64(problem, False)
    dinv64 = np.pad(1.0 / diag_D(a64, b64, problem.h1, problem.h2), 1)
    rng = np.random.default_rng(seed)
    r64, e64 = (np.pad(rng.standard_normal((M - 1, N - 1)), 1)
                for _ in range(2))
    fields = (jnp.asarray(u, jnp.float32) for u in (a64, b64, dinv64, r64,
                                                    e64))
    return problem, *fields


def _gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _ring(u):
    return np.concatenate([np.asarray(u[0]), np.asarray(u[-1]),
                           np.asarray(u[:, 0]), np.asarray(u[:, -1])])


def _presmooth(sg, problem, a, b, dinv, r, sweeps):
    xt, rest = pallas_mg.mg_presmooth_residual(
        sg, r.T, a.T, b.T, dinv.T, problem.h1, problem.h2, sweeps, OMEGA,
        interpret=True)
    return xt.T, rest.T


@pytest.mark.parametrize("M,N,bm", SHAPES, ids=IDS)
def test_presmooth_residual_matches_the_xla_composition(M, N, bm):
    problem, a, b, dinv, r, _ = _level(M, N)
    sg = pallas_mg.strip_grid(M, N, bm)
    assert sg.nb == (1 if bm is None else -(-(N + 1) // bm))
    x, res = _presmooth(sg, problem, a, b, dinv, r, DEFAULT_MG.pre_smooth)
    x_ref = smooth_jacobi(None, r, a, b, dinv, problem.h1, problem.h2,
                          DEFAULT_MG.pre_smooth, OMEGA, from_zero=True)
    res_ref = r - apply_A(x_ref, a, b, problem.h1, problem.h2)
    assert x.dtype == res.dtype == jnp.float32
    assert _gap(x, x_ref) <= TOL and _gap(res, res_ref) <= TOL
    assert not _ring(x).any() and not _ring(res).any()


@pytest.mark.parametrize("M,N,bm", SHAPES, ids=IDS)
def test_postsmooth_matches_the_xla_composition(M, N, bm):
    problem, a, b, dinv, r, e = _level(M, N, seed=1)
    sg = pallas_mg.strip_grid(M, N, bm)
    x, _ = _presmooth(sg, problem, a, b, dinv, r, DEFAULT_MG.pre_smooth)
    out = pallas_mg.mg_postsmooth(
        sg, x.T, e.T, r.T, a.T, b.T, dinv.T, problem.h1, problem.h2,
        DEFAULT_MG.post_smooth, OMEGA, interpret=True).T
    want = smooth_jacobi(x + e, r, a, b, dinv, problem.h1, problem.h2,
                         DEFAULT_MG.post_smooth, OMEGA)
    assert _gap(out, want) <= TOL
    assert not _ring(out).any()


def test_the_correction_is_folded_in_exactly():
    """With no sweep, the post-smoother returns x + e bit for bit, and
    one pre-sweep is the closed form ω·D⁻¹r bit for bit."""
    problem, a, b, dinv, r, e = _level(36, 70, seed=2)
    sg = pallas_mg.strip_grid(36, 70, 24)
    x, _ = _presmooth(sg, problem, a, b, dinv, r, 1)
    np.testing.assert_array_equal(
        x, smooth_jacobi(None, r, a, b, dinv, problem.h1, problem.h2, 1,
                         OMEGA, from_zero=True))
    out = pallas_mg.mg_postsmooth(
        sg, x.T, e.T, r.T, a.T, b.T, dinv.T, problem.h1, problem.h2, 0,
        OMEGA, interpret=True).T
    np.testing.assert_array_equal(out, x + e)


def test_sweeps_beyond_the_halo_are_refused():
    problem, a, b, dinv, r, _ = _level(40, 60)
    with pytest.raises(ValueError, match="sweeps"):
        _presmooth(pallas_mg.strip_grid(40, 60), problem, a, b, dinv, r,
                   HALO + 1)


def test_kernel_levels_rule():
    """The 6400×9600 plan of ``ellipse-6400x9600-mg`` puts its two finest
    levels on the kernels on a TPU in fp32, and no level anywhere else."""
    plan = plan_levels(6400, 9600)
    assert len(plan) == 8
    assert kernel_levels("tpu", "float32", plan) == 2
    assert kernel_levels("cpu", "float32", plan) == 0
    assert kernel_levels("tpu", "float64", plan) == 0
    assert kernel_levels("tpu", "float32", plan,
                         MGConfig(pre_smooth=HALO + 1)) == 0
    # The published grids are too small to be bandwidth-bound.
    assert kernel_levels("tpu", "float32", plan_levels(2400, 3200)) == 0


def test_cpu_hierarchy_carries_no_strips():
    reset_hierarchy_cache()
    try:
        hier = device_hierarchy(Problem(M=40, N=60), "float32", True)
        assert hier.strips == ()
    finally:
        reset_hierarchy_cache()


def test_vcycle_on_the_kernels_matches_the_xla_cycle():
    problem = Problem(M=40, N=60)
    hier = device_hierarchy(problem, "float32", True)
    rng = np.random.default_rng(3)
    r = jnp.pad(jnp.asarray(rng.standard_normal((39, 59)), jnp.float32), 1)
    k = len(hier.levels) - 1
    got = v_cycle(with_strips(hier, k), r, problem.h1, problem.h2,
                  DEFAULT_MG, kernel_levels=k, interpret=True)
    want = v_cycle(hier, r, problem.h1, problem.h2, DEFAULT_MG)
    assert _gap(got, want) <= TOL
    assert not _ring(got).any()


def _twin_jaxpr(twin):
    problem = Problem(M=40, N=60)
    a, b, rhs, aux, hier = mg_solve_setup(problem, "float32", True)
    hier = with_strips(hier, 1)
    stack = jnp.stack([rhs, rhs])
    if twin == "solo":
        call = lambda: _solve_mg(problem, True, DEFAULT_MG, 0, 0, 0.0, a, b,
                                 rhs, aux, hier, interpret=True)
    elif twin == "batched":
        call = lambda: _solve_batched_mg(problem, True, DEFAULT_MG, 0, 0.0,
                                         a, b, stack, aux, hier)
    elif twin == "member_init":
        call = lambda: _member_init_mg(problem, True, DEFAULT_MG, a, b, aux,
                                       hier, rhs)
    else:
        state = jax.vmap(lambda x: _member_init_mg(
            problem, True, DEFAULT_MG, a, b, aux, hier, x))(stack)
        call = lambda: _step_lanes_mg(problem, True, 4, DEFAULT_MG, 0, 0.0,
                                      a, b, aux, hier, None, state)
    return str(jax.make_jaxpr(call)())


@pytest.mark.parametrize("twin", ["batched", "member_init", "lanes"])
def test_vmapped_twins_keep_the_xla_cycle(twin):
    """The twins ignore ``hier.strips``, so the V-cycle's bit parity
    under ``vmap`` (tests/test_mg.py) holds on every platform."""
    assert "pallas_call" not in _twin_jaxpr(twin)


def test_solo_program_takes_both_kernels():
    text = _twin_jaxpr("solo")
    for name in ("mg_presmooth_residual", "mg_postsmooth"):
        assert name in text


@pytest.fixture
def kernels_everywhere(monkeypatch):
    """The hierarchy sees a TPU and takes every level it can; the kernels
    still run interpreted."""
    monkeypatch.setattr(hierarchy, "_platform", lambda: "tpu")
    monkeypatch.setattr(pallas_mg, "MIN_STRIP_LEVEL_BYTES", 0)
    reset_hierarchy_cache()
    yield
    reset_hierarchy_cache()


def _solve(problem):
    return pcg_solve(problem, dtype="float32", preconditioner="mg")


def test_pcg_solve_sets_the_gauge(kernels_everywhere, monkeypatch):
    problem = Problem(M=40, N=60)
    on_kernels = _solve(problem)
    levels = len(plan_levels(40, 60))
    assert metrics.snapshot(rank=0)["gauges"]["mg.pallas_levels"] == (
        levels - 1)
    monkeypatch.setattr(hierarchy, "_platform", lambda: "cpu")
    reset_hierarchy_cache()
    on_xla = _solve(problem)
    assert metrics.snapshot(rank=0)["gauges"]["mg.pallas_levels"] == 0
    assert int(on_kernels.iterations) == int(on_xla.iterations)
    assert _gap(on_kernels.w, on_xla.w) <= 1e-4
