"""The member-axis Pallas kernels behind ``solve_batched`` on a TPU
(``ops.pallas_cg._fused_solve_batched``), run here in interpret mode.

Member i of a batched fused solve is the one-RHS fused solve of its gate,
bit for bit (w, iterations, diff, residual_dot), on the same canvas: a
member that stops is frozen with α = β = 0, which leaves its w and r
exactly as they were, and its strip partials are summed as the one-RHS
loop sums its own. Flags follow the XLA batched loop. The dispatch rule
is a pure function of the request, so it is tested for every family
without a chip; ``solve_batched`` itself is driven down the fused path by
pretending the platform is a TPU.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from poisson_tpu.config import Problem
from poisson_tpu.obs import metrics
from poisson_tpu.ops import pallas_cg
from poisson_tpu.solvers import batched
from poisson_tpu.solvers.pcg import (
    FLAG_BREAKDOWN,
    FLAG_CONVERGED,
    FLAG_NONE,
    FLAG_NONFINITE,
    host_fields64,
)

pytestmark = pytest.mark.batched

# Gates that converge at different k, and a zero right-hand side.
GATES = (1.0, 0.5, 0.0, 2.0, 1.3)


@pytest.fixture(autouse=True)
def _fresh_bucket_cache():
    batched.reset_bucket_cache()
    yield
    batched.reset_bucket_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """``solve_batched`` sees a TPU (the kernels still run interpreted)."""
    monkeypatch.setattr(batched, "_platform", lambda: "tpu")


def _batched(problem, gates, bm):
    """The batched fused loop called directly, on ``bm``-row strips."""
    cv, cs, cw, g, rhs, sc2, sc_int = pallas_cg.build_canvases(problem, bm)
    stack = rhs[None] * jnp.asarray(gates, rhs.dtype)[:, None, None]
    return pallas_cg._fused_solve_batched(problem, cv, True, cs, cw, g,
                                          stack, sc2, sc_int)


def _single(problem, bm, gate):
    return pallas_cg.pallas_cg_solve(problem, bm=bm, rhs_gate=gate)


def _assert_member_is(res, i, one):
    np.testing.assert_array_equal(np.asarray(res.w[i]), np.asarray(one.w))
    assert int(res.iterations[i]) == int(one.iterations)
    assert np.asarray(res.diff[i]).tobytes() == np.asarray(one.diff).tobytes()
    assert (np.asarray(res.residual_dot[i]).tobytes()
            == np.asarray(one.residual_dot).tobytes())


@pytest.mark.parametrize("M,N,bm", [(40, 60, 16), (48, 40, 8)])
def test_members_bit_identical_to_the_single_fused_solve(M, N, bm):
    problem = Problem(M=M, N=N)
    assert pallas_cg.canvas_spec(problem, bm).nb >= 2
    res = _batched(problem, GATES, bm)
    for i, gate in enumerate(GATES):
        _assert_member_is(res, i, _single(problem, bm, gate))
    iters = [int(k) for k in np.asarray(res.iterations)]
    flags = [int(f) for f in np.asarray(res.flag)]
    # The gates spread the counts, so frozen members are exercised.
    assert len(set(iters)) >= 4
    assert iters[2] == 1 and flags[2] == FLAG_BREAKDOWN
    assert all(f == FLAG_CONVERGED for i, f in enumerate(flags) if i != 2)
    assert int(res.max_iterations) == max(iters)
    assert res.w.shape == (len(GATES), M + 1, N + 1)


def test_nan_member_stops_nonfinite_and_leaves_batchmates_alone():
    problem = Problem(M=40, N=60)
    gates = (1.0, float("nan"), 2.0)
    res = _batched(problem, gates, 16)
    xla = batched.solve_batched(problem, rhs_gates=gates, dtype="float32")
    assert int(res.flag[1]) == int(xla.flag[1]) == FLAG_NONFINITE
    assert int(res.iterations[1]) == int(xla.iterations[1]) == 1
    for i in (0, 2):
        _assert_member_is(res, i, _single(problem, 16, gates[i]))


def test_cap_freezes_members_with_the_xla_flags():
    problem = Problem(M=40, N=60, max_iter=30)
    res = _batched(problem, (1.0, 0.0), 16)
    xla = batched.solve_batched(problem, rhs_gates=(1.0, 0.0),
                                dtype="float32")
    assert [int(k) for k in np.asarray(res.iterations)] == [30, 1]
    assert [int(f) for f in np.asarray(res.flag)] == [FLAG_NONE,
                                                      FLAG_BREAKDOWN]
    assert [int(f) for f in np.asarray(xla.flag)] == [FLAG_NONE,
                                                      FLAG_BREAKDOWN]
    _assert_member_is(res, 0, _single(problem, 16, 1.0))


def test_batched_bm_cuts_the_strips_to_the_interior():
    problem = Problem(M=400, N=600)
    assert pallas_cg.pick_bm(problem) == 128
    assert pallas_cg.batched_bm(problem) == 104
    tight = pallas_cg.canvas_spec(problem, pallas_cg.batched_bm(problem))
    assert tight.nb == pallas_cg.canvas_spec(problem).nb == 4
    assert tight.rows == 4 * 104 + 2 * pallas_cg.HALO


def test_solve_batched_dispatches_fused_and_slices_padding(on_tpu):
    problem = Problem(M=40, N=60)
    gates = (1.0, 0.5, 2.0)
    metrics.reset()
    res = batched.solve_batched(problem, rhs_gates=gates, dtype="float32",
                                member_ids=("a", "b", "c"))
    assert metrics.get("batched.fused.dispatches") == 1
    assert metrics.get("batched.fused.members") == 3
    assert metrics.get("batched.padding_members") == 1     # bucket 4
    assert metrics.get("batched.bucket_cache.misses") == 1
    assert res.origin == ("a", "b", "c")
    assert res.w.shape == (3, 41, 61)
    for field in (res.iterations, res.diff, res.residual_dot, res.flag):
        assert np.asarray(field).shape == (3,)
    bm = pallas_cg.batched_bm(problem)
    for i, gate in enumerate(gates):
        _assert_member_is(res, i, _single(problem, bm, gate))
    assert int(res.max_iterations) == max(
        int(k) for k in np.asarray(res.iterations))
    batched.solve_batched(problem, rhs_gates=(1.0, 1.0, 1.0, 1.0),
                          dtype="float32")
    assert metrics.get("batched.bucket_cache.hits") == 1
    assert metrics.get("batched.fused.dispatches") == 2


def test_every_input_form_reaches_the_fused_path(on_tpu):
    """f = 2 and a gate of 2 scale the RHS exactly (a power of two), and a
    physical stack of f·1[D] scales onto the same canvas, so all three
    forms solve the same members."""
    problem = Problem(M=40, N=60)
    by_gate = batched.solve_batched(problem, rhs_gates=(1.0, 2.0),
                                    dtype="float32")
    by_problem = batched.solve_batched(
        [problem, problem.with_(f_val=2.0)], dtype="float32")
    physical = host_fields64(problem, False)[2]
    by_stack = batched.solve_batched(
        problem, rhs_stack=np.stack([physical, 2.0 * physical]),
        dtype="float32")
    for other in (by_problem, by_stack):
        np.testing.assert_array_equal(np.asarray(other.iterations),
                                      np.asarray(by_gate.iterations))
        np.testing.assert_allclose(np.asarray(other.w),
                                   np.asarray(by_gate.w), rtol=1e-5,
                                   atol=1e-9)
    np.testing.assert_array_equal(np.asarray(by_problem.w),
                                  np.asarray(by_gate.w))


PLAIN = dict(mesh=None, geometries=None, mg=False, block=False,
             verify_every=0)


@pytest.mark.parametrize("platform,dtype,scaled,family,fused", [
    ("tpu", "float32", True, {}, True),
    ("cpu", "float32", True, {}, False),
    ("gpu", "float32", True, {}, False),
    ("tpu", "float64", False, {}, False),
    ("tpu", "float64", True, {}, False),
    ("tpu", "float32", False, {}, False),
    ("tpu", "float32", True, {"mesh": object()}, False),
    ("tpu", "float32", True, {"geometries": [None]}, False),
    ("tpu", "float32", True, {"mg": True}, False),
    ("tpu", "float32", True, {"block": True}, False),
    ("tpu", "float32", True, {"verify_every": 25}, False),
])
def test_dispatch_rule(platform, dtype, scaled, family, fused):
    kw = dict(PLAIN, **family)
    assert batched.uses_fused_kernels(platform, dtype, scaled,
                                      **kw) is fused


def test_families_keep_the_xla_program_on_a_tpu(on_tpu):
    """The families outside the rule dispatch exactly as before, even
    where the platform is a TPU."""
    problem = Problem(M=20, N=20)
    metrics.reset()
    batched.solve_batched(problem, rhs_gates=(1.0, 2.0), dtype="float32",
                          verify_every=5)
    batched.solve_batched(problem, rhs_gates=(1.0, 2.0), dtype="float32",
                          scaled=False)
    batched.solve_batched(problem, rhs_gates=(1.0, 2.0), dtype="float32",
                          geometries=[None, None])
    assert metrics.get("batched.fused.dispatches") == 0
    assert metrics.get("batched.bucket_cache.misses") == 3
