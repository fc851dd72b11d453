"""Native C++ oracle: golden counts, OpenMP behaviour, parity with JAX.

The reference's serial/OpenMP stages are native C++ compared empirically
across implementations (SURVEY §4.1); here the native backend and the
JAX/XLA backend are compared *in-process* — same golden iteration counts,
same solution to fp64 round-off.
"""

import numpy as np
import pytest

from poisson_tpu.config import Problem
from poisson_tpu.native import build, has_openmp, native_solve
from poisson_tpu.solvers.pcg import pcg_solve


def test_build_produces_library():
    path = build()
    assert path.endswith(".so")


def test_library_is_keyed_on_source_and_command(tmp_path, monkeypatch):
    """A library built from other source (say, one copied in with the
    tree) or with other flags is never the one loaded."""
    import poisson_tpu.native as native

    base = native.library_path()
    assert build() == base
    src = tmp_path / "poisson_oracle.cpp"
    src.write_text(open(native._SRC).read() + "\n// edited\n")
    monkeypatch.setattr(native, "_SRC", str(src))
    assert native.library_path() != base
    monkeypatch.undo()
    monkeypatch.setenv("CXXFLAGS", "-O1")
    assert native.library_path() != base


@pytest.mark.parametrize(
    "M,N,weighted,expected",
    [
        (10, 10, False, 17),
        (20, 20, False, 31),
        (40, 40, False, 61),
        (40, 40, True, 50),
    ],
)
def test_native_golden_iterations(M, N, weighted, expected):
    # num_threads=1: exact counts need a fixed reduction order (the default
    # team is machine- and test-order-dependent).
    r = native_solve(Problem(M=M, N=N, weighted_norm=weighted), num_threads=1)
    assert r.iterations == expected
    assert r.diff < 1e-6


def test_native_matches_jax_fp64():
    """Cross-backend equivalence: the reference's only correctness method
    (SURVEY §4.1), automated. Summation order differs (sequential vs XLA
    tree reduction), so parity is to round-off, not bitwise."""
    p = Problem(M=40, N=40)
    rn = native_solve(p, num_threads=1)
    rj = pcg_solve(p)
    assert rn.iterations == int(rj.iterations)
    np.testing.assert_allclose(rn.w, np.asarray(rj.w), rtol=0, atol=1e-10)


def test_native_openmp_thread_counts_agree():
    """The stage1 experiment (thread sweep, same answer): iteration count
    is reduction-order sensitive only within one ulp of delta, so allow ±1;
    solutions must agree to round-off."""
    if not has_openmp():
        pytest.skip("library built without OpenMP")
    p = Problem(M=40, N=40)
    base = native_solve(p, num_threads=1)
    for t in (2, 4):
        r = native_solve(p, num_threads=t)
        assert abs(r.iterations - base.iterations) <= 1
        np.testing.assert_allclose(r.w, base.w, rtol=0, atol=1e-10)


@pytest.mark.slow
def test_native_golden_400x600():
    # 4-thread reduction order is nondeterministic; the count is exact at a
    # fixed order and can flip by one ulp otherwise (see thread-sweep test).
    r = native_solve(Problem(M=400, N=600), num_threads=4)
    assert abs(r.iterations - 546) <= 1


@pytest.mark.parametrize("M,N", [(2, 2), (2, 10), (10, 2), (3, 200)])
def test_edge_grids_agree_with_jax(M, N):
    """Degenerate-direction and iteration-cap semantics on minimal grids:
    tiny interiors exhaust the Krylov space (exact solve) or hit the
    (M-1)(N-1) cap — both backends must stop identically."""
    p = Problem(M=M, N=N)
    rn = native_solve(p, num_threads=1)
    rj = pcg_solve(p)
    assert rn.iterations == int(rj.iterations)
    np.testing.assert_allclose(rn.w, np.asarray(rj.w), rtol=0, atol=1e-10)


@pytest.mark.xslow
@pytest.mark.parametrize(
    "M,N,expected", [(1600, 2400, 1858), (2400, 3200, 2449)]
)
def test_native_golden_largest_grids(M, N, expected):
    """The two largest published grids (BASELINE.md, Этап_4_1213.pdf
    Table 1). ~2-3 min each on CPU."""
    import os

    r = native_solve(Problem(M=M, N=N), num_threads=os.cpu_count())
    assert abs(r.iterations - expected) <= 1
