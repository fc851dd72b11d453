"""Plain reference for the multigrid-preconditioned ellipse configuration:
CG preconditioned by one geometric V(2,2) cycle per iteration, written from
the published description and importing nothing of the program under test.

Problem and discretisation: those of ``ellipse_pcg.py`` (BASELINE.md), whose
``host_fields`` gives the level-0 couplings and right-hand side.

V-cycle (Briggs, Henson & McCormick, "A Multigrid Tutorial", 2nd ed.,
ch. 3-4 and 10):

- levels: vertex-centred factor-2 coarsening, (m, n) -> (m/2, n/2), while
  both are even and min(m, n)/2 >= 10 (16 levels at most;
  ``perf/work_mg.levels``); level l has the spacings (2^l h1, 2^l h2);
- coefficients: a coarse face averages the fine faces it covers, the two
  in-line faces in series by their arithmetic mean, and the three
  transverse fine lines its doubled length spans with the weights
  1/4, 1/2, 1/4. The coarse operator is the same 5-point formula on those
  faces at the coarse spacings;
- smoother: weighted Jacobi x <- x + omega D^-1 (r - A x), omega = 0.8, two
  sweeps down (the first from x = 0: x = omega D^-1 r) and two up;
- transfers: full weighting, the separable [1 2 1]/4 x [1 2 1]/4 filter
  sampled at the even fine nodes; bilinear prolongation, linear
  interpolation along each axis in turn (a fine node on a coarse one
  copies it, one between two takes their mean);
- coarsest level: its operator as a dense matrix, inverted once on the host
  in float64, symmetrised (inv + inv^T)/2, cast once, applied as a
  matrix-vector product at the highest matmul precision.

CG (stage 2's solve_mpi, as ``ellipse_pcg.py``): w0 = 0, r0 = B,
z0 = V(r0), p0 = z0; each step alpha = (z, r)/(Ap, p), w += alpha p,
r -= alpha Ap, diff = ||alpha p|| in the norm sqrt(h1 h2 sum(.^2)),
z = V(r), stop once diff < delta (the step counts); else
beta = (z', r')/(z, r), p = z + beta p. A direction with
|(Ap, p)| < 1e-15 stops the loop.

Where the program differs, and why the answers still agree:

- the program runs CG on the diagonally scaled system D^-1/2 A D^-1/2 with
  the cycle wrapped as sqrt(d) V(sqrt(d) r); this runs it on A itself.
  The two are the same iteration under y = D^1/2 w, so they agree to
  rounding, and not to the same rounding: in float32 at 1600x2400 this
  field is 2.2e-4 off the float64 answer (of max|w|), the program's
  scaled one 1.3e-3 (it rounds the products D^-1/2 p before the operator
  differences them), so the gap the check reads is mostly the program's;
- the program applies the coarsest inverse as a broadcast multiply and a
  row sum (for its batched bit parity); this is a matrix-vector product;
- the program works on full (M+1, N+1) grids with a zero ring; this on the
  interior, padding where a neighbour is read.

Every array and every scalar is held in ``dtype``: float32 is the
configuration's stated precision; bfloat16 is the control that has to come
out as not correct. Coefficients and the coarsest inverse are derived in
float64 on the host and cast once.
"""

from __future__ import annotations

import functools
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perf import work_mg

OMEGA = 0.8
PRE_SWEEPS = POST_SWEEPS = 2
_DENOM_TOL = 1e-15


def _pcg_module():
    from perf.entry import load_module

    return load_module(pathlib.Path(__file__).with_name("ellipse_pcg.py"))


def coarsen_faces(fa, fb):
    """Coarse faces from fine ones. ``fa`` holds the x-faces a[i, j]
    (i = 1..m, j = 1..n-1), ``fb`` the y-faces b[i, j] (i = 1..m-1,
    j = 1..n), each at [i - 1, j - 1]."""
    # x-faces: the fine pair (2I-1, 2I) in series, then columns
    # 2J-1, 2J, 2J+1 weighted 1/4, 1/2, 1/4 (J = 1..n/2-1).
    pair = 0.5 * (fa[0::2, :] + fa[1::2, :])
    ca = 0.25 * pair[:, 0:-2:2] + 0.5 * pair[:, 1:-1:2] + 0.25 * pair[:, 2::2]
    # y-faces: the same with the axes swapped.
    pair = 0.5 * (fb[:, 0::2] + fb[:, 1::2])
    cb = 0.25 * pair[0:-2:2, :] + 0.5 * pair[1:-1:2, :] + 0.25 * pair[2::2, :]
    return ca, cb


def couplings(fa, fb, h1: float, h2: float):
    """(cN, cS, cE, cW) on the interior from the faces at spacing h1, h2."""
    return (fa[1:, :] / (h1 * h1), fa[:-1, :] / (h1 * h1),
            fb[:, 1:] / (h2 * h2), fb[:, :-1] / (h2 * h2))


def dense_operator(c_n, c_s, c_e, c_w):
    """The 5-point operator on an interior of shape c_n.shape, row-major
    over (i, j), as a dense float64 matrix."""
    mi, nj = c_n.shape
    idx = np.arange(mi * nj).reshape(mi, nj)
    A = np.zeros((mi * nj, mi * nj))
    A[idx, idx] = c_n + c_s + c_e + c_w
    A[idx[:-1, :], idx[1:, :]] = -c_n[:-1, :]
    A[idx[1:, :], idx[:-1, :]] = -c_s[1:, :]
    A[idx[:, :-1], idx[:, 1:]] = -c_e[:, :-1]
    A[idx[:, 1:], idx[:, :-1]] = -c_w[:, 1:]
    return A


def host_levels(problem: dict):
    """float64 per-level couplings (cN, cS, cE, cW), the coarsest dense
    inverse, and the level-0 right-hand side."""
    M, N = problem["M"], problem["N"]
    width = problem["x_max"] - problem["x_min"]
    height = problem["y_max"] - problem["y_min"]
    c_n, c_s, c_e, c_w, rhs = _pcg_module().host_fields(problem)
    h1, h2 = width / M, height / N
    # The faces back from the couplings: rows 1..M of a, columns 1..N of b.
    fa = np.concatenate([c_s, c_n[-1:, :]], axis=0) * (h1 * h1)
    fb = np.concatenate([c_w, c_e[:, -1:]], axis=1) * (h2 * h2)
    levels = []
    dims = work_mg.levels(M, N)
    for lvl, (m, n) in enumerate(dims):
        if lvl:
            fa, fb = coarsen_faces(fa, fb)
        levels.append(couplings(fa, fb, width / m, height / n))
    inv = np.linalg.inv(dense_operator(*levels[-1]))
    return levels, 0.5 * (inv + inv.T), rhs


def _apply(c, u):
    c_n, c_s, c_e, c_w = c
    up = jnp.pad(u, 1)
    return ((c_n + c_s + c_e + c_w) * u - c_n * up[2:, 1:-1]
            - c_s * up[:-2, 1:-1] - c_e * up[1:-1, 2:] - c_w * up[1:-1, :-2])


def _restrict(r):
    """Full weighting: interior (m-1, n-1) -> (m/2-1, n/2-1)."""
    up = jnp.pad(r, 1)
    fx = 0.25 * (up[:-2, :] + 2.0 * up[1:-1, :] + up[2:, :])[1::2, :]
    return 0.25 * (fx[:, :-2] + 2.0 * fx[:, 1:-1] + fx[:, 2:])[:, 1::2]


def _interpolate_rows(ep):
    """Linear interpolation down the rows of ``ep`` (its first and last
    rows the zero boundary, mc + 1 in all) onto the fine interior rows
    1..2mc-1: odd fine rows the mean of their two coarse neighbours, even
    ones the coarse row they sit on."""
    odd = 0.5 * (ep[:-1] + ep[1:])
    pairs = jnp.stack([odd[:-1], ep[1:-1]], axis=1)
    pairs = pairs.reshape((2 * pairs.shape[0],) + ep.shape[1:])
    return jnp.concatenate([pairs, odd[-1:]], axis=0)


def _prolong(e):
    """Bilinear: interior (mc-1, nc-1) -> (2mc-1, 2nc-1), one axis at a
    time."""
    rows = _interpolate_rows(jnp.pad(e, 1))
    return _interpolate_rows(rows.T).T


def _vcycle(levels, coarse_inv, r, lvl=0):
    c = levels[lvl]
    if lvl == len(levels) - 1:
        e = jnp.dot(coarse_inv, r.reshape(-1))
        return e.reshape(r.shape)
    diag = c[0] + c[1] + c[2] + c[3]
    x = OMEGA * r / diag
    for _ in range(PRE_SWEEPS - 1):
        x = x + OMEGA * (r - _apply(c, x)) / diag
    x = x + _prolong(_vcycle(levels, coarse_inv,
                             _restrict(r - _apply(c, x)), lvl + 1))
    for _ in range(POST_SWEEPS):
        x = x + OMEGA * (r - _apply(c, x)) / diag
    return x


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _mgpcg(delta, h1h2, max_iter, weighted, dtype_name, levels, coarse_inv,
           rhs, gate):
    dtype = jnp.dtype(dtype_name)
    norm_w = jnp.asarray(h1h2 if weighted else 1.0, dtype)
    h1h2 = jnp.asarray(h1h2, dtype)
    precond = functools.partial(_vcycle, levels, coarse_inv)

    r0 = rhs * gate.astype(dtype)
    z0 = precond(r0)
    zero = jnp.zeros_like(r0)
    init = (jnp.int32(0), jnp.asarray(False), zero, r0, z0,
            jnp.sum(z0 * r0) * h1h2, jnp.asarray(jnp.inf, dtype))

    def cond(s):
        k, done = s[0], s[1]
        return (~done) & (k < max_iter)

    def body(s):
        k, _, w, r, p, zr, _ = s
        ap = _apply(levels[0], p)
        den = jnp.sum(ap * p) * h1h2
        degenerate = jnp.abs(den) < _DENOM_TOL
        alpha = jnp.where(degenerate, jnp.zeros((), dtype),
                          zr / jnp.where(degenerate, jnp.ones((), dtype), den))
        step = alpha * p
        w = w + step
        r = r - alpha * ap
        diff = jnp.sqrt(jnp.sum(step * step) * norm_w)
        z = precond(r)
        zr_new = jnp.sum(z * r) * h1h2
        done = degenerate | (diff < delta)
        p = z + (zr_new / zr) * p
        return (k + 1, done, w, r, p, zr_new, diff)

    k, _, w, _, _, _, diff = lax.while_loop(cond, body, init)
    return w, k, diff


class Reference:
    """The reference for one configuration: levels and the coarsest inverse
    built once on the host and placed on ``device``, then one solve per
    right-hand-side gate."""

    def __init__(self, problem: dict, max_iter: int, dtype: str = "float32",
                 device=None):
        self.problem = problem
        self.max_iter = int(max_iter)
        self.dtype = dtype
        h1 = (problem["x_max"] - problem["x_min"]) / problem["M"]
        h2 = (problem["y_max"] - problem["y_min"]) / problem["N"]
        self.h1h2 = h1 * h2
        put = (lambda x: jax.device_put(jnp.asarray(x, dtype), device))
        levels, coarse_inv, rhs = host_levels(problem)
        self.levels = tuple(tuple(put(c) for c in lv) for lv in levels)
        self.coarse_inv = put(coarse_inv)
        self.rhs = put(rhs)

    def solve(self, gate: float):
        """(w on the full (M+1, N+1) grid as float64, iterations, diff)."""
        with jax.default_matmul_precision("highest"):
            w, k, diff = _mgpcg(float(self.problem["delta"]), self.h1h2,
                                self.max_iter,
                                bool(self.problem.get("weighted_norm", True)),
                                self.dtype, self.levels, self.coarse_inv,
                                self.rhs, jnp.float32(gate))
        w = np.pad(np.asarray(w, np.float64), 1)
        return w, int(k), float(diff)
