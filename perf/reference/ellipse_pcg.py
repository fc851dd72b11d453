"""Plain reference for the ellipse configurations: Jacobi-preconditioned CG
on the fictitious-domain discretisation, written from the published
description and importing nothing of the program under test.

Problem (BASELINE.md; the reference reports' stage 0-4 programs): -div(k
grad u) = f on the box [x_min, x_max] x [y_min, y_max] holding the ellipse
x^2 + 4y^2 < 1, zero Dirichlet data on the box. Grid nodes x_i = x_min +
i*h1, y_j = y_min + j*h2 (i = 0..M, j = 0..N); unknowns at the interior
nodes. With eps = max(h1, h2)^2 and l the length of a cell face inside the
ellipse, h the face's full length, the face coefficient is 1 (face inside),
1/eps (face outside) or l/h + (1 - l/h)/eps (cut face); a[i, j] sits on the
vertical face x = x_i - h1/2, b[i, j] on the horizontal face y = y_j - h2/2.
The right-hand side is f at interior nodes inside the ellipse, 0 elsewhere.

Iteration (stage 2's solve_mpi): w0 = 0, r0 = B, z0 = D^-1 r0, p0 = z0;
each step Ap, alpha = (z, r)/(Ap, p), w += alpha p, r -= alpha Ap,
diff = ||alpha p|| in the norm sqrt(h1 h2 sum(.^2)), z = D^-1 r, and stop
once diff < delta (the step counts); else beta = (z', r')/(z, r),
p = z + beta p. A direction with |(Ap, p)| < 1e-15 stops the loop.

Every array and every scalar is held in ``dtype``: float32 is the
configurations' stated precision; bfloat16 is the control that has to come
out as not correct. Coefficients are derived in float64 on the host and
cast once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_FACE_TOL = 1e-9
_DENOM_TOL = 1e-15


def _face_coefficient(length, h, eps):
    frac = length / h
    return np.where(np.abs(length - h) < _FACE_TOL, 1.0,
                    np.where(length < _FACE_TOL, 1.0 / eps,
                             frac + (1.0 - frac) / eps))


def host_fields(problem: dict):
    """float64 (cN, cS, cE, cW, rhs) on the interior (M-1, N-1): the four
    neighbour couplings a/h1^2, b/h2^2 of each interior node and f on the
    nodes inside the ellipse."""
    M, N = problem["M"], problem["N"]
    h1 = (problem["x_max"] - problem["x_min"]) / M
    h2 = (problem["y_max"] - problem["y_min"]) / N
    eps = max(h1, h2) ** 2
    x = (problem["x_min"] + np.arange(M + 1) * h1)[:, None]
    y = (problem["y_min"] + np.arange(N + 1) * h2)[None, :]
    # Vertical face at x - h1/2 spanning [y - h2/2, y + h2/2].
    xf = x - 0.5 * h1
    half_y = np.sqrt(np.maximum(0.0, (1.0 - xf * xf) / 4.0))
    la = np.maximum(0.0, np.minimum(y + 0.5 * h2, half_y)
                    - np.maximum(y - 0.5 * h2, -half_y))
    # Horizontal face at y - h2/2 spanning [x - h1/2, x + h1/2].
    yf = y - 0.5 * h2
    half_x = np.sqrt(np.maximum(0.0, 1.0 - 4.0 * yf * yf))
    lb = np.maximum(0.0, np.minimum(x + 0.5 * h1, half_x)
                    - np.maximum(x - 0.5 * h1, -half_x))
    a = _face_coefficient(la, h2, eps)
    b = _face_coefficient(lb, h1, eps)
    inside = (x * x + 4.0 * y * y) < 1.0
    rhs = np.where(inside, problem["f_val"], 0.0)[1:M, 1:N]
    c_n = a[2:M + 1, 1:N] / (h1 * h1)
    c_s = a[1:M, 1:N] / (h1 * h1)
    c_e = b[1:M, 2:N + 1] / (h2 * h2)
    c_w = b[1:M, 1:N] / (h2 * h2)
    return c_n, c_s, c_e, c_w, rhs


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _pcg(delta, h1h2, max_iter, weighted, dtype_name, c_n, c_s, c_e, c_w,
         rhs, gate):
    dtype = jnp.dtype(dtype_name)
    diag = c_n + c_s + c_e + c_w
    norm_w = jnp.asarray(h1h2 if weighted else 1.0, dtype)
    h1h2 = jnp.asarray(h1h2, dtype)

    def apply_a(u):
        up = jnp.pad(u, 1)
        return (diag * u - c_n * up[2:, 1:-1] - c_s * up[:-2, 1:-1]
                - c_e * up[1:-1, 2:] - c_w * up[1:-1, :-2])

    r0 = rhs * gate.astype(dtype)
    z0 = r0 / diag
    zero = jnp.zeros_like(r0)
    init = (jnp.int32(0), jnp.asarray(False), zero, r0, z0,
            jnp.sum(z0 * r0) * h1h2, jnp.asarray(jnp.inf, dtype))

    def cond(s):
        k, done = s[0], s[1]
        return (~done) & (k < max_iter)

    def body(s):
        k, _, w, r, p, zr, _ = s
        ap = apply_a(p)
        den = jnp.sum(ap * p) * h1h2
        degenerate = jnp.abs(den) < _DENOM_TOL
        alpha = jnp.where(degenerate, jnp.zeros((), dtype),
                          zr / jnp.where(degenerate, jnp.ones((), dtype), den))
        step = alpha * p
        w = w + step
        r = r - alpha * ap
        diff = jnp.sqrt(jnp.sum(step * step) * norm_w)
        z = r / diag
        zr_new = jnp.sum(z * r) * h1h2
        done = degenerate | (diff < delta)
        p = z + (zr_new / zr) * p
        return (k + 1, done, w, r, p, zr_new, diff)

    k, _, w, _, _, _, diff = lax.while_loop(cond, body, init)
    return w, k, diff


class Reference:
    """The reference for one configuration: fields built once on the host
    and placed on ``device``, then one solve per right-hand-side gate."""

    def __init__(self, problem: dict, max_iter: int, dtype: str = "float32",
                 device=None):
        self.problem = problem
        self.max_iter = int(max_iter)
        self.dtype = dtype
        h1 = (problem["x_max"] - problem["x_min"]) / problem["M"]
        h2 = (problem["y_max"] - problem["y_min"]) / problem["N"]
        self.h1h2 = h1 * h2
        put = (lambda x: jax.device_put(jnp.asarray(x, dtype), device))
        self.fields = tuple(put(f) for f in host_fields(problem))

    def solve(self, gate: float):
        """(w on the full (M+1, N+1) grid as float64, iterations, diff)."""
        w, k, diff = _pcg(float(self.problem["delta"]), self.h1h2,
                          self.max_iter,
                          bool(self.problem.get("weighted_norm", True)),
                          self.dtype, *self.fields, jnp.float32(gate))
        w = np.pad(np.asarray(w, np.float64), 1)
        return w, int(k), float(diff)
