"""PDE-constrained optimisation with the differentiable solver.

Recover an unknown source term from an observed solution by gradient
descent through the PCG solve (implicit adjoint differentiation — each
gradient is one extra solve, regardless of iteration count):

    JAX_PLATFORMS=cpu python examples/source_identification.py
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax

jax.config.update("jax_enable_x64", True)  # delta=1e-10 needs fp64 state

import jax.numpy as jnp

from poisson_tpu import Problem
from poisson_tpu.models.fictitious_domain import build_fields
from poisson_tpu.solvers import differentiable_solve

problem = Problem(M=40, N=40, delta=1e-10)
_, _, true_source = build_fields(problem)
observed = differentiable_solve(problem, true_source)


def loss(source):
    w = differentiable_solve(problem, source)
    return jnp.sum((w - observed) ** 2)


source = 0.5 * true_source  # wrong initial guess
for step in range(5):
    value, grad = jax.value_and_grad(loss)(source)
    # Exact line search on the quadratic: t* = |g|^2 / (2 |A^{-1}g|^2).
    ainv_g = differentiable_solve(problem, grad)
    t = jnp.sum(grad * grad) / (2 * jnp.sum(ainv_g * ainv_g) + 1e-30)
    source = source - t * grad
    print(f"step {step}: loss {float(value):.3e}")

print(f"final loss {float(loss(source)):.3e} "
      f"(source recovered to solver tolerance)")
