"""Minimal usage: solve the reference's flagship problem and report.

    JAX_PLATFORMS=cpu python examples/basic_solve.py   # or on TPU: drop the env var
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from poisson_tpu import Problem, pcg_solve
from poisson_tpu.analysis import l2_error_vs_analytic

problem = Problem(M=400, N=600)
result = pcg_solve(problem)

print(f"grid {problem.M}x{problem.N}: converged in {int(result.iterations)} "
      f"iterations (golden: 546)")
print(f"final ||dw|| = {float(result.diff):.3e}")
print(f"L2 error vs analytic u=(1-x^2-4y^2)/10: "
      f"{float(l2_error_vs_analytic(problem, result.w)):.3e}")
