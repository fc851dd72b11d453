"""Distributed solve over every visible device (the stage2/3/4 workload).

On a CPU-only host, emulate a pod slice first:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/distributed_solve.py

On TPU hardware the same script uses the real chips; on a multi-host pod,
call ``poisson_tpu.parallel.multihost.initialize_multihost()`` first (as
the first JAX call) and run one copy per host.
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax

from poisson_tpu import Problem
from poisson_tpu.parallel import make_solver_mesh, pcg_solve_sharded

mesh = make_solver_mesh()  # near-square 2D mesh over all devices
problem = Problem(M=400, N=600)
result = pcg_solve_sharded(problem, mesh)

print(f"devices: {len(jax.devices())}  mesh: {dict(mesh.shape)}")
print(f"converged in {int(result.iterations)} iterations (golden: 546), "
      f"||dw|| = {float(result.diff):.3e}")

if jax.devices()[0].platform == "tpu":
    # The fused-kernel distributed path (stage4's configuration).
    from poisson_tpu.parallel import pallas_cg_solve_sharded

    fused = pallas_cg_solve_sharded(problem, mesh)
    print(f"fused Pallas path: {int(fused.iterations)} iterations")

    # The communication-avoiding s=2 pair iteration over the same mesh:
    # ~1.46x less HBM traffic per iteration and one Gram reduction round
    # per PAIR of iterations (parallel.pallas_ca_sharded module doc).
    from poisson_tpu.parallel import ca_cg_solve_sharded

    ca = ca_cg_solve_sharded(problem, mesh)
    print(f"CA s=2 path: {int(ca.iterations)} iterations")
