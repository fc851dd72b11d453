"""Shape optimization as a durable solver session.

Recover an unknown domain offset from an observed solution by gradient
descent on the ellipse parameters, driven through the solve service as
ONE design session: each iteration is a forward solve + an implicit
adjoint solve (:func:`poisson_tpu.solvers.adjoint.shape_gradient`),
the descended ellipse becomes the session's next step — warm-started
from the previous iterate while the move stays inside the validity
bound — and every transition is a journaled, recoverable step boundary:

    JAX_PLATFORMS=cpu python examples/shape_opt.py

Runs in well under a minute on CPU (40x40 grid, 12 descent steps).
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax

jax.config.update("jax_enable_x64", True)  # adjoint solves want fp64

import numpy as np

from poisson_tpu import Problem
from poisson_tpu.geometry import Ellipse
from poisson_tpu.obs import metrics
from poisson_tpu.serve import ServicePolicy, SessionHost, SolveService
from poisson_tpu.solvers.pcg import pcg_solve

# Tight solver tolerance: the adjoint differentiates THROUGH the solve,
# so solver error is gradient noise — keep it well below the descent's
# per-step moves, but above this grid's Krylov breakdown floor (~1e-9).
problem = Problem(M=40, N=40, delta=1e-8)

# The "observed" solution: a solve on the TRUE (unknown) domain — the
# default ellipse shifted right by 0.12 (about 2.5 grid cells).
true_spec = Ellipse(cx=0.12)
target = np.asarray(pcg_solve(problem, geometry=true_spec).w)

svc = SolveService(ServicePolicy(capacity=64))
host = SessionHost(svc)
sess = host.open("shape-opt", problem, kind="design", dtype="float64",
                 geometry=Ellipse(), params={"note": "examples/shape_opt"})
assert sess is not None, "design session was shed on an idle service"

first_loss = None
loss = float("inf")
for it in range(12):
    out, loss, grads = host.design_step(sess, target, lr=20.0)
    if first_loss is None:
        first_loss = loss
    p = sess.design_params
    print(f"step {it}: loss {loss:.3e}  cx {p['cx']:+.4f}  "
          f"({int(out.iterations)} iterations)")

warm_hits = metrics.snapshot()["counters"].get("session.warm.hits", 0)
summary = host.close(sess)
err = abs(sess.design_params["cx"] - true_spec.cx)
print(f"closed: {summary['steps']} steps, slo_good={summary['slo_good']}, "
      f"{warm_hits} warm-started")
print(f"final loss {loss:.3e} (from {first_loss:.3e}), "
      f"center error {err:.4f} (grid cell h1 = {problem.h1:.3f})")
if not (loss < 0.25 * first_loss and err < problem.h1):
    print("shape optimization did NOT converge", file=sys.stderr)
    sys.exit(1)
print("recovered the domain offset to within one grid cell")
