"""Plain reference for the multigrid configurations split over a device
mesh: the MG-preconditioned CG of ``ellipse_mgpcg.py`` (loaded from it,
not copied), with its arrays placed on the cell's mesh and its jitted
loop partitioned by XLA's SPMD partitioner. No ``shard_map``, no halo
code, and nothing of the program under test.

Placement: the reference's arrays are interior grids of odd sides
((M_l - 1) x (N_l - 1)), which a mesh axis cannot split evenly, and a
jit's operands must split evenly. So each is placed zero-padded at its
far edges to the next multiple of the mesh's sides, as
``NamedSharding(mesh, P('x', 'y'))``, and the jitted function cuts the
padding off again before it calls the reference's own loop: the
arithmetic is ``ellipse_mgpcg``'s on the same values, and only the
partitioner decides where each piece of it runs (it keeps the loop
state split as its operands are). The dense coarsest inverse is placed
whole on every device. The answer comes back whole.

On one device (``mesh=None``) it is ``ellipse_mgpcg.Reference`` itself.
"""

from __future__ import annotations

import functools
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from perf.entry import load_module

_MG = load_module(pathlib.Path(__file__).with_name("ellipse_mgpcg.py"))


def _padded(x: np.ndarray, parts) -> np.ndarray:
    """``x`` zero-padded at its far edges to multiples of ``parts``."""
    return np.pad(x, [(0, -s % p) for s, p in zip(x.shape, parts)])


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _mgpcg_mesh(delta, h1h2, max_iter, weighted, dtype_name, shapes,
                levels, coarse_inv, rhs, gate):
    """``ellipse_mgpcg._mgpcg`` on the padded operands, cut back to
    ``shapes`` (each level's interior shape) first."""
    cut = tuple(tuple(c[:m, :n] for c in lv)
                for lv, (m, n) in zip(levels, shapes))
    m0, n0 = shapes[0]
    return _MG._mgpcg(delta, h1h2, max_iter, weighted, dtype_name, cut,
                      coarse_inv, rhs[:m0, :n0], gate)


class Reference(_MG.Reference):
    """The reference for one configuration: levels and the coarsest
    inverse built once on the host (``ellipse_mgpcg.host_levels``, or
    ``host`` where its result is handed in), placed on ``mesh`` (or on
    ``device``), then one solve per right-hand-side gate."""

    def __init__(self, problem: dict, max_iter: int, dtype: str = "float32",
                 device=None, mesh=None, host=None):
        if mesh is None:
            super().__init__(problem, max_iter, dtype, device=device)
            return
        self.problem = problem
        self.max_iter = int(max_iter)
        self.dtype = dtype
        h1 = (problem["x_max"] - problem["x_min"]) / problem["M"]
        h2 = (problem["y_max"] - problem["y_min"]) / problem["N"]
        self.h1h2 = h1 * h2
        parts = tuple(mesh.shape.values())
        split = NamedSharding(mesh, P(*mesh.axis_names))
        whole = NamedSharding(mesh, P())
        dt = jnp.dtype(dtype)

        def put(x):
            return jax.device_put(_padded(np.asarray(x, dt), parts),
                                  split)

        levels, coarse_inv, rhs = host or _MG.host_levels(problem)
        self.shapes = tuple(lv[0].shape for lv in levels)
        self.levels = tuple(tuple(put(c) for c in lv) for lv in levels)
        self.coarse_inv = jax.device_put(np.asarray(coarse_inv, dt),
                                         whole)
        self.rhs = put(rhs)
        self.mesh = mesh

    def solve(self, gate: float):
        """(w on the full (M+1, N+1) grid as float64, iterations, diff)."""
        if getattr(self, "mesh", None) is None:
            return super().solve(gate)
        with jax.default_matmul_precision("highest"):
            w, k, diff = _mgpcg_mesh(
                float(self.problem["delta"]), self.h1h2, self.max_iter,
                bool(self.problem.get("weighted_norm", True)), self.dtype,
                self.shapes, self.levels, self.coarse_inv, self.rhs,
                jnp.float32(gate))
        w = np.pad(np.asarray(w, np.float64), 1)
        return w, int(k), float(diff)
