"""A whole run, past the look for a chip, with the timed path broken
underneath: ``correct`` has to come out false for every fault the cell
can have. Each fault is planted in the program, where the answer is
produced, not in the harness."""

import contextlib
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from perf.tests.conftest import rehearse


def _wrap(module, name, after):
    """Patch ``module.name`` so its result goes through ``after``."""
    real = getattr(module, name)
    return mock.patch.object(module, name,
                             lambda *a, **k: after(real(*a, **k)))


def _unchanged(r):
    """A solve that hands back its initial state: w0 = 0, no iteration."""
    return r._replace(w=jnp.zeros_like(r.w),
                      iterations=jnp.zeros_like(r.iterations))


def _altered(r):
    """An answer altered where it is produced: the field off by 5%."""
    return r._replace(w=r.w * 1.05)


def _half_left_out(r):
    """Half of the batch never solved: its members come back at w0 with
    no iteration, flagged as the rest are."""
    half = r.w.shape[0] // 2
    keep = (jnp.arange(r.w.shape[0]) < half)
    return r._replace(
        w=jnp.where(keep[:, None, None], r.w, 0.0),
        iterations=jnp.where(keep, r.iterations, 0))


@contextlib.contextmanager
def _no_exchange():
    """The halo exchange between chips left out: each shard keeps its own
    stale halo ring. The sharded program is traced anew under the patch."""
    from poisson_tpu.parallel import pallas_sharded

    jax.clear_caches()
    try:
        with mock.patch.object(pallas_sharded, "_exchange_r_halo",
                               lambda r, spec, px, py: r):
            yield
    finally:
        jax.clear_caches()


def _pallas():
    from poisson_tpu.ops import pallas_cg
    return pallas_cg, "pallas_cg_solve"


def _sharded():
    from poisson_tpu import parallel
    return parallel, "pallas_cg_solve_sharded"


def _batched():
    from poisson_tpu.solvers import batched
    return batched, "solve_batched"


FAULTS = [
    ("solve-2400x3200", "pallas", _pallas, _unchanged, {}),
    ("solve-2400x3200", "pallas", _pallas, _altered, {}),
    ("batch64-400x600", None, _batched, _unchanged, {}),
    ("batch64-400x600", None, _batched, _altered, {}),
    ("batch64-400x600", None, _batched, _half_left_out, {}),
    ("mesh2x2-2400x3200", "pallas-sharded", _sharded, _unchanged, {}),
    ("mesh2x2-2400x3200", "pallas-sharded", _sharded, _altered, {}),
    ("mesh2x2-2400x3200", "pallas-sharded", None, _no_exchange, {}),
]


@pytest.mark.parametrize(
    "workload,backend,where,fault,traffic", FAULTS,
    ids=[f"{w}-{f.__name__.strip('_')}" for w, _, _, f, _ in FAULTS])
def test_fault_is_not_correct(workload, backend, where, fault, traffic):
    patch = fault() if where is None else _wrap(*where(), fault)
    with patch:
        result = rehearse(workload, backend=backend, **traffic)
    assert not result["correct"], result["checks"]
