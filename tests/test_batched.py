"""Batched multi-RHS driver (``solvers.batched``): batch-vs-sequential
bit-parity, per-member convergence masking, bucketing, and the CLI/bench
throughput surfaces.

The load-bearing property is the first one: ``solve_batched`` is a
*hardware batching* transform, not a numerical change, so each member's
iterates, flags, and iteration counts must match ``pcg_solve`` of the same
problem bit-for-bit — including members that converge early and sit frozen
while stragglers keep iterating (their post-freeze state must be exactly
their sequential final state).
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from poisson_tpu.config import Problem
from poisson_tpu.obs import metrics
from poisson_tpu.solvers.batched import (
    DEFAULT_BUCKETS,
    bucket_size,
    reset_bucket_cache,
    solve_batched,
)
from poisson_tpu.solvers.pcg import FLAG_CONVERGED, pcg_solve

pytestmark = pytest.mark.batched


@pytest.fixture(autouse=True)
def _fresh_bucket_cache():
    """Counter assertions (hits/misses) must not depend on which bucket
    shapes earlier tests — or an earlier in-process run — already traced:
    the traced-shapes set and the metrics registry move together."""
    reset_bucket_cache()
    yield
    reset_bucket_cache()

# Distinct RHS magnitudes → distinct convergence trajectories (δ is an
# absolute threshold), so early convergers genuinely freeze while the
# largest-gate member keeps iterating.
GATES = (0.25, 1.0, 4.0)


@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
def test_batch_matches_sequential_bit_for_bit(dtype):
    p = Problem(M=40, N=40)
    seq = [pcg_solve(p, dtype=dtype, rhs_gate=g) for g in GATES]
    bat = solve_batched(p, rhs_gates=GATES, dtype=dtype)

    iters = np.asarray(bat.iterations)
    assert iters.shape == (len(GATES),)
    # The gates must actually spread the counts — otherwise the masked
    # freeze is never exercised and this test proves nothing.
    assert len({int(k) for k in iters}) >= 2
    for i, r in enumerate(seq):
        assert int(iters[i]) == int(r.iterations)
        assert int(np.asarray(bat.flag)[i]) == int(r.flag) == FLAG_CONVERGED
        # Bit-for-bit, not allclose: the batched member ran the exact
        # sequential iterate sequence and then froze.
        np.testing.assert_array_equal(np.asarray(bat.w)[i],
                                      np.asarray(r.w))
        assert float(np.asarray(bat.diff)[i]) == float(r.diff)
        assert float(np.asarray(bat.residual_dot)[i]) == float(
            r.residual_dot)
    assert int(bat.max_iterations) == max(int(r.iterations) for r in seq)


def test_problem_sequence_form_matches_sequential():
    base = Problem(M=30, N=30)
    problems = [base, base.with_(f_val=2.0), base.with_(f_val=0.5)]
    seq = [pcg_solve(p) for p in problems]
    bat = solve_batched(problems)
    for i, r in enumerate(seq):
        assert int(np.asarray(bat.iterations)[i]) == int(r.iterations)
        np.testing.assert_array_equal(np.asarray(bat.w)[i],
                                      np.asarray(r.w))


def test_rhs_stack_form_solves_distinct_rhs():
    p = Problem(M=30, N=30)
    from poisson_tpu.models.fictitious_domain import build_fields

    _, _, rhs = build_fields(p, dtype=np.float64, xp=np)
    stack = np.stack([rhs, 2.0 * rhs])
    bat = solve_batched(p, rhs_stack=stack)
    assert np.asarray(bat.iterations).shape == (2,)
    assert all(int(f) == FLAG_CONVERGED for f in np.asarray(bat.flag))
    # Solutions are distinct (different RHS) and finite.
    w = np.asarray(bat.w)
    assert np.isfinite(w).all()
    assert not np.array_equal(w[0], w[1])


def test_rhs_stack_shape_validated():
    p = Problem(M=30, N=30)
    with pytest.raises(ValueError, match="rhs_stack must be"):
        solve_batched(p, rhs_stack=np.zeros((2, 10, 10)))


def test_bucket_padding_is_invisible_and_counted():
    p = Problem(M=20, N=20)
    metrics.reset()
    bat = solve_batched(p, rhs_gates=(1.0, 2.0, 0.5))   # buckets to 4
    assert np.asarray(bat.iterations).shape == (3,)
    assert np.asarray(bat.w).shape[0] == 3
    assert metrics.get("batched.bucket_cache.misses") == 1
    assert metrics.get("batched.padding_members") == 1
    assert metrics.get("batched.solves") == 3
    # Same bucket again (different batch size, same executable): a hit.
    solve_batched(p, rhs_gates=(1.0, 2.0, 0.5, 3.0))
    assert metrics.get("batched.bucket_cache.hits") == 1


def test_bucket_ladder():
    assert [bucket_size(n) for n in (1, 2, 3, 5, 16, 17, 256)] == [
        1, 2, 4, 8, 16, 32, 256]
    assert bucket_size(300) == 300          # beyond the ladder: exact size
    assert DEFAULT_BUCKETS[-1] == 256
    with pytest.raises(ValueError):
        bucket_size(0)


def test_explicit_bucket_and_too_small_bucket():
    p = Problem(M=20, N=20)
    bat = solve_batched(p, rhs_gates=(1.0, 2.0), bucket=8)
    assert np.asarray(bat.iterations).shape == (2,)
    with pytest.raises(ValueError, match="bucket 1 smaller than batch"):
        solve_batched(p, rhs_gates=(1.0, 2.0), bucket=1)


def test_mesh_composition_rejects_unwired_families():
    """mesh= composes with the plain multi-RHS forms (PR 12; parity
    pinned in tests/test_placement.py); the executable families without
    a sharded program must still be rejected loudly, never silently
    mis-sharded."""
    import jax

    from poisson_tpu.parallel.mesh import make_solver_mesh

    p = Problem(M=20, N=20)
    mesh = make_solver_mesh(jax.devices()[:1])
    with pytest.raises(ValueError, match="geometries"):
        solve_batched(p, rhs_gates=(1.0,), mesh=mesh,
                      geometries=[{"type": "ellipse"}])
    with pytest.raises(ValueError, match="Jacobi"):
        solve_batched(p, rhs_gates=(1.0,), mesh=mesh,
                      preconditioner="mg")
    with pytest.raises(ValueError, match="integrity probe"):
        solve_batched(p, rhs_gates=(1.0,), mesh=mesh, verify_every=5)


def test_mismatched_problems_rejected():
    with pytest.raises(ValueError, match="share the operator"):
        solve_batched([Problem(M=20, N=20), Problem(M=22, N=20)])


def test_input_form_validation():
    p = Problem(M=20, N=20)
    with pytest.raises(ValueError, match="exactly one of"):
        solve_batched(p)
    with pytest.raises(ValueError, match="exactly one of"):
        solve_batched(p, rhs_gates=(1.0,), rhs_stack=np.zeros((1, 21, 21)))
    with pytest.raises(ValueError, match="at least one"):
        solve_batched([])


def test_max_iter_cap_respected_per_member():
    """A capped batched solve freezes members at the cap exactly like the
    sequential loop (cond: k < max_iter)."""
    p = Problem(M=20, N=20, max_iter=5)
    seq = pcg_solve(p, rhs_gate=1.0)
    bat = solve_batched(p, rhs_gates=(1.0, 1.0))
    assert int(seq.iterations) == 5
    assert [int(k) for k in np.asarray(bat.iterations)] == [5, 5]
    np.testing.assert_array_equal(np.asarray(bat.w)[0], np.asarray(seq.w))


def test_solve_report_handles_member_vector():
    """The report path must format batched results: scalar slots carry the
    fused-loop max, the member vector rides alongside (satellite: vector
    iterations must never crash a report line)."""
    from poisson_tpu.utils.timing import solve_report

    p = Problem(M=20, N=20)
    bat = solve_batched(p, rhs_gates=GATES)
    rep = solve_report(p, bat, solve_seconds=0.1, compile_seconds=0.0,
                       dtype="float64", backend="xla_batched")
    assert rep.iterations == int(bat.max_iterations)
    assert rep.batch == len(GATES)
    assert rep.iterations_per_member == [
        int(k) for k in np.asarray(bat.iterations)]
    assert "members" in rep.table()
    json.loads(rep.json_line())     # serializable


def test_ops_accept_batch_dimension_directly():
    """PCGOps / ops.stencil are batch-polymorphic without vmap: a
    (B, M+1, N+1) stack gets per-member stencil applications and
    per-member reductions identical to the unbatched ops per slice."""
    from poisson_tpu.solvers.pcg import host_setup, single_device_ops

    p = Problem(M=20, N=20)
    a, b, rhs, aux = host_setup(p, "float64", False)
    ops = single_device_ops(p, a, b, aux)
    stack = jnp.stack([rhs, 2.0 * rhs, 0.5 * rhs])

    for name, fn in [("apply_A", ops.apply_A),
                     ("apply_Dinv", ops.apply_Dinv)]:
        out = fn(stack)
        assert out.shape == stack.shape, name
        for i in range(3):
            np.testing.assert_array_equal(np.asarray(out[i]),
                                          np.asarray(fn(stack[i])), name)
    dots = ops.dot(stack, stack)
    sqs = ops.sqnorm(stack)
    assert dots.shape == (3,) and sqs.shape == (3,)
    for i in range(3):
        assert float(dots[i]) == float(ops.dot(stack[i], stack[i]))
        assert float(sqs[i]) == float(ops.sqnorm(stack[i]))


def test_solve_report_flag_aggregation_not_fooled_by_cap_hit():
    """A batch with a budget-exhausted member (FLAG_NONE=0) must not be
    reported as converged just because max(0, 1) == FLAG_CONVERGED; and a
    failure member must surface as the stop verdict."""
    from poisson_tpu.solvers.pcg import (
        FLAG_NONE,
        FLAG_NONFINITE,
        PCGResult,
    )
    from poisson_tpu.utils.timing import solve_report

    p = Problem(M=20, N=20)

    def fake(flags):
        n = len(flags)
        return PCGResult(
            w=np.zeros((n,) + p.grid_shape), iterations=np.array([3] * n),
            diff=np.array([0.5] * n), residual_dot=np.array([1.0] * n),
            flag=np.array(flags, np.int32), max_iterations=np.int32(3))

    metrics.reset()
    rep = solve_report(p, fake([FLAG_NONE, FLAG_CONVERGED]), 0.1, 0.0,
                       dtype="x")
    assert rep.stopped is None                      # cap-hit ≠ failure…
    assert metrics.get("pcg.solves.running") == 1   # …but ≠ converged too
    assert metrics.get("pcg.solves.converged") == 0
    rep = solve_report(p, fake([FLAG_CONVERGED, FLAG_NONFINITE]), 0.1, 0.0,
                       dtype="x")
    assert rep.stopped == "nonfinite"


def test_bucket_executable_shared_across_f_val():
    """f_val never enters the traced program, so batches differing only in
    RHS magnitude must reuse the bucket executable (counter parity with
    the jit cache — the review's counter-vs-jit-key mismatch)."""
    p = Problem(M=20, N=20)
    metrics.reset()
    solve_batched([p, p.with_(f_val=2.0)])
    assert metrics.get("batched.bucket_cache.misses") == 1
    solve_batched([p.with_(f_val=3.0), p.with_(f_val=0.5)])
    assert metrics.get("batched.bucket_cache.hits") == 1
    assert metrics.get("batched.bucket_cache.misses") == 1


def test_iterations_scalar_helper():
    from poisson_tpu.solvers.pcg import iterations_scalar

    assert iterations_scalar(np.int32(7)) == 7
    assert iterations_scalar(np.array([3, 9, 5])) == 9


def test_cli_solve_batched_json(capsys):
    from poisson_tpu.cli import main

    assert main(["solve-batched", "30", "30", "--batch", "3",
                 "--vary-rhs", "--compare-sequential", "--json"]) == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["batch"] == 3
    assert rec["bucket"] == 4
    assert rec["converged"] == 3
    assert len(rec["iterations"]) == 3
    assert rec["max_iterations"] == max(rec["iterations"])
    assert rec["iterations_match_sequential"] is True
    assert rec["solves_per_sec"] > 0


def test_cli_solve_batched_table(capsys):
    from poisson_tpu.cli import main

    assert main(["solve-batched", "30", "30", "--batch", "2"]) == 0
    out = capsys.readouterr().out
    assert "batch=2" in out and "solves/s" in out


_CACHE_CONFIG = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_entry_size_bytes",
                 "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture()
def restore_cache_config():
    """The cache settings are process-global jax config: put them back so
    later tests never persist into a vanished or unexpected directory."""
    import jax

    saved = {name: getattr(jax.config, name) for name in _CACHE_CONFIG}
    yield
    for name, value in saved.items():
        jax.config.update(name, value)


def test_compile_cache_counters_wiring(tmp_path, monkeypatch,
                                       restore_cache_config):
    """With JAX_COMPILATION_CACHE_DIR set, enable() sets no cache
    directory of its own (JAX reads the variable itself) and the
    monitoring listener folds JAX's cache events into obs counters."""
    import jax

    from poisson_tpu.utils import compile_cache

    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "cc"))
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.enable() == str(tmp_path / "cc")
    assert jax.config.jax_compilation_cache_dir is None
    metrics.reset()
    # The listener is wired to the jax.monitoring bus: a cache event on
    # the bus must land in the counters (platform-independent, unlike
    # provoking a real persistent-cache round trip on every backend).
    from jax import monitoring

    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event("/jax/compilation_cache/cache_misses")
    monitoring.record_event("/jax/unrelated/event")
    assert metrics.get("compile_cache.hits") == 1
    assert metrics.get("compile_cache.misses") == 1


def test_compile_cache_defaults_to_repo_dir(monkeypatch,
                                            restore_cache_config):
    import pathlib

    import jax

    from poisson_tpu.utils import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    repo = pathlib.Path(__file__).resolve().parents[1]
    assert compile_cache.enable() == str(repo / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(repo / ".jax_cache")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_compile_cache_env_dir_used_by_a_fresh_process(tmp_path):
    """End to end: a process started with JAX_COMPILATION_CACHE_DIR keeps
    what it compiles there, and enable() leaves that directory alone."""
    import os
    import pathlib
    import subprocess
    import sys

    cache = tmp_path / "cc"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PLATFORMS="cpu")
    code = ("import jax, jax.numpy as jnp\n"
            "from poisson_tpu.utils import compile_cache\n"
            "print(compile_cache.enable())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=pathlib.Path(__file__).resolve().parents[1],
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [str(cache), str(cache)]
    assert any(cache.iterdir())


def test_bench_batched_record_shape():
    """bench.py --batch on a tiny grid: one JSON line with the throughput
    schema and sequential-parity bit (subprocess: bench owns sys.argv)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench.py", "--batch", "3", "20", "20"],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "batched_solves_per_sec"
    assert rec["unit"] == "solves/sec"
    assert rec["value"] > 0
    assert rec["detail"]["batch"] == 3
    assert rec["detail"]["bucket"] == 4
    assert rec["detail"]["iterations_match_sequential"] is True
    assert rec["detail"]["converged"] == 3
    assert "speedup_vs_sequential" in rec


def test_summarize_session_renders_batched_rows(tmp_path, capsys):
    """The session summarizer shows solves/sec (not a fake MLUPS) for
    batched bench records."""
    import sys

    from benchmarks import summarize_session as ss

    log = tmp_path / "session.jsonl"
    log.write_text(json.dumps({
        "step": "bench_batched", "at": "2026-08-04T00:00:00Z", "ok": True,
        "result": {
            "metric": "batched_solves_per_sec", "value": 123.4,
            "unit": "solves/sec", "speedup_vs_sequential": 3.21,
            "detail": {"grid": [400, 600], "batch": 16, "bucket": 16,
                       "iterations": 546,
                       "iterations_match_sequential": True,
                       "backend": "xla_batched", "platform": "tpu"},
        },
    }) + "\n")
    old = sys.argv
    sys.argv = ["summarize_session.py", str(log)]
    try:
        assert ss.main() == 0
    finally:
        sys.argv = old
    out = capsys.readouterr().out
    assert "123.4 sv/s" in out
    assert "B=16" in out
    assert "3.21x vs seq" in out
