"""Multi-chip dry-run contract at device counts beyond the suite's mesh.

The driver validates multi-chip sharding by running
``__graft_entry__.dryrun_multichip(n)`` under
``--xla_force_host_platform_device_count=n``. The suite's own process is
pinned to 8 virtual devices (conftest), so higher counts run in a
subprocess with their own XLA flags — the closest single-host stand-in for
a larger pod slice.
"""

import os
import pathlib
import subprocess
import sys

import pytest

_ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.slow
def test_dryrun_multichip_self_hosting_from_plain_env():
    """dryrun_multichip called from a process whose ambient JAX
    environment is NOT a forced n-device CPU mesh (no JAX_PLATFORMS, no
    device-count flag): the entry point re-execs a child with both set,
    so n=8 succeeds although the caller only ever sees one device."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = str(_ROOT)
    code = (
        "import __graft_entry__ as g;"
        "g.dryrun_multichip(8);"
        "print('OUTER_OK')"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=_ROOT, env=env,
        capture_output=True, text=True, timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OUTER_OK" in proc.stdout


@pytest.mark.slow
def test_dryrun_gate_from_a_parent_holding_its_devices():
    """The parent has initialized its own JAX runtime (as a process
    holding a chip would): the dry run still runs, in a child pinned to
    an 8-device CPU mesh, and leaves the parent's devices alone."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = str(_ROOT)
    code = (
        "import jax, __graft_entry__ as g;"
        "assert len(jax.devices()) == 1;"
        "g.dryrun_multichip(8);"
        "assert len(jax.devices()) == 1;"
        "print('OUTER_OK')"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=_ROOT, env=env,
        capture_output=True, text=True, timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OUTER_OK" in proc.stdout


@pytest.mark.slow
@pytest.mark.parametrize("n", [16, 32])
def test_dryrun_multichip_scales(n):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = str(_ROOT)
    code = (
        "import jax; jax.config.update('jax_platforms','cpu');"
        "import __graft_entry__ as g;"
        f"g.dryrun_multichip({n});"
        "print('OK', len(jax.devices()))"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=_ROOT, env=env,
        capture_output=True, text=True, timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert f"OK {n}" in proc.stdout
