"""chip_smoke.py: its phases at 40x40 on the CPU (Pallas interpret mode,
virtual devices for the 2x2 mesh), and its refusal to run without a TPU."""

import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

import chip_smoke

ROOT = pathlib.Path(__file__).resolve().parents[1]
GRID = (40, 40)


def test_phase_solve():
    out = chip_smoke.phase_solve(*GRID)
    assert out["iterations"] == out["oracle_iterations"] == 50
    assert out["max_abs_dw_vs_oracle"] <= chip_smoke.FP32_ATOL
    assert out["tpu_custom_call"] is False   # interpret mode on the CPU


def test_phase_batched_then_service():
    gates = chip_smoke.rhs_gates()
    batched = chip_smoke.phase_batched(*GRID, gates)
    assert batched["matched"] == len(gates) == 8
    assert len(set(batched["iterations"])) > 1   # the gates differ
    service = chip_smoke.phase_service(*GRID, gates, batched["iterations"])
    assert service["ok"] == 8
    assert service["executor_backends"] == ["xla"]


def test_phase_sharded_on_four_devices():
    out = chip_smoke.phase_sharded(*GRID, jax.devices()[:4])
    assert out["single_iterations"] == 50
    for name in ("pallas_sharded", "xla_sharded"):
        assert out[name]["iterations"] == 50
        assert len(out[name]["memory"]) == 4


def test_golden_miss_fails_the_phase():
    from poisson_tpu.config import Problem

    with pytest.raises(chip_smoke.SmokeFailure, match="golden 50"):
        chip_smoke._check_golden("fused", Problem(M=40, N=40), 56)


def test_service_phase_fails_on_wrong_iterations():
    gates = chip_smoke.rhs_gates(2)
    with pytest.raises(chip_smoke.SmokeFailure, match="service outcomes"):
        chip_smoke.phase_service(*GRID, gates, [0, 0])


def test_spread_needs_every_device():
    spread = [{"peak_bytes_in_use": 100}] * 4
    on_device_0 = [{"peak_bytes_in_use": 400}] + [
        {"peak_bytes_in_use": 0}] * 3
    assert chip_smoke._spread(spread, 50)
    assert not chip_smoke._spread(on_device_0, 50)
    assert not chip_smoke._spread([{"peak_bytes_in_use": None}] * 4, 1)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_refuses_cpu(argv):
    """No TPU: non-zero exit and no result line, in either mode."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", *argv], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        assert not line.startswith("{") or "ok" not in json.loads(line)


def test_fails_without_the_repo(tmp_path):
    """chip_smoke.py alone in a directory: non-zero, no result line."""
    import shutil

    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
