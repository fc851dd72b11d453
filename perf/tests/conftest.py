"""The harness's own tests, run directly (``python -m pytest perf/tests``),
on the CPU: four virtual devices for the mesh, Pallas in interpret mode.
The tier-1 command (``pytest tests/``) does not collect them."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

import copy  # noqa: E402

import pytest  # noqa: E402

# A grid the CPU solves in milliseconds, and how long a rehearsal window is.
TINY = (40, 40)
SECONDS = 0.6



def tiny(workload: str, M: int = TINY[0], N: int = TINY[1], **traffic):
    """(bench, cell, config, traffic) of ``workload`` from the committed
    files, at an M x N grid, with ``traffic`` keys replaced."""
    import json

    from perf import run as harness

    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    cfile = next(c["file"] for c in bench["configs"]
                 if c["name"] == cell["config"])
    config = json.loads((harness.ROOT / cfile).read_text())
    mix = json.loads((harness.PERF / "traffic" /
                      f"{cell['traffic']}.json").read_text())
    config = copy.deepcopy(config)
    config["problem"].update(M=M, N=N)
    return bench, cell, config, dict(mix, **traffic)


def rehearse(workload: str, seed: int = 2**31 + 7, trace: bool = False,
             seconds: float = SECONDS, backend=None, **traffic) -> dict:
    """One run of ``workload`` at the tiny grid on the CPU devices, past
    the harness's look for a chip; ``backend`` stands in for the CLI's
    auto choice (which picks no Pallas path off the TPU)."""
    import contextlib
    from unittest import mock

    import jax

    from perf import entry
    from perf import run as harness

    bench_cell = tiny(workload, **traffic)
    devices = jax.devices()[: bench_cell[1]["chips"]]
    patch = (mock.patch.object(entry, "pick_backend", lambda run: backend)
             if backend else contextlib.nullcontext())
    with patch:
        return harness.run_cell(workload, seed, seconds, trace,
                                devices=devices, bench_cell=bench_cell)


@pytest.fixture
def run_tiny():
    return rehearse
