"""Run one cell of the chip benchmark and print its result line.

    python perf/run.py --workload NAME --seed N --seconds S --trace 0|1

The cell, its configuration, its traffic mix and its metrics are named in
``BENCHMARK.json`` at the root of the checkout, and each is found by name:

- ``perf/configs/<config>.json``: the deployment (grid, domain, delta,
  chips and mesh, state dtype), its reference and the limits of the
  comparison that decides ``correct``;
- ``perf/traffic/<traffic>.json``: the parameters the one generator
  (``perf/generate.py``) reads, and the driver that runs them;
- ``perf/drivers/<driver>.py``: set-up with warm-up, the measured window,
  and the comparison with the reference;
- ``perf/reference/<reference>.py``: the plain reference;
- ``perf/metrics/<metric>.py``: one reader per metric, end to end or per
  layer, returning a number or None (nothing to read).

A run sets up and warms every shape it uses (``setup_s``), measures for
``--seconds``, reads the device's peak memory, frees the program's state,
and only then runs the reference. With ``--trace 1`` the window runs
under the profiler and the result carries the per-layer metrics, the
device's busy time and a breakdown; otherwise the end-to-end metrics. A
traffic file's ``trace_seconds`` keeps the profiler to the window's first
dispatches, those that start within that many seconds, where a whole
window holds more device events than a run can read back in time. The
last line on stdout is the result; the numbers compared, each beside its
limit, are the last lines on stderr. A machine without a TPU, or with
fewer chips than the cell asks for, exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
PERF = ROOT / "perf"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perf.entry import load_module  # noqa: E402
# JAX's persistent compilation cache, at a fixed path inside the checkout
# so that every run after a cell's first finds its programs there.
CACHE_DIR = ROOT / ".perf_cache" / "jax"
TRACE_DIR = ROOT / ".perf_cache" / "trace"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """Everything one run knows; drivers fill ``records``, ``window_s``,
    ``info`` and ``kept`` (the answers the check compares, on the host),
    and metric readers read it."""

    workload: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    devices: list
    records: list = dataclasses.field(default_factory=list)
    window_s: float = 0.0
    setup_s: float = 0.0
    info: dict = dataclasses.field(default_factory=dict)
    kept: list = dataclasses.field(default_factory=list)
    trace: Any = None
    peak: Optional[dict] = None


def load_cell(workload: str, root: pathlib.Path = ROOT):
    """(benchmark, cell, config, traffic) for ``workload``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "perf" / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def metrics_of(bench: dict, workload: str, kind: str) -> list:
    """The ``kind`` ("end_to_end" or "per_layer") metrics this cell
    reports: those that list it, and those that list no cells."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def _ensure_chip(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {devices[0].platform!r}; "
                     "the benchmark runs on nothing else")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def _annotate(name: str):
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


class _Tracer:
    """The profiler over the window (``on``), or over the dispatches that
    start within its first ``limit_s`` seconds: the trace then stops
    between two dispatches, so that it holds whole dispatches only.
    ``run.info["traced"]`` counts the records of the traced dispatches."""

    def __init__(self, run: Run, on: bool, limit_s: Optional[float]):
        import jax

        self.run, self.on, self.limit_s = run, on, limit_s
        if on:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
        self.window = _annotate("perf.window")
        self.window.__enter__()
        self.t0 = time.perf_counter()

    def span(self, name: str):
        if (self.on and self.limit_s is not None and name == "perf.dispatch"
                and time.perf_counter() - self.t0 >= self.limit_s):
            self.stop()
        return _annotate(name)

    def stop(self) -> None:
        import jax

        if self.window is not None:
            self.window.__exit__(None, None, None)
            self.window = None
        if self.on:
            jax.profiler.stop_trace()
            self.on = False
            self.run.info["traced"] = len(self.run.records)


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def read_metrics(run: Run, metrics: list) -> dict:
    out = {}
    for m in metrics:
        value = load_module(PERF / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: pathlib.Path = ROOT, devices=None,
             bench_cell=None) -> dict:
    """One run of ``workload``; returns the result line as a dict.

    ``devices`` skips the look for a chip (the tests' CPU rehearsals);
    ``bench_cell`` hands in ``(bench, cell, config, traffic)`` in place of
    the files."""
    bench, cell, config, traffic = bench_cell or load_cell(workload, root)
    if devices is None:
        devices = _ensure_chip(int(cell["chips"]))
    run = Run(workload=workload, config=config, traffic=traffic,
              seed=int(seed), seconds=float(seconds), devices=list(devices))
    d0 = run.devices[0]
    if d0.platform == "tpu":
        from perf import work

        run.peak = work.peak(d0.device_kind)
    driver = load_module(PERF / "drivers" / f"{traffic['driver']}.py")

    compiles = []
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(
        lambda event, duration, **_: compiles.append(time.perf_counter())
        if event == _COMPILE_EVENT else None)

    t_setup = time.perf_counter()
    state = driver.setup(run)
    run.info["devices_s"] = t_setup - T_START
    run.info["driver_setup_s"] = time.perf_counter() - t_setup
    tracer = _Tracer(run, trace, traffic.get("trace_seconds"))
    t_window = time.perf_counter()
    run.setup_s = t_window - T_START
    try:
        driver.window(run, state, tracer.span)
    finally:
        tracer.stop()
    t_end = time.perf_counter()
    window_compiles = sum(t_window <= t <= t_end for t in compiles)
    peak_bytes = memory_peak(run.devices)
    if trace:
        from perf import trace as trace_mod

        run.trace = trace_mod.load(str(TRACE_DIR),
                                   [d.id for d in run.devices])

    driver.release(run, state)
    del state
    gc.collect()
    t_check = time.perf_counter()
    checks, attempted, failed = driver.check(run)
    run.info["reference_s"] = time.perf_counter() - t_check

    kind = "per_layer" if trace else "end_to_end"
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(run.devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": checks.ok, "attempted": attempted,
              "failed": failed,
              "metrics": read_metrics(run, metrics_of(bench, workload, kind)),
              "device": device}
    if trace and run.trace is not None and run.trace.devices:
        device["busy_s"] = run.trace.mean_busy_s()
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.device_ops(),
                               "idle_gaps": run.trace.idle_gaps()}
    result["info"] = dict(run.info, window_compiles=window_compiles,
                          window_s=run.window_s, setup_s=run.setup_s)
    result["checks"] = checks.report()
    return result


def _print_result(result: dict) -> None:
    info = result["info"]
    print("info: " + json.dumps(info), file=sys.stderr, flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def configure_jax() -> None:
    """Keep every compiled program in the checkout's fixed cache
    directory, whatever the machine's environment says."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    configure_jax()
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"perf/run.py: {e}", file=sys.stderr)
        return 3
    _print_result(result)
    return 0


if __name__ == "__main__":
    with contextlib.suppress(BrokenPipeError):
        sys.exit(main())
