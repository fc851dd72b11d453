"""TPU evidence session: every hardware measurement of a round, in one
resilient run on the chip:

  1. device identity (device_kind, HBM stats)
  2. flagship bench 800x1200 + the two
     larger published grids — golden iteration counts and L2 land in the
     same JSON lines (re-validating the post-tree-sum kernels on hardware)
  3. roofline sweep at 2400x3200 (strip heights x sequential/parallel
     grid) and 1600x2400 — settles the large-grid plateau question
  4. the masked sharded kernels Mosaic-compiled and run on a real chip
     (1x1 mesh, 800x1200): golden count + L2 vs analytic
  5. beyond-reference grids: 4800x4800 probe and the 16384x16384
     north-star attempt (fixed-iteration MLUPS probe; allocation failures
     are recorded with memory stats, not raised)
  6. report artifacts: L2-vs-iteration curve CSV (+ PNG if matplotlib is
     usable) and a cross-backend sweep table

Every step runs as a subprocess with its own timeout, and this parent
never imports JAX: each step is the one process holding the chip.
Failures are recorded and the session moves on. Results land in ``benchmarks/results/``
as JSON-lines (``session.jsonl``) plus the artifact files, ready to commit.

Usage:  python benchmarks/tpu_session.py [--quick] [--outdir DIR]
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import pathlib
import subprocess
import sys

_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT))


def _utc() -> str:
    return datetime.datetime.now(datetime.timezone.utc).isoformat(
        timespec="seconds"
    )


def _recorded_layouts(rec) -> set:
    """Every reduction layout a step's recorded result attributes itself
    to, wherever the step reports it: top level (the kernel/CA/grid
    probes), ``detail`` (bench.py's JSON line), or per-row ``solver``
    entries (roofline.py's report). A bench record whose backend is
    ``xla`` makes no layout claim — the stamp records the ambient env,
    but no Pallas kernel ran, so the number is layout-independent. A
    result naming NO layout — or, pathologically, two — is handled by
    the caller (no-claim replays stand; mixed-layout results can never
    match one launch layout and are dropped)."""
    found = set()
    if not isinstance(rec, dict):
        return found
    if rec.get("serial_reduce") is not None:
        found.add(bool(rec["serial_reduce"]))
    det = rec.get("detail")
    if isinstance(det, dict) and det.get("serial_reduce") is not None \
            and det.get("backend") != "xla":
        found.add(bool(det["serial_reduce"]))
    rows = rec.get("solver")
    if isinstance(rows, list):
        for row in rows:
            if isinstance(row, dict) and row.get("serial_reduce") is not None:
                found.add(bool(row["serial_reduce"]))
    return found


class Session:
    def __init__(self, outdir: pathlib.Path, resume_after: str | None = None):
        self.outdir = outdir
        outdir.mkdir(parents=True, exist_ok=True)
        self.log = outdir / "session.jsonl"
        # Hung-device defense: after any step timeout the device is
        # re-probed with a cheap 150 s identity check; a dead probe aborts
        # the session (the remaining steps would only time out in turn),
        # and a relaunch resumes. A timeout with an ALIVE probe is a
        # slow-step statement: the session presses on (each step's own
        # timeout bounds the cost).
        self.consecutive_timeouts = 0
        self.aborted = False
        # Resume support: on a relaunch, steps that already recorded ok
        # AFTER `resume_after` (entries from earlier rounds must not
        # satisfy a fresh session) are replayed from the log instead of
        # re-run, so a hang mid-session costs only the steps it ate.
        self.prior: dict[str, dict] = {}
        if resume_after and self.log.exists():
            for line in self.log.read_text().splitlines():
                try:
                    e = json.loads(line)
                except ValueError:
                    continue
                if not (e.get("ok") and e.get("step")
                        and e.get("at", "") >= resume_after):
                    continue
                if e.get("step") == "identity":
                    # The liveness gate must always run live: replaying a
                    # stale identity would let a hung-device session march
                    # into its step budget.
                    continue
                if "result" in e and e.get("result") is None:
                    # ok-but-unparseable: replaying the null would make a
                    # relaunch fail identically forever; re-run instead.
                    continue
                self.prior[e["step"]] = e
        # A replayed result is credited to a LAYOUT (the kernel gate's
        # verdict names one; bench/ca/grid/roofline numbers are layout-
        # dependent evidence), so any step that recorded which reduction
        # layout it ran may only replay into a launch that would run it
        # under the same layout; on mismatch the replay is dropped and
        # the step re-runs live (a relaunch with a different
        # POISSON_TPU_SERIAL_REDUCE would otherwise credit the wrong
        # layout). The two explicit A/B steps run under a forced pin
        # regardless of the ambient env.
        pinned = os.environ.get("POISSON_TPU_SERIAL_REDUCE") == "1"
        forced = {"kernel_probe_serial": True, "kernel_probe_default": False}
        for step in list(self.prior):
            layouts = _recorded_layouts(self.prior[step].get("result"))
            want = forced.get(step, pinned)
            if layouts and layouts != {want}:
                del self.prior[step]

    def record(self, step: str, payload: dict) -> None:
        entry = {"step": step, "at": _utc(), **payload}
        with self.log.open("a") as f:
            f.write(json.dumps(entry) + "\n")
        print(f"[{step}] {json.dumps(payload)[:300]}", flush=True)

    def decide_layout(self, serial: bool, reason: str,
                      affirmative: bool = True) -> None:
        """Record the kernel-layout decision in the log. ``affirmative``
        says whether a probe proved that layout healthy on the chip (an
        inconclusive or failed probe records the kept layout with
        ``affirmative: false``). Later runs read no verdict from here:
        the layout is the ``POISSON_TPU_SERIAL_REDUCE`` they are given."""
        self.record("layout_decision", {
            "serial_reduce": serial, "reason": reason,
            "affirmative": affirmative, "at": _utc()})

    def _device_alive(self) -> bool:
        """Cheap liveness re-probe (150 s cap) — device identity only."""
        try:
            proc = subprocess.run(
                [sys.executable, "-c",
                 "import jax\n"
                 "assert jax.devices()[0].platform == 'tpu'\n"],
                cwd=_ROOT, env=dict(os.environ), text=True,
                capture_output=True, timeout=150,
            )
            return proc.returncode == 0
        except subprocess.TimeoutExpired:
            return False

    def run(self, step: str, argv: list[str], timeout: float,
            parse_json_tail: bool = False,
            extra_env: dict[str, str] | None = None) -> dict | None:
        """Run a subprocess step; record rc/output; never raise.

        Failures return a dict with ``ok: False`` that distinguishes a
        timeout (``timeout: True`` — usually a device statement) from a
        nonzero exit (``rc`` — an in-process verdict, e.g. a
        libtpu/Mosaic abort, with stderr recorded); callers that need to
        attribute blame (the kernel-layout gate) rely on the difference.
        ``None`` is only returned when a zero-exit step produced no
        parseable JSON tail."""
        if self.aborted:
            self.record(step, {"ok": False, "skipped": "session aborted "
                               "(hung-device defense); relaunch to resume"})
            return {"ok": False, "skipped": True}
        if step in self.prior:
            e = self.prior[step]
            replay = {"ok": True, "resumed_from": e.get("at")}
            if "result" in e:
                replay["result"] = e.get("result")
            self.record(step, replay)
            if parse_json_tail:
                return e.get("result")
            return {"ok": True, "stdout": e.get("stdout", "")}
        try:
            proc = subprocess.run(
                argv, cwd=_ROOT, env={**os.environ, **(extra_env or {})},
                text=True, capture_output=True, timeout=timeout,
            )
        except subprocess.TimeoutExpired:
            self.record(step, {"ok": False, "error": f"timeout>{timeout:.0f}s"})
            self.consecutive_timeouts += 1
            alive = self._device_alive()
            if not alive:
                self.aborted = True
                self.record("abort", {
                    "reason": f"hung-device defense: step timed out and the "
                              f"liveness probe is dead "
                              f"({self.consecutive_timeouts} consecutive "
                              "timeout(s)); remaining steps skipped, "
                              "relaunch with --resume-after to resume",
                })
            return {"ok": False, "timeout": True}
        self.consecutive_timeouts = 0
        out = proc.stdout.strip()
        if proc.returncode != 0:
            # Full stderr to a file: the jsonl line keeps a 1500-char tail,
            # but a Mosaic/libtpu abort's real error can be far longer and
            # root-causing it needs every line (VERDICT r3 item 2).
            err_path = self.outdir / f"{step}_stderr.txt"
            entry = {
                "ok": False, "rc": proc.returncode,
                "stderr": proc.stderr[-1500:], "stdout": out[-500:],
            }
            try:
                err_path.write_text(proc.stderr)
                entry["stderr_file"] = err_path.name
            except OSError:
                pass
            self.record(step, entry)
            return {"ok": False, "rc": proc.returncode}
        payload: dict = {"ok": True}
        parsed = None
        if parse_json_tail and out:
            for line in reversed(out.splitlines()):
                line = line.strip()
                if line.startswith("{"):
                    try:
                        parsed = json.loads(line)
                        break
                    except ValueError:
                        continue
            payload["result"] = parsed
        else:
            payload["stdout"] = out[-2000:]
        if proc.stderr.strip():
            # Warnings ride along even on success.
            payload["stderr"] = proc.stderr.strip()[-1500:]
        self.record(step, payload)
        return parsed if parse_json_tail else payload


_KERNEL_PROBE = r"""
import json, sys, time
import jax
from poisson_tpu.analysis import l2_error_host
from poisson_tpu.config import Problem
from poisson_tpu.ops.pallas_cg import pallas_cg_solve, SERIAL_REDUCE

dev = jax.devices()[0]
assert dev.platform == "tpu", dev.platform
out = {"serial_reduce": SERIAL_REDUCE}
try:
    p = Problem(M=40, N=40)
    r = pallas_cg_solve(p)
    out["tiny_iters"] = int(r.iterations)
    p = Problem(M=800, N=1200)
    t0 = time.perf_counter()
    r = pallas_cg_solve(p)
    k = int(r.iterations)
    # Same tolerance bench.py grants its sanity probe: reduction-order
    # drift of O(0.1%) is healthy; anything larger means broken kernels.
    out.update(ok=(abs(out["tiny_iters"] - 50) <= 5 and abs(k - 989) <= 9),
               flagship_iters=k, l2=l2_error_host(p, r.w),
               compile_and_first_s=round(time.perf_counter() - t0, 1))
except Exception as e:
    import traceback, pathlib
    tb = traceback.format_exc()
    # Full error text to a committed-results file: root-causing a Mosaic
    # machine-code failure needs every line, and the round-3 failure left
    # no error text anywhere in the repo (VERDICT r3 item 2).
    name = "kernel_probe_error_serial.txt" if SERIAL_REDUCE else "kernel_probe_error.txt"
    pathlib.Path("benchmarks/results").mkdir(parents=True, exist_ok=True)
    pathlib.Path("benchmarks/results", name).write_text(tb)
    out.update(ok=False, error=tb[-1800:], error_file=name)
print(json.dumps(out))
"""


_CA_PROBE = r"""
import json, sys, time, dataclasses
import jax
from poisson_tpu.analysis import l2_error_host
from poisson_tpu.config import Problem
from poisson_tpu.ops.pallas_ca import ca_cg_solve
from poisson_tpu.ops.pallas_cg import SERIAL_REDUCE
from poisson_tpu.utils.timing import fence, mlups

dev = jax.devices()[0]
assert dev.platform == "tpu", dev.platform
out = {"backend": "pallas_ca(s=2)", "serial_reduce": SERIAL_REDUCE,
       "device_kind": dev.device_kind}
# Each stage guarded: whatever was measured before a failure still lands
# in the JSON (the session charter: failures recorded, never raised).
try:
    # Correctness on the flagship grid: golden count + L2 at the floor.
    p = Problem(M=800, N=1200)
    t0 = time.perf_counter()
    res = ca_cg_solve(p)
    fence(res.iterations)
    out.update(ok=True, flagship_iters=int(res.iterations), golden=989,
               l2=l2_error_host(p, res.w),
               compile_and_first_s=round(time.perf_counter() - t0, 1))
    t0 = time.perf_counter()
    res = ca_cg_solve(p)
    fence(res.iterations)
    solve = time.perf_counter() - t0
    out.update(flagship_solve_s=round(solve, 4),
               flagship_mlups=round(mlups(p, int(res.iterations), solve), 1))
except Exception:
    import traceback
    out.update(ok=False, error=traceback.format_exc()[-1500:])
if out.get("ok"):
    try:
        # Plateau grid: fixed-iteration slope (convergence disabled), the
        # traffic-reduction measurement VERDICT r2 #5 asks for.
        big = Problem(M=2400, N=3200, delta=1e-30, max_iter=200)
        lo = dataclasses.replace(big, max_iter=50)
        for q in (lo, big):
            r = ca_cg_solve(q)
            fence(r.iterations)
        t0 = time.perf_counter()
        r = ca_cg_solve(lo)
        fence(r.iterations)
        t_lo = time.perf_counter() - t0
        t0 = time.perf_counter()
        r = ca_cg_solve(big)
        fence(r.iterations)
        t_hi = time.perf_counter() - t0
        per_iter = (t_hi - t_lo) / (big.max_iter - lo.max_iter)
        out.update(big_grid=[2400, 3200],
                   big_iter_seconds=round(per_iter, 6),
                   big_mlups=round(2399 * 3199 / per_iter / 1e6, 1))
    except Exception:
        import traceback
        out.update(big_grid_error=traceback.format_exc()[-1200:])
print(json.dumps(out))
"""


_SHARDED_1X1 = r"""
import json
import jax
import numpy as np
from poisson_tpu.config import Problem
from poisson_tpu.parallel import make_solver_mesh
from poisson_tpu.parallel.pallas_sharded import pallas_cg_solve_sharded
from poisson_tpu.analysis import l2_error_host
from poisson_tpu.utils.timing import fence, mlups
import time

dev = jax.devices()[0]
assert dev.platform == "tpu", dev.platform
mesh = make_solver_mesh(jax.devices()[:1], grid=(1, 1))
problem = Problem(M=800, N=1200)
t0 = time.perf_counter()
res = pallas_cg_solve_sharded(problem, mesh, interpret=False)
fence(res.iterations)
first = time.perf_counter() - t0
t0 = time.perf_counter()
res = pallas_cg_solve_sharded(problem, mesh, interpret=False)
fence(res.iterations)
solve = time.perf_counter() - t0
print(json.dumps({
    "backend": "pallas_sharded(masked, Mosaic)", "mesh": [1, 1],
    "grid": [800, 1200], "iterations": int(res.iterations),
    "golden": 989, "l2_error": l2_error_host(problem, res.w),
    "compile_and_first_s": round(first, 2),
    "solve_s": round(solve, 4),
    "mlups": round(mlups(problem, int(res.iterations), solve), 1),
    "device_kind": dev.device_kind,
}))
"""

_RESIDENT_PROBE = r"""
import json, time
import jax
import jax.numpy as jnp
from poisson_tpu.analysis import l2_error_host
from poisson_tpu.config import Problem
from poisson_tpu.ops.pallas_resident import resident_cg_solve

dev = jax.devices()[0]
assert dev.platform == "tpu", dev.platform
out = {"backend": "pallas_resident(persistent kernel)",
       "device_kind": dev.device_kind, "grids": {}}
for (M, N, golden) in ((40, 40, 50), (400, 600, 546)):
    p = Problem(M=M, N=N)
    rec = {"golden": golden}
    try:
        t0 = time.perf_counter()
        r = resident_cg_solve(p)
        r.diff.block_until_ready()
        rec["compile_and_first_s"] = round(time.perf_counter() - t0, 1)
        rec["iterations"] = int(r.iterations)
        rec["l2"] = l2_error_host(p, r.w)
        # Correctness verdict lands BEFORE the timing section: a noisy
        # or failed slope must not erase hardware evidence that the
        # kernel ran and converged at the golden count.
        rec["ok"] = abs(rec["iterations"] - golden) <= 1
        # Single-launch solves are close to the constant fetch latency,
        # so time a data-dependency chain at two lengths and take the
        # slope (bench.py's methodology).
        def chain(k):
            gate = jnp.float32(1.0)
            t0 = time.perf_counter()
            for _ in range(k):
                rr = resident_cg_solve(p, rhs_gate=gate)
                gate = (rr.diff * 0.0 + 1.0).astype(jnp.float32)
            rr.diff.block_until_ready()
            return time.perf_counter() - t0
        chain(2)  # warm the gated trace
        t_lo = min(chain(2) for _ in range(3))
        t_hi = min(chain(8) for _ in range(3))
        solve = (t_hi - t_lo) / 6
        if solve > 0:
            rec["solve_s"] = round(solve, 5)
            rec["mlups"] = round(
                (M - 1) * (N - 1) * rec["iterations"] / solve / 1e6, 1
            )
        else:
            rec["timing_note"] = (
                f"slope within timer noise (t_lo={t_lo:.5f}, "
                f"t_hi={t_hi:.5f}); correctness verdict stands"
            )
    except Exception:
        import traceback
        err = traceback.format_exc()[-1200:]
        if "ok" in rec:
            rec["timing_error"] = err   # correctness verdict stands
        else:
            rec.update(ok=False, error=err)
    out["grids"][f"{M}x{N}"] = rec
out["ok"] = all(g.get("ok") for g in out["grids"].values())
print(json.dumps(out))
"""

_CA_SHARDED_1X1 = r"""
import json
import jax
from poisson_tpu.config import Problem
from poisson_tpu.parallel import make_solver_mesh
from poisson_tpu.parallel.pallas_ca_sharded import ca_cg_solve_sharded
from poisson_tpu.analysis import l2_error_host
from poisson_tpu.utils.timing import fence, mlups
import time

dev = jax.devices()[0]
assert dev.platform == "tpu", dev.platform
mesh = make_solver_mesh(jax.devices()[:1], grid=(1, 1))
problem = Problem(M=800, N=1200)
t0 = time.perf_counter()
res = ca_cg_solve_sharded(problem, mesh, interpret=False)
fence(res.iterations)
first = time.perf_counter() - t0
t0 = time.perf_counter()
res = ca_cg_solve_sharded(problem, mesh, interpret=False)
fence(res.iterations)
solve = time.perf_counter() - t0
print(json.dumps({
    "backend": "pallas_ca_sharded(masked, Mosaic)", "mesh": [1, 1],
    "grid": [800, 1200], "iterations": int(res.iterations),
    "golden": 989, "l2_error": l2_error_host(problem, res.w),
    "compile_and_first_s": round(first, 2),
    "solve_s": round(solve, 4),
    "mlups": round(mlups(problem, int(res.iterations), solve), 1),
    "device_kind": dev.device_kind,
}))
"""

_BIG_GRID = r"""
import json, sys, time, dataclasses
import jax
import jax.numpy as jnp
from poisson_tpu.config import Problem
from poisson_tpu.ops.pallas_cg import (
    SERIAL_REDUCE, build_canvases, _fused_solve,
)

M, N, iters = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
# argv bn: 0 (or absent) measures the TRUE full-width geometry (the
# canvas_spec sentinel that disables the auto-blocking pick).
bn = int(sys.argv[4]) if len(sys.argv) > 4 else 0
dev = jax.devices()[0]
assert dev.platform == "tpu", dev.platform
out = {"grid": [M, N], "bn": bn or None, "serial_reduce": SERIAL_REDUCE,
       "device_kind": dev.device_kind}
try:
    problem = Problem(M=M, N=N, delta=1e-30, max_iter=iters)
    cv, cs, cw, g, rhs, sc2, _ = build_canvases(problem, None, "float32", bn)
    canvases_gb = 8 * cv.rows * cv.cols * 4 / 2**30
    out.update(bm=cv.bm, nb=cv.nb, canvas_rows=cv.rows, canvas_cols=cv.cols,
               working_set_gb=round(canvases_gb, 2))
    lo = dataclasses.replace(problem, max_iter=max(5, iters // 4))
    s = _fused_solve(lo, cv, False, False, SERIAL_REDUCE, cs, cw, g, rhs, sc2)
    s.diff.block_until_ready()
    t0 = time.perf_counter()
    s = _fused_solve(lo, cv, False, False, SERIAL_REDUCE, cs, cw, g, rhs, sc2)
    s.diff.block_until_ready()
    t_lo = time.perf_counter() - t0
    s = _fused_solve(problem, cv, False, False, SERIAL_REDUCE, cs, cw, g, rhs, sc2)
    s.diff.block_until_ready()
    t0 = time.perf_counter()
    s = _fused_solve(problem, cv, False, False, SERIAL_REDUCE, cs, cw, g, rhs, sc2)
    s.diff.block_until_ready()
    t_hi = time.perf_counter() - t0
    per_iter = (t_hi - t_lo) / (problem.max_iter - lo.max_iter)
    out.update(ok=True, iter_seconds=round(per_iter, 6),
               mlups=round((M - 1) * (N - 1) / per_iter / 1e6, 1),
               probe_iters=iters)
except Exception as e:
    try:
        stats = jax.devices()[0].memory_stats() or {}
    except Exception:
        stats = {}
    out.update(ok=False, error=repr(e)[:600],
               hbm_limit_gb=round(stats.get("bytes_limit", 0) / 2**30, 1),
               hbm_in_use_gb=round(stats.get("bytes_in_use", 0) / 2**30, 2))
print(json.dumps(out))
"""


def _bench_value(rec, backend_name: str):
    """The bench.py headline value from ``rec``, credited ONLY when the
    record says that exact backend produced it ON REAL HARDWARE.
    A CPU number must never enter the verdict as hardware evidence."""
    if not isinstance(rec, dict):
        return None
    det = rec.get("detail") or {}
    if det.get("backend") == backend_name and det.get("platform") == "tpu":
        value = rec.get("value")
        if value is None:
            # A hardware-labeled record with no value is malformed; say
            # so rather than silently treating the backend as unproven.
            print(f"[decide_backend_chain] hardware-labeled {backend_name} "
                  "record excluded: no 'value' in bench result", flush=True)
        return value
    return None


def decide_backend_chain(bench800, ca, fused_probe_ok,
                         bench_ca_runner, bench_fused_runner,
                         xla_runner=None):
    """The session's measured backend preference (recorded in the log),
    or None for no statement.

    Only backends with affirmative evidence from THIS session enter the
    chain, fastest first. A Pallas-labeled bench value is affirmative by
    itself — bench.py fails a backend that misses the golden count. Both
    sides of the speed comparison use bench.py's fetch-cancelled slope
    methodology: the probes' single-solve timings include the constant
    fetch latency and would make a faster backend lose a comparison it
    deserves to win. So when a probe proved a backend correct but
    bench800 ran a different one, the matching forced runner
    (BENCH_BACKEND=<name>) is invoked for a bench-grade number
    (``fused_probe_ok`` is the kernel-probe gate's verdict for the fused
    path under the session's layout; ``ca`` is the CA probe).

    An explicit ``{"chain": []}`` says xla measured fastest, or the
    flagship bench on TPU ran xla and no Pallas backend proved healthy.
    """
    fused_v = _bench_value(bench800, "pallas_fused")
    ca_v = _bench_value(bench800, "pallas_ca")
    ca_ok = bool(isinstance(ca, dict) and ca.get("ok")
                 and abs(int(ca.get("flagship_iters") or 0) - 989) <= 9)
    if ca_ok and ca_v is None:
        ca_v = _bench_value(bench_ca_runner(), "pallas_ca")
    if fused_probe_ok and fused_v is None:
        fused_v = _bench_value(bench_fused_runner(), "pallas_fused")
    proven = [(name, v) for name, v in
              (("pallas_ca", ca_v), ("pallas_fused", fused_v))
              if v is not None]
    proven.sort(key=lambda t: -t[1])
    det800 = (bench800.get("detail") or {}) if isinstance(bench800, dict) \
        else {}
    xla_v = _bench_value(bench800, "xla")
    if xla_v is None and xla_runner is not None and proven:
        # The Pallas pass models are unvalidated against this chip (the
        # prior round's Pallas rows imply >2 TB/s on an ~0.8 TB/s part,
        # i.e. a measurement artifact) — XLA's fusion may honestly win.
        # The chain must reflect the measured maximum, so XLA gets the
        # same bench-grade measurement as the Pallas candidates.
        xla_v = _bench_value(xla_runner(), "xla")
    evidence = dict(proven)
    if xla_v is not None:
        evidence["xla"] = xla_v
    if proven and (xla_v is None or proven[0][1] > xla_v):
        return {
            "chain": [n for n, _ in proven], "at": _utc(),
            "evidence": evidence,
        }
    if proven:
        # Pallas backends ran healthy but XLA measured faster: the
        # measured maximum is xla, so the chain is empty with the losing
        # Pallas numbers preserved as evidence.
        return {
            "chain": [], "at": _utc(),
            "evidence": evidence,
            "note": "xla measured fastest on hardware this session; "
                    "healthy Pallas numbers preserved in evidence",
        }
    if det800.get("platform") == "tpu" and det800.get("backend") == "xla":
        return {
            "chain": [], "at": _utc(),
            "evidence": evidence,
            "note": "flagship bench on TPU ran xla; no Pallas "
                    "backend proved healthy this session",
        }
    return None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--outdir", default=str(_ROOT / "benchmarks" / "results"))
    ap.add_argument("--quick", action="store_true",
                    help="flagship + sharded-1x1 + roofline only")
    ap.add_argument("--resume-after", default=None, metavar="ISO_UTC",
                    help="replay ok-steps recorded at/after this UTC "
                         "timestamp instead of re-running them (pass the "
                         "aborted session's start time on a relaunch)")
    args = ap.parse_args()
    s = Session(pathlib.Path(args.outdir), resume_after=args.resume_after)
    py = sys.executable
    # The session owns its bench steps: an ambient BENCH_BACKEND pin
    # inherited from the operator's shell would stop bench800 from
    # attempting the Pallas chain and turn into false negative evidence
    # in the backend-chain artifact. Forced steps set their own pin.
    os.environ.pop("BENCH_BACKEND", None)

    # 1. identity — also the device liveness gate for the whole session
    ident = s.run("identity", [
        py, "-c",
        "import json\n"
        "import jax\n"
        "d = jax.devices()[0]\n"
        "m = {}\n"
        "try: m = d.memory_stats() or {}\n"
        "except Exception: pass\n"
        "print(json.dumps({'platform': d.platform, 'kind': d.device_kind, "
        "'n': len(jax.devices()), "
        "'hbm_gb': round(m.get('bytes_limit', 0) / 2**30, 1)}))",
    ], timeout=150, parse_json_tail=True)
    if not ident or ident.get("platform") != "tpu":
        s.record("abort", {"reason": "no healthy TPU; nothing captured"})
        return 2 if s.aborted else 1

    # 1.5 kernel health: the fused path must actually run on hardware
    # before anything downstream leans on it. The probe tests whichever
    # reduction layout the ambient env selects (normally the per-strip
    # partial default; an operator can pre-pin serial-Kahan); if that
    # layout fails Mosaic, A/B the OTHER layout and — when it works —
    # adopt it for every remaining step (subprocesses inherit our env).
    # Produces the layout A/B evidence either way. Layout-symmetric on
    # purpose: the verdict must name the layout that actually ran, not
    # assume the default did.
    def _no_verdict(p):
        # Timeout / skip / no result is a device statement, not a kernel
        # one — it must not indict (or acquit) either layout.
        return p is None or (isinstance(p, dict)
                             and (p.get("timeout") or p.get("skipped")))

    pinned_serial = os.environ.get("POISSON_TPU_SERIAL_REDUCE", "0") == "1"
    first_name = "serial-Kahan" if pinned_serial else "per-strip partial"
    alt_name = "per-strip partial" if pinned_serial else "serial-Kahan"

    # The fused path's health under the session's ADOPTED layout — set by
    # whichever probe below ends up green; feeds decide_backend_chain.
    fused_probe_ok = False

    probe = s.run("kernel_probe", [py, "-c", _KERNEL_PROBE],
                  timeout=900, parse_json_tail=True)
    if _no_verdict(probe):
        # One retry; if still inconclusive, keep the current layout and
        # make no layout claim.
        probe = s.run("kernel_probe_retry", [py, "-c", _KERNEL_PROBE],
                      timeout=900, parse_json_tail=True)
    if _no_verdict(probe):
        s.decide_layout(
            pinned_serial,
            f"{first_name}-layout probe inconclusive twice (timeout "
            "or no result); keeping it — no statement about either "
            "layout's hardware health",
            affirmative=False,
        )
    elif not probe.get("ok"):
        # Definitive in-process verdict against the probed layout: a
        # nonzero exit (Mosaic/libtpu abort — stderr recorded), a Python
        # exception, or suspect iteration counts. A/B the other layout.
        if "rc" in probe:
            first_verdict = (
                f"crashed on hardware (rc={probe['rc']}, stderr recorded)"
            )
        elif "error" in probe:
            first_verdict = "failed on hardware (exception)"
        else:
            first_verdict = (
                f"suspect iteration counts ({probe.get('tiny_iters')}, "
                f"{probe.get('flagship_iters')})"
            )
        os.environ["POISSON_TPU_SERIAL_REDUCE"] = (
            "0" if pinned_serial else "1"
        )
        alt_step = ("kernel_probe_default" if pinned_serial
                    else "kernel_probe_serial")
        probe2 = s.run(alt_step, [py, "-c", _KERNEL_PROBE],
                       timeout=900, parse_json_tail=True)
        if probe2 and probe2.get("ok"):
            fused_probe_ok = True
            s.decide_layout(
                not pinned_serial,
                f"{first_name} layout {first_verdict}; {alt_name} "
                "layout probed healthy and is adopted for the rest "
                "of the session",
            )
        else:
            # Restore the layout the session started with.
            if pinned_serial:
                os.environ["POISSON_TPU_SERIAL_REDUCE"] = "1"
            else:
                del os.environ["POISSON_TPU_SERIAL_REDUCE"]
            s.decide_layout(
                pinned_serial,
                f"{first_name} layout {first_verdict}; {alt_name} "
                "layout did not probe healthy either — keeping the "
                f"{first_name} layout",
                # Not affirmative: the kept layout just failed its own
                # probe, and an alt probe lost to a hang says nothing
                # about the alt layout.
                affirmative=False,
            )
    else:
        # The probed layout ran clean on the chip — an affirmative
        # verdict.
        fused_probe_ok = True
        s.decide_layout(
            pinned_serial,
            f"{first_name} layout probed healthy on "
            f"hardware (flagship {probe.get('flagship_iters')} iters, "
            f"l2={probe.get('l2')})",
        )

    # 2. benches (flagship first)
    bench800 = None
    for grid, to in (((800, 1200), 900), ((1600, 2400), 1200),
                     ((2400, 3200), 1800)):
        if args.quick and grid != (800, 1200):
            continue
        got = s.run(f"bench_{grid[0]}x{grid[1]}",
                    [py, "bench.py", str(grid[0]), str(grid[1])],
                    timeout=to, parse_json_tail=True)
        if grid == (800, 1200):
            bench800 = got

    # 3. masked sharded kernels on the real chip (1x1 mesh) — cheap, so
    # it runs right after the benches.
    s.run("sharded_1x1_mosaic", [py, "-c", _SHARDED_1X1],
          timeout=1200, parse_json_tail=True)

    # 3.2 the sharded CA variant on the real chip (1x1 mesh): Mosaic-
    # compiles the ±2-band masked CA kernels + width-2 ring exchange —
    # the round-5 sharded-CA build's hardware verdict.
    s.run("ca_sharded_1x1_mosaic", [py, "-c", _CA_SHARDED_1X1],
          timeout=1200, parse_json_tail=True)

    # 3.3 the VMEM-resident persistent kernel (round 5): whole solve in
    # one launch at the small published grids — golden + L2 + the
    # chained-slope timing (the small-tier record attempt).
    s.run("resident_probe", [py, "-c", _RESIDENT_PROBE],
          timeout=900, parse_json_tail=True)

    # 3.5 communication-avoiding pair-iteration: golden + L2 on the
    # flagship grid, fixed-iteration slope at the 2400x3200 plateau (the
    # algorithmic traffic-reduction A/B for the roofline story). Ahead
    # of the rooflines: if the session is cut, the CA hardware verdict
    # outranks another geometry sweep.
    ca = s.run("ca_probe", [py, "-c", _CA_PROBE],
               timeout=1800, parse_json_tail=True)

    # 3.6 hardware-measured backend preference, recorded in the log.
    payload = decide_backend_chain(
        bench800, ca, fused_probe_ok,
        lambda: s.run("bench_800x1200_ca", [py, "bench.py", "800", "1200"],
                      timeout=900, parse_json_tail=True,
                      extra_env={"BENCH_BACKEND": "pallas_ca"}),
        lambda: s.run("bench_800x1200_fused",
                      [py, "bench.py", "800", "1200"],
                      timeout=900, parse_json_tail=True,
                      extra_env={"BENCH_BACKEND": "pallas_fused"}),
        xla_runner=lambda: s.run(
            "bench_800x1200_xla", [py, "bench.py", "800", "1200"],
            timeout=900, parse_json_tail=True,
            extra_env={"BENCH_BACKEND": "xla"}),
    )
    if payload is not None:
        s.record("backend_chain", payload)

    # 4. roofline (full-width strip heights x parallel, plus the
    # column-blocked geometry at its auto strip height)
    s.run("roofline_2400x3200", [
        py, "benchmarks/roofline.py", "2400", "3200",
        "--bm", "48,72,96", "--iters", "200", "--parallel",
    ], timeout=1800, parse_json_tail=True)
    s.run("roofline_2400x3200_blocked", [
        py, "benchmarks/roofline.py", "2400", "3200",
        "--bn", "1024,2048", "--iters", "200", "--parallel",
    ], timeout=1800, parse_json_tail=True)
    # CA pass-model A/B at the plateau: the same stream ceiling, the CA
    # ~10.1-pass model vs the fused ~14.7 — settles whether the measured
    # CA advantage (ca_probe) matches its traffic model.
    s.run("roofline_2400x3200_ca", [
        py, "benchmarks/roofline.py", "2400", "3200",
        "--backend", "ca", "--bm", "48,72", "--iters", "200",
    ], timeout=1800, parse_json_tail=True)
    if not args.quick:
        s.run("roofline_1600x2400", [
            py, "benchmarks/roofline.py", "1600", "2400",
            "--bm", "64,128", "--iters", "200", "--parallel",
        ], timeout=1200, parse_json_tail=True)

    # 5. beyond-reference grids (full-width and column-blocked geometries)
    s.run("grid_4800x4800", [py, "-c", _BIG_GRID, "4800", "4800", "50"],
          timeout=900, parse_json_tail=True)
    s.run("grid_4800x4800_bn1024",
          [py, "-c", _BIG_GRID, "4800", "4800", "50", "1024"],
          timeout=900, parse_json_tail=True)
    # Host-side field build alone is ~6-7 min at 16384^2 (measured), plus
    # a ~9 GiB canvas transfer to the device — budget generously.
    s.run("grid_16384x16384_bn2048",
          [py, "-c", _BIG_GRID, "16384", "16384", "50", "2048"],
          timeout=3600, parse_json_tail=True)
    s.run("grid_16384x16384", [py, "-c", _BIG_GRID, "16384", "16384", "50"],
          timeout=3600, parse_json_tail=True)

    if not args.quick:
        # 6. report artifacts
        curve = str(s.outdir / "curve_800x1200_tpu.csv")
        # sweep.py always emits its table too: pin it to one cheap row so
        # the chip time is spent on the curve, not a duplicate
        # sweep (the real table is the dedicated sweep_table step below).
        got = s.run("curve_800x1200", [
            py, "benchmarks/sweep.py", "--curve", "800x1200:989",
            "--curve-out", curve, "--grids", "40x40",
            "--backends", "xla", "--repeat", "1",
        ], timeout=1200)
        if got and got.get("ok"):
            s.run("curve_png", [
                py, "benchmarks/plot_curve.py", curve,
                str(s.outdir / "curve_800x1200_tpu.png"),
            ], timeout=300)
        s.run("sweep_table", [
            py, "benchmarks/sweep.py", "--grids",
            "400x600,800x1200,1600x2400,2400x3200",
            "--backends", "pallas,pallas-ca,xla", "--repeat", "2",
            "--out", str(s.outdir / "sweep_tpu.md"),
        ], timeout=3600)

    if s.aborted:
        s.record("done", {"log": str(s.log), "aborted": True})
        return 2  # relaunch with --resume-after to resume
    s.record("done", {"log": str(s.log)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
