"""Programmatic, fenced ``jax.profiler.trace`` capture on the span rails.

Before this module, device-timeline capture existed as exactly one CLI
flag on one path (``python -m poisson_tpu … --profile DIR``). Here it is
a first-class telemetry sink with the same env-driven configuration as
the rest of the stack (``POISSON_TPU_PROFILE_DIR``, or
``obs.configure(profile_dir=…)``), usable from bench.py, the batched
driver, and the sharded solvers without touching their argv contracts:

    from poisson_tpu.obs import profile
    with profile.capture("bench.solve"):
        fence(run().iterations)

``capture`` is a no-op null context when no directory is configured —
call sites never guard. When configured, the region is bracketed by a
``jax.profiler.trace`` into ``<dir>/<name>/`` AND recorded as an
``obs`` span (so the profiler capture itself is visible — and
attributable — on the Perfetto timeline), with the device fenced via
``jax.effects_barrier`` before the trace closes so in-flight work lands
inside the capture window instead of dribbling past it. Each capture
increments the ``profile.captures`` counter and emits a
``profile.capture`` event carrying the artifact path.

Captures are for *extra* runs, not timed ones: profiling perturbs the
very latencies the bench measures, so the drivers capture one
additional solve after their timed section (the pattern the CLI's
``--profile`` always used, now shared).
"""

from __future__ import annotations

import contextlib
import os
from typing import Optional

_PROFILE_DIR: Optional[str] = None


def configure(profile_dir: Optional[str]) -> None:
    """Install (or clear, with None) the process-wide capture directory.
    Called by :func:`poisson_tpu.obs.configure`; safe to call directly."""
    global _PROFILE_DIR
    _PROFILE_DIR = profile_dir or None


def profile_dir() -> Optional[str]:
    """The active capture directory (None = capture() is a no-op)."""
    return _PROFILE_DIR


def configure_from_env() -> None:
    """Adopt ``POISSON_TPU_PROFILE_DIR`` when no directory is configured
    yet — the one idiom every entry point (CLI solve, batched CLI,
    bench) shares, kept here so the env contract has a single owner."""
    if _PROFILE_DIR is None:
        configure(os.environ.get("POISSON_TPU_PROFILE_DIR"))


def enabled() -> bool:
    return _PROFILE_DIR is not None


@contextlib.contextmanager
def capture(name: str, profile_dir: Optional[str] = None):
    """Fenced profiler capture of the enclosed region into
    ``<dir>/<name>/`` (an explicit ``profile_dir`` wins over the
    configured one; with neither, a zero-cost null context).

    Best-effort by design: a profiler that cannot start (unsupported
    runtime, unwritable disk) must never take the solve down — the
    region still runs, the failure lands on the ``profile.errors``
    counter and as a ``profile.capture_failed`` event.
    """
    target = profile_dir or _PROFILE_DIR
    if not target:
        yield None
        return

    from poisson_tpu import obs

    out = os.path.join(target, name.replace("/", "_"))
    try:
        import jax

        trace_cm = jax.profiler.trace(out)
        trace_cm.__enter__()
    except Exception as e:
        metrics_note = repr(e)[:200]
        obs.inc("profile.errors")
        obs.event("profile.capture_failed", capture=name, dir=out,
                  error=metrics_note)
        yield None
        return
    span = obs.span(f"profile.{name}", dir=out)
    span.__enter__()
    try:
        yield out
    finally:
        # Fence BEFORE the trace closes: dispatched-but-unfinished device
        # work must land inside the capture window.
        try:
            jax.effects_barrier()
        except Exception:
            pass
        span.__exit__(None, None, None)
        try:
            trace_cm.__exit__(None, None, None)
        except Exception:
            pass
        obs.inc("profile.captures")
        obs.event("profile.capture", capture=name, dir=out)
