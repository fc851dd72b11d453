"""JAX persistent compilation cache, placed from outside, with hit/miss
counters.

Compile time is the dominant fixed cost of every cold start in this stack
(the flagship solve compiles in seconds; the solve itself runs in under
one) — and the batched driver multiplies the stakes: one bucket executable
serves hundreds of solves, so persisting it across processes turns every
warm start into pure execute time.

Where the cache lives is the deployment's decision: when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets no directory; otherwise the cache is the fixed ``<repo>/.jax_cache``
(the path is part of the cache's key, so it must not move between runs).
Every entry point (``poisson_tpu.cli``, ``bench.py``, ``chip_smoke.py``)
calls :func:`enable` before its first trace.

Cache traffic is surfaced through the unified telemetry counters
(``obs.metrics``): JAX publishes ``/jax/compilation_cache/cache_hits`` /
``…/cache_misses`` on its ``jax.monitoring`` bus, and the listener
registered here folds them into ``compile_cache.hits`` /
``compile_cache.misses`` — landing in the same snapshot as
``time.compile_seconds``, so a metrics file alone answers "did this run
pay for its compiles or reuse them?".
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")

_LISTENER_INSTALLED = False

# jax.monitoring event names → our counter names (low cardinality, dotted —
# the obs.metrics convention).
_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "compile_cache.hits",
    "/jax/compilation_cache/cache_misses": "compile_cache.misses",
}


def _listener(event: str, **kwargs) -> None:
    name = _EVENT_COUNTERS.get(event)
    if name is not None:
        from poisson_tpu.obs import metrics

        metrics.inc(name)


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already points the cache there
    and no directory is set here. Unset: the cache is :data:`DEFAULT_DIR`.
    Either way the persistence thresholds drop to zero so even the
    small/fast programs this stack compiles are persisted (the defaults
    skip entries below a minimum size and compile time), and the hit/miss
    counters are installed.
    """
    global _LISTENER_INSTALLED
    import jax
    from jax import monitoring

    if not _LISTENER_INSTALLED:
        monitoring.register_event_listener(_listener)
        _LISTENER_INSTALLED = True
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from poisson_tpu.obs import metrics

    metrics.gauge("compile_cache.dir", path)
    return path
