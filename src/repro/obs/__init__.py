"""Runtime observability: metrics registry, job tracing, exporters.

Import surface:

* :mod:`repro.obs.metrics` — re-exported here; safe from any layer
  (it imports nothing from ``repro``, so even ``repro.core.events``
  can depend on it without a cycle).
* :mod:`repro.obs.trace` / :mod:`repro.obs.export` — import these
  submodules explicitly. ``trace`` imports ``repro.core.events``, so
  pulling it in eagerly here would cycle with core modules that use
  the registry.
* :mod:`repro.obs.spans` — ``nbi:`` profiler spans on the device path
  (the serving engines, ``train()``); it imports JAX only when a span is
  made, and is always on (a no-op outside a ``jax.profiler`` trace).

Instrumentation is **off by default**: :func:`get_registry` returns a
no-op :class:`NullRegistry` until :func:`enable` is called (or the
process starts with ``NBI_OBS=1``). See ``docs/observability.md``.
"""

from .metrics import (
    DURATION_BUCKETS,
    LATENCY_BUCKETS,
    NULL_REGISTRY,
    MetricsRegistry,
    NullRegistry,
    disable,
    enable,
    get_registry,
    timed,
)

__all__ = [
    "DURATION_BUCKETS",
    "LATENCY_BUCKETS",
    "NULL_REGISTRY",
    "MetricsRegistry",
    "NullRegistry",
    "disable",
    "enable",
    "get_registry",
    "timed",
]
