"""Observability: superstep tracing, phase metrics, exporters, the
BSP-vs-hybrid report CLI, the one injectable clock, and :func:`span`.

:func:`span` is the one span primitive on the device trace's clock: a
``jax.profiler.TraceAnnotation``, recorded only while a profiler session
(``jax.profiler.trace``) is open and otherwise a no-op of about a
microsecond, so the executor opens its ``engine.*`` spans unconditionally.

Layout (each submodule is importable on its own; nothing on the engines'
hot path imports them — hooks and wrappers are opt-in):

* :mod:`repro.obs.clock`   — the injectable monotonic / perf clock every
  time-consuming subsystem (ft, checkpoint, serve) routes through.
* :mod:`repro.obs.trace`   — span tracer, the executor ``TraceHook``, the
  phased per-phase profiler, and exchange-bytes accounting.
* :mod:`repro.obs.metrics` — the typed metrics registry unifying the
  engine ``Counters``, straggler / checkpoint / serving statistics.
* :mod:`repro.obs.export`  — Chrome trace-event JSON (Perfetto-loadable)
  and the machine-readable profile blob.
* :mod:`repro.obs.report`  — ``python -m repro.obs.report``: the paper's
  headline exchange-vs-compute comparison, measured.

``from repro.obs import clock`` and ``span`` are the only imports light
enough for leaf modules (the clock pulls nothing but stdlib ``time``;
``span`` imports ``jax.profiler`` when first called); everything else is
loaded lazily through ``__getattr__`` so wiring ``obs`` into a module
costs nothing until a tracer or registry is actually constructed.
"""

from __future__ import annotations

import importlib

from repro.obs import clock  # noqa: F401  (stdlib-only; safe everywhere)

_SUBMODULES = ("trace", "metrics", "export", "report")

__all__ = ["clock", "span", *_SUBMODULES]


def span(name: str, **args):
    """A host span ``name`` (with ``args`` as its stats) on the profiler's
    clock, as a context manager: it lands in the same trace as the device
    ops, and costs next to nothing outside a profiler session."""
    from jax import profiler

    return profiler.TraceAnnotation(name, **args)


def __getattr__(name: str):
    if name in _SUBMODULES:
        return importlib.import_module(f"repro.obs.{name}")
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
