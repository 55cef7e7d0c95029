"""repro.obs — the unified telemetry layer (DESIGN.md §10).

Three pillars, one import:

  * **metrics** — the process-global ``REGISTRY`` of labeled counters /
    gauges / histograms every layer writes to, with JSON snapshot and
    Prometheus text exporters and one consistent ``reset``; the legacy
    stats dicts (``ServingEngine.stats``, ``pipeline._STATS``,
    ``ArtifactStore.stats``) are read-through ``MetricsView``s over it.
  * **tracing** — the global ``TRACER`` of nestable spans around every
    compile stage, serve phase, block-pipeline chunk and fit step, each a
    JAX profiler annotation (so a profiler trace holds them beside the
    device's operations) and, when enabled, an in-memory record
    exportable as Chrome/Perfetto trace-event JSON
    (``TRACER.export_chrome_json(path)`` then open at
    https://ui.perfetto.dev).
  * **drift** — ``drift_report(cg)``: the compile-time cost model
    (predicted row-cycles, modeled HBM bytes/block, recorded on every
    artifact as ``cg.perf_model``) vs measured wall per unit, plus FIFO
    high-water vs configured depth as runtime deadlock headroom.

Plus ``get_logger`` — the level-controlled structured logger the launch
paths print through (quiet by default under pytest).

``drift`` imports ``repro.core`` (it replays execution units), while the
core modules import ``repro.obs.metrics`` / ``tracing`` at module top —
so the drift names are loaded lazily here (PEP 562) to keep the import
graph acyclic: metrics/tracing/log depend on nothing in repro.
"""

from repro.obs.log import current_level, get_logger, set_level
from repro.obs.metrics import (REGISTRY, Counter, Gauge, Histogram,
                               MetricsRegistry, MetricsView, counter, gauge,
                               histogram)
from repro.obs.tracing import TRACER, SpanEvent, Tracer, span

_DRIFT_NAMES = ("DriftReport", "FifoHeadroom", "UnitDrift",
                "build_perf_model", "drift_report", "fifo_high_water")


def __getattr__(name):
    if name in _DRIFT_NAMES or name == "drift":
        import importlib
        drift = importlib.import_module("repro.obs.drift")
        if name == "drift":
            return drift
        return getattr(drift, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "REGISTRY", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "MetricsView", "counter", "gauge", "histogram",
    "TRACER", "SpanEvent", "Tracer", "span",
    "current_level", "get_logger", "set_level",
    *_DRIFT_NAMES,
]
