"""repro.obs — the observability subsystem (DESIGN.md §10).

Four pieces, layered bottom-up:

  * ``obs.metrics``  — counters / gauges / fixed-bucket histograms in one
    registry with JSONL + Prometheus exporters. The kernel layer's
    launch/host-sync counters are one backend of this registry.
  * ``obs.tracing``  — the span API (``obs.span("kernel", path=...)``) with
    device-sync-aware close and a second sink on the JAX profiler's clock
    (``obs.to_profiler(True)``), plus the ``QueryTrace``/``BatchTrace``
    records ``MDRQEngine.query_batch(..., trace=True)`` emits.
  * ``obs.querylog`` — the bounded reservoir-sampled query log
    ``MDRQServer`` keeps (the learned-path training input).
  * ``obs.audit``    — estimated-vs-observed drift report per (path x
    selectivity-decile) cell, and the bridge from traces to
    ``Planner.calibrate``.

Import as ``from repro import obs`` and use ``obs.span`` / ``obs.registry``
/ ``obs.audit`` directly; the submodules stay importable for the full
surface. This package never imports engine/kernel code at module level —
it is the leaf everything else instruments itself with.
"""
from repro.obs.audit import (AuditCell, DriftReport, audit,
                             calibration_samples)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               registry)
from repro.obs.querylog import QueryLog, QueryLogEntry
from repro.obs.tracing import (NULL_SPAN, BatchTrace, QueryTrace, Span,
                               Tracer, enabled, span, to_profiler)

__all__ = [
    "AuditCell", "DriftReport", "audit", "calibration_samples",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "registry",
    "QueryLog", "QueryLogEntry",
    "NULL_SPAN", "BatchTrace", "QueryTrace", "Span", "Tracer", "enabled",
    "span", "to_profiler",
]
