"""bigdl_tpu.obs: the unified telemetry subsystem.

One coherent, exportable telemetry layer over the training and serving
stacks (docs/observability.md):

- :mod:`~bigdl_tpu.obs.metrics` — thread-safe registry of labeled
  Counter/Gauge/Histogram families, Prometheus text exposition + JSON
  snapshots, a process-global default registry.
- :mod:`~bigdl_tpu.obs.spans` — host-side span tracer (nested,
  thread-aware, bounded ring buffer) exporting Chrome trace-event JSON
  loadable in Perfetto; a leaf span also enters the profiler's trace
  through an annotator that ``utils.profiling`` installs. Never inside
  jit-traced code (the ``span-in-jit`` lint rule enforces it).
- :mod:`~bigdl_tpu.obs.reqtrace` — request-scoped timelines (bounded
  lifecycle-event rings per trace ID, Perfetto export with one track
  per request) and the flight recorder (last-N scheduler iterations,
  dumped on anomaly / restart / SIGUSR2). Gated by
  ``BIGDL_TPU_REQ_TRACE``.
- :mod:`~bigdl_tpu.obs.exporters` — background ``/metrics`` +
  ``/trace`` + ``/requests`` + ``/healthz`` HTTP endpoint, JSONL sink,
  FileWriter bridge.
- :mod:`~bigdl_tpu.obs.anomaly` — rolling-median step-time anomaly
  detector, the first registry consumer (fires the flight recorder).

The whole package is stdlib-only (it never imports jax), so recording
costs a clock read + a lock; ``BIGDL_TPU_OBS=0`` (or
:func:`set_enabled`) no-ops it entirely.
"""

from bigdl_tpu.obs import reqtrace
from bigdl_tpu.obs.anomaly import StepTimeAnomalyDetector
from bigdl_tpu.obs.exporters import JsonlSink, MetricsServer, SummaryBridge
from bigdl_tpu.obs.metrics import (Counter, Gauge, Histogram,
                                   MetricsRegistry, counter,
                                   default_registry, enabled, gauge,
                                   histogram, set_enabled)
from bigdl_tpu.obs.reqtrace import (FlightRecorder, ReqTraceRecorder,
                                    default_flight, default_recorder,
                                    flight_dump, mint)
from bigdl_tpu.obs.spans import (Span, SpanTracer, default_tracer,
                                 leaf_span, record_span, record_span_at,
                                 span)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "counter",
    "gauge", "histogram", "default_registry", "enabled", "set_enabled",
    "Span", "SpanTracer", "span", "leaf_span", "record_span",
    "record_span_at", "default_tracer",
    "ReqTraceRecorder", "FlightRecorder", "default_recorder",
    "default_flight", "flight_dump", "mint", "reqtrace",
    "MetricsServer", "JsonlSink", "SummaryBridge",
    "StepTimeAnomalyDetector",
]
