"""Unified observability layer: tracing, metrics, in-graph telemetry.

* ``repro.obs.trace`` — causally-linked request-lifecycle spans with an
  injectable clock (deterministic under the scheduler sim).
* ``repro.obs.metrics`` — the process-wide labeled metrics registry with
  JSONL + Prometheus-textfile exporters.
* ``repro.obs.ingraph`` — true-gradient swamping stats from inside the
  jitted train step (``QDotConfig.stats_tag`` + ``io_callback``).
* ``repro.obs.sink`` / ``repro.obs.clock`` — the shared JSONL appender,
  bounded ring buffer, and clock seam the rest build on.

Everything is opt-in: with no tracer/registry/tag installed, the
instrumented code paths are bit-identical to this package not existing
(pinned in ``tests/test_obs_spans.py`` / ``tests/test_obs_ingraph.py``).
"""

from repro.obs.clock import Clock, SystemClock, VirtualClock
from repro.obs.metrics import (
    MetricsRegistry,
    collect_process_metrics,
    get_registry,
    record_controller_events,
    record_spec_events,
    set_registry,
)
from repro.obs.sink import RingBuffer, jsonl_append
from repro.obs.trace import (
    Span,
    Tracer,
    percentile,
    request_latencies,
    span,
    span_forest,
)

__all__ = [
    "Clock", "SystemClock", "VirtualClock",
    "MetricsRegistry", "get_registry", "set_registry",
    "collect_process_metrics", "record_controller_events",
    "record_spec_events",
    "RingBuffer", "jsonl_append",
    "Span", "Tracer", "span", "span_forest", "request_latencies",
    "percentile",
]
