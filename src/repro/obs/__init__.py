"""Unified observability for the reproduction stack (docs/observability.md).

Three layers, one import surface:

- :mod:`repro.obs.trace` — structured per-request span trees (admission →
  lane queue → chunk exec → SS rounds → greedy selection → recovery /
  degradation attempts) recorded host-side around jitted calls into a
  bounded in-process ring buffer.  Off by default (``configure(trace=True)``
  or ``REPRO_TRACE=1``); when off the ``span()`` hooks are near-zero-cost
  no-ops and telemetry-on results are bit-identical to telemetry-off
  (tests/test_obs.py pins this on oracle and pallas).  Lexical spans also
  enter ``jax.profiler.TraceAnnotation``; the served path's host↔device
  traffic goes through :func:`device_put` / :func:`device_get` /
  :func:`block_until_ready`, which count syncs and bytes on the open span.
- :mod:`repro.obs.metrics` — process-wide counters / gauges / histograms
  with Prometheus text-format and JSON exporters (``repro.api.metrics()``;
  :func:`repro.obs.metrics.start_metrics_server` for a pull endpoint).
- :mod:`repro.obs.events` — the unified event bus every subsystem's audit
  records ride (fault draws, recovery/degradation records, session audit
  events, WAL truncations) with one global ordering and shared
  request/session ids, plus the bounded :class:`RingLog` that replaced the
  unbounded in-memory audit lists.
"""

from repro.obs.events import Event, EventBus, RingLog, get_bus
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    start_metrics_server,
)
from repro.obs.trace import (
    Span,
    Tracer,
    block_until_ready,
    configure,
    device_get,
    device_put,
    format_trace,
    get_tracer,
    named_scope,
    span,
    trace_enabled,
    trace_summary,
)

__all__ = [
    "Counter",
    "Event",
    "EventBus",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "RingLog",
    "Span",
    "Tracer",
    "block_until_ready",
    "configure",
    "device_get",
    "device_put",
    "format_trace",
    "get_bus",
    "get_registry",
    "reset",
    "span",
    "start_metrics_server",
    "trace_enabled",
    "trace_summary",
]


def reset() -> None:
    """Clear every global observability sink (tracer ring, metrics registry,
    event bus) — the test/bench isolation hook.  Configuration (trace
    enabled/disabled, capacities) is preserved; only recorded data is
    dropped."""
    get_tracer().clear()
    get_registry().clear()
    get_bus().clear()
