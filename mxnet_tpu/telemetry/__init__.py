"""mxnet_tpu.telemetry — unified metrics registry + cross-layer tracing.

The process-wide observability layer (ISSUE r7; the operability counterpart
to the serving layer): every hot subsystem — eager jit cache, serving
endpoint/server, ParallelTrainStep, kvstore, DataLoader, and the resilience
layer (retry/checkpoint/watchdog/circuit-breaker, ISSUE r8:
``mxtpu_retries_total``, ``mxtpu_checkpoint_*``, ``mxtpu_circuit_state``,
``checkpoint.save``/``checkpoint.restore`` spans) — reports into ONE
thread-safe registry, exported two ways:

    from mxnet_tpu import telemetry

    telemetry.snapshot()          # whole registry as one JSON-able dict
    telemetry.prometheus_text()   # Prometheus text exposition (scrapable)
    telemetry.periodic_logger(10) # background heartbeat + snapshot file

    with telemetry.span("app.request", user="u1") as s:
        ...                       # nested spans share s.trace_id

Metric families (full catalog: OBSERVABILITY.md) are created by subsystems
at import time via get-or-create, bump pre-bound label children on the hot
path, and are linted at registration (``^mxtpu_[a-z0-9_]+$``, unique) so a
rename can never silently break a dashboard. Spans nest, carry a trace id
across threads (a serving request's id survives queue → batch assembly →
compiled device step), and feed BOTH the profiler's chrome trace (when a
session runs) and the registry's duration histograms (always).

Relationship to ``profiler``: the profiler answers "where did this
microsecond go" (per-op events, XPlane device traces) for a bounded capture
window; telemetry answers "is the fleet healthy" (counters/gauges/quantiles,
negligible overhead, always on). Spans bridge the two — the same trace id
appears in chrome-trace ``args`` and in metric label space.
"""
from __future__ import annotations

from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, REGISTRY,
                      DEFAULT_BUCKETS, METRIC_NAME_RE)
from .flight import FlightRecorder, event
from .tracing import (Span, span, current_span, current_trace_id,
                      new_trace_id, spool_flush, read_spool, journey)
from .reporter import (PeriodicReporter, periodic_logger, dump,
                       sample_device_memory, summary_line)
from .debug_server import DebugServer
from .slo import SLOMonitor
from . import flight, debug_server, slo
from . import compile_ledger, memstats, perf_sentinel
from . import fleet, goodput

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "DEFAULT_BUCKETS", "METRIC_NAME_RE",
    "Span", "span", "current_span", "current_trace_id", "new_trace_id",
    "spool_flush", "read_spool", "journey",
    "PeriodicReporter", "periodic_logger", "dump", "sample_device_memory",
    "summary_line",
    "FlightRecorder", "event", "flight",
    "DebugServer", "debug_server",
    "SLOMonitor", "slo",
    "compile_ledger", "memstats", "perf_sentinel", "fleet",
    "goodput",
    "counter", "gauge", "histogram", "snapshot", "snapshot_json",
    "prometheus_text", "lint_names",
]


# -- registry conveniences (the surface subsystems and users actually call) --

def counter(name, help="", labelnames=()) -> Counter:
    """Get-or-create a Counter in the default registry."""
    return REGISTRY.counter(name, help, labelnames)


def gauge(name, help="", labelnames=()) -> Gauge:
    """Get-or-create a Gauge in the default registry."""
    return REGISTRY.gauge(name, help, labelnames)


def histogram(name, help="", labelnames=(), buckets=None) -> Histogram:
    """Get-or-create a Histogram (fixed log-spaced default buckets)."""
    return REGISTRY.histogram(name, help, labelnames, buckets)


def _refresh_memory_gauges():
    """On-demand gauge refresh for the operator's single-pane exports:
    device memory_stats plus the memstats holder/attribution gauges (the
    scrape IS the sampling tick — no background thread required)."""
    sample_device_memory()
    try:
        memstats.reconcile()
    except Exception:
        pass


def snapshot() -> dict:
    """Whole-registry snapshot as one JSON-able dict (refreshes device
    memory + attribution gauges first — the snapshot is the operator's
    single pane)."""
    _refresh_memory_gauges()
    return REGISTRY.snapshot()


def snapshot_json(**dumps_kw) -> str:
    import json as _json
    return _json.dumps(snapshot(), **dumps_kw)


def prometheus_text() -> str:
    """Prometheus text exposition of the default registry."""
    _refresh_memory_gauges()
    return REGISTRY.prometheus_text()


def lint_names() -> list:
    """Metric-name lint violations in the default registry (empty = clean)."""
    return REGISTRY.lint_names()
