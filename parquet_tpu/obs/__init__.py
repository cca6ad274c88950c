"""Unified telemetry: the observability subsystem every layer reports
through.

- :mod:`parquet_tpu.obs.metrics` — process-wide registry of counters,
  gauges, and fixed-bucket latency histograms (p50/p95/p99); the six
  legacy per-operation stats dataclasses (``ReadStats``, ``WriteStats``,
  ``CacheStats``, ``ReadReport``, planner counters, ``RouteHistory``)
  keep their APIs and publish here too.
- :mod:`parquet_tpu.obs.trace` — the one span API, near-zero overhead
  off, with two sinks: an active ``jax.profiler`` session (``pq.<name>``
  annotations on the device's clock) and Chrome trace-event JSON for
  Perfetto (``PARQUET_TPU_TRACE=/path.json`` enables it per process).
- :mod:`parquet_tpu.obs.export` — Prometheus text-format rendering
  (``python -m parquet_tpu stats --prom``) and the live scrape endpoint
  (``start_metrics_server`` / ``stats --serve PORT``).
- :mod:`parquet_tpu.obs.ledger` — the process-wide resource ledger:
  every byte-holding tier keeps a named account current at its own
  mutation sites (``ledger.*`` gauges), with soft/hard memory-pressure
  watermarks (``PARQUET_TPU_MEM_SOFT``/``HARD``) that shrink the LRU
  tiers and gate admissions, and the ``/debugz`` live-residency
  endpoint on the metrics server.
- :mod:`parquet_tpu.obs.scope` — request-scoped telemetry:
  ``op_scope(name)`` gives every operation its own identity (per-op
  ``OpReport`` attribution across shared-pool workers, per-request
  Perfetto tracks), with 1-in-N head sampling
  (``PARQUET_TPU_TRACE_SAMPLE``) and slow-op tail capture
  (``PARQUET_TPU_SLOW_OP_S`` / ``PARQUET_TPU_SLOW_LOG``).
"""

from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, REGISTRY,
                      counter, gauge, histogram, metrics_delta,
                      metrics_snapshot, pool_wait_seconds, reset_metrics)
# NOTE: the live gate is ``trace.on()`` on the MODULE — instrumentation
# sites import the module and call it each time (a re-exported copy of
# ``TRACE_ENABLED`` would go stale on enable/disable)
from . import trace
from .trace import (NULL_SPAN, disable_tracing, enable_tracing, enabled,
                    flush_trace, reset_trace, span, trace_events,
                    trace_span)
from .export import (MetricsServer, debugz_snapshot, render_prometheus,
                     start_metrics_server)
from . import ledger
from .ledger import (LEDGER, ResourceLedger, ledger_account,
                     ledger_snapshot)
from . import scope
from .scope import OpScope, current_op, live_ops, maybe_op_scope, op_scope

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
           "counter", "gauge", "histogram", "metrics_delta",
           "metrics_snapshot", "pool_wait_seconds", "reset_metrics",
           "NULL_SPAN", "trace", "disable_tracing", "enable_tracing",
           "enabled", "flush_trace", "reset_trace", "span", "trace_events",
           "trace_span", "render_prometheus", "MetricsServer",
           "start_metrics_server", "debugz_snapshot", "ledger", "LEDGER",
           "ResourceLedger", "ledger_account", "ledger_snapshot", "scope",
           "OpScope", "current_op", "live_ops", "maybe_op_scope",
           "op_scope"]
