"""Unified telemetry: span tracer, metrics registry, sinks, leveled log.

One layer (DESIGN.md §13) replaces the scattered channels the repo grew
— trainer prints, the tiered store's bare stats dict, the serving
engine's private latency list, hand-invoked Table-5 reports:

  trace:   ``span("train/step/data")`` host spans →
           Chrome-trace/Perfetto JSON (``--trace OUT.json``); each
           enabled span is also a ``jax.profiler.TraceAnnotation``
           (``StepTraceAnnotation`` with ``step=``), so a jax profile
           captured at the same time holds it on the device trace's
           clock.
  metrics: process-wide ``MetricsRegistry`` — counters, gauges, bounded
           p50/p95/p99 reservoirs, labeled series, snapshot/diff.
  sinks:   JSONL step log + schema-validated end-of-run ``summary.json``
           (``--metrics-out DIR``), consumed by launch/report.py and
           benchmarks/check_regression.py --validate-schema.
  log:     leveled stderr progress lines (``REPRO_LOG_LEVEL``), keeping
           stdout machine-parseable.
  sites:   op sites are ``jax.named_scope``s, which the compiler keeps in
           each instruction's ``op_name``: the ACT context's scope path
           (``kgat/layer0/spmm``) plus ``act_quant``, ``act_dequant``,
           ``attention``, ``spmm``, ``bpr`` and ``optimizer``, so device
           time can be put down to a site. A named scope never changes
           an ACT scope path, so no stochastic-rounding key moves.
           ``repro.obs.sites`` keeps the compiled train step and maps its
           instructions to their sites.
  compile: ``compile/cache_hits`` / ``compile/cache_misses`` counters,
           fed from ``jax.monitoring`` (``repro.compile_cache``).

This package imports neither jax nor numpy at module scope — it must be
importable (and near-free) everywhere, including kernels and launchers
that manage backend initialization order carefully.
"""

from .log import log, log_level, set_log_level
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    diff,
    get_registry,
    reset_registry,
    series_key,
)
from .sinks import (
    SCHEMA_VERSION,
    SUMMARY_SCHEMA,
    StepLogWriter,
    SummarySchemaError,
    build_summary,
    validate_summary,
    write_summary,
)
from .trace import Tracer, get_tracer, span

__all__ = [
    "log", "log_level", "set_log_level",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "diff",
    "get_registry", "reset_registry", "series_key",
    "SCHEMA_VERSION", "SUMMARY_SCHEMA", "StepLogWriter",
    "SummarySchemaError", "build_summary", "validate_summary",
    "write_summary",
    "Tracer", "get_tracer", "span",
]
