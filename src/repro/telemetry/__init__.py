"""``repro.telemetry`` — unified observability for the whole stack.

One subsystem replaces the previous per-module counter dicts and the
serve-only latency tracker (see docs/observability.md):

* **metrics registry** (:mod:`repro.telemetry.registry`) — named
  counters / gauges / bucketed histograms with label sets, thread-safe,
  process-global (:data:`REGISTRY`), with nested-dict
  :func:`snapshot`, Prometheus text exposition
  (:func:`repro.telemetry.export.prometheus_text`, stdlib only) and
  per-test :func:`reset`;
* **span tracer** (:mod:`repro.telemetry.spans`) — ``with
  span("plan.build", scheme=...):`` nested timed spans with ids and
  parents in a bounded ring, exported as Perfetto-loadable
  Chrome-trace JSON (:func:`repro.telemetry.export.chrome_trace`),
  optionally mirrored into ``jax.profiler.TraceAnnotation``;
* **attribution** (:mod:`repro.telemetry.attribution`) — profiled
  device time joined with the analytic HBM-byte and MAC models into
  achieved-GB/s / achieved-MACs/s gauges (a live roofline);
* **op scopes** (:mod:`repro.telemetry.scopes`) — under ``spans``, each
  executor compile maps its HLO instructions to the program's
  ``jax.named_scope`` layers (``dwt.to_planes``, ``dwt.pad``, ...), so
  a device trace's ops can be put on those layers (:func:`op_scopes`).

Everything is gated on ``$REPRO_TELEMETRY`` (``off`` | ``counters``
[default] | ``spans``): under ``off`` every instrument site is a
branch-and-return no-op, so the hot path pays nothing
(:mod:`repro.telemetry.config`).

    from repro import telemetry as T

    T.set_mode("spans")
    pyr = dwt2(x, levels=3, fuse="pyramid")
    T.write_chrome_trace("trace.json")       # -> ui.perfetto.dev
    print(T.prometheus_text())               # -> any Prometheus scraper
"""
from repro.telemetry.attribution import (plan_cost_inputs, plan_macs,
                                         record_execution, roofline)
from repro.telemetry.config import (CONFIG, DEFAULT_MODE, JAX_ANNOTATIONS_ENV,
                                    MODE_ENV, MODES, mode, reload, set_mode)
from repro.telemetry.export import (chrome_trace, parse_prometheus_text,
                                    prometheus_text, write_chrome_trace)
from repro.telemetry.registry import (DEFAULT_BUCKETS, MAX_SERIES, REGISTRY,
                                      Counter, CounterAlias, Gauge,
                                      Histogram, MetricsRegistry)
from repro.telemetry.scopes import op_scopes, record_op_scopes
from repro.telemetry import scopes as _scopes
from repro.telemetry.spans import (NOOP_SPAN, TRACER, SpanRecord, SpanTracer,
                                   current_span, span, span_summary)

__all__ = [
    # config
    "mode", "set_mode", "reload", "MODES", "MODE_ENV", "DEFAULT_MODE",
    "JAX_ANNOTATIONS_ENV", "CONFIG",
    # registry
    "REGISTRY", "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "CounterAlias", "MAX_SERIES", "DEFAULT_BUCKETS",
    "counter", "gauge", "histogram", "snapshot", "reset",
    # spans
    "span", "current_span", "span_summary", "SpanTracer", "SpanRecord",
    "TRACER", "NOOP_SPAN",
    # export
    "prometheus_text", "parse_prometheus_text", "chrome_trace",
    "write_chrome_trace",
    # attribution
    "record_execution", "plan_cost_inputs", "plan_macs", "roofline",
    # op scopes
    "op_scopes", "record_op_scopes",
]


def counter(name: str, help: str = "", labelnames=None) -> Counter:
    """Get-or-create a counter on the global registry."""
    return REGISTRY.counter(name, help=help, labelnames=labelnames)


def gauge(name: str, help: str = "", labelnames=None) -> Gauge:
    """Get-or-create a gauge on the global registry."""
    return REGISTRY.gauge(name, help=help, labelnames=labelnames)


def histogram(name: str, help: str = "", labelnames=None,
              buckets=DEFAULT_BUCKETS) -> Histogram:
    """Get-or-create a histogram on the global registry."""
    return REGISTRY.histogram(name, help=help, labelnames=labelnames,
                              buckets=buckets)


def snapshot() -> dict:
    """Nested-dict snapshot of every metric on the global registry."""
    return REGISTRY.snapshot()


def reset() -> None:
    """Zero every metric series, clear the span ring and the op-scope
    map (per-test isolation; metric definitions survive)."""
    REGISTRY.reset()
    TRACER.clear()
    _scopes.clear()


def stats() -> dict:
    """The ``engine.stats()["telemetry"]`` section: active mode, metric
    and series counts, span-ring accounting."""
    n_series = sum(len(m._series) for m in REGISTRY)
    return {"mode": mode(), "metrics": len(REGISTRY),
            "series": n_series,
            "dropped_series": REGISTRY.dropped_series,
            "spans": TRACER.stats()}
