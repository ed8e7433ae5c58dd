"""Unified observability: flight recorder, span tracing, metrics.

    from repro import obs

    tracer = obs.Tracer()
    rec = obs.FlightRecorder(capacity=4096, fence=False)
    reg = obs.MetricsRegistry()
    with obs.use_tracer(tracer), obs.use_recorder(rec), \
            obs.use_metrics(reg):
        engine.serve(...)
    tracer.export_chrome_trace("serve.trace.json", recorder=rec)
    reg.write_snapshot("serve.metrics.json")

Three planes, one discipline (scoped like ``gemm.use_backend``, strict
zero-cost no-ops when inactive):

* **Flight recorder** (``obs.recorder``) — fixed-size ring buffer of
  per-dispatch GEMM records hooked into ``gemm.execute``: plan key,
  (m, n, k), backend, lever, epilogue, plan-cache hit/miss, wall time
  and achieved GFLOPS with fraction-of-roofline.  Jitted dispatches
  register trace-time *manifests* instead of fabricated timings.
* **Span tracing** (``obs.spans``) — nestable ``span()`` scopes through
  plan resolve, pack, autotune, the scheduler's steps and phases,
  prefix-cache and fault events; exported as Chrome-trace JSON for
  ``ui.perfetto.dev``, or forwarded to the JAX profiler's trace on the
  device's clock (``Tracer(sink="profiler")``).
* **Metrics** (``obs.metrics``) — counters/gauges/fixed-bucket
  histograms unifying ``ServeStats`` / ``PrefixCacheStats`` /
  ``StoreInfo`` / ``plan_cache_info`` behind Prometheus-text and JSON
  snapshot exporters.

See docs/observability.md for the record schema, span taxonomy, metric
naming, and the async-dispatch fencing caveats.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               active_metrics, gemm_collector,
                               publish_prefix_stats, publish_serve_stats,
                               set_metrics, use_metrics)
from repro.obs.recorder import (FlightRecorder, active_recorder,
                                manifest_scope, manifests, no_recorder,
                                reset_manifests, set_recorder,
                                stacked_in_place_share, use_recorder)
from repro.obs.report import (format_table, gemm_events, per_shape_table,
                              synthesize_gemm_events)
from repro.obs.spans import (Tracer, active_tracer, current_span, instant,
                             no_tracer, set_tracer, span, use_tracer,
                             validate_chrome_trace)
from repro.obs.timing import FencedTimer, measure

__all__ = [
    "Counter", "FencedTimer", "FlightRecorder", "Gauge", "Histogram",
    "MetricsRegistry", "Tracer",
    "active_metrics", "active_recorder", "active_tracer", "current_span",
    "format_table", "gemm_collector", "gemm_events", "instant",
    "manifest_scope", "manifests", "measure", "no_recorder", "no_tracer",
    "per_shape_table", "publish_prefix_stats", "publish_serve_stats",
    "reset_manifests", "set_metrics", "set_recorder", "set_tracer",
    "span", "stacked_in_place_share", "synthesize_gemm_events",
    "use_metrics", "use_recorder",
    "use_tracer", "validate_chrome_trace",
]
