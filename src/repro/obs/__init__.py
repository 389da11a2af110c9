"""Gopher Scope: unified tracing, metrics and skew analytics.

Three host-side layers with one rule — zero cost when disabled, and never
a sync inside compiled loops:

  trace.py    nested-span tracer (run → phase → superstep → stage) with
              Chrome-trace/Perfetto + JSONL export; the engine's traced
              stepped driver emits into it. ``step`` puts the engine's
              host steps on the profiler's clock (``gopher.*`` spans);
              ``op_stages`` maps the compiled loops' device ops to their
              ``gopher.*`` named scopes
  metrics.py  labeled counters/gauges/histograms; engine, tier planner,
              block patcher and serving loop all feed the process default
              registry; snapshottable as a plain dict
  skew.py     partition imbalance / straggler scores off live telemetry —
              the input ROADMAP's Gopher Balance consumes
"""
from repro.obs.metrics import (MetricsRegistry, default_registry,
                               set_default_registry, validate_metrics)
from repro.obs.skew import (SkewTracker, imbalance_score, pair_skew,
                            skew_report)
from repro.obs.trace import (NOOP, SPAN_SECONDS, Span, Tracer, get_tracer,
                             op_stages, set_tracer, step,
                             validate_chrome_trace)

__all__ = [
    "Tracer", "Span", "NOOP", "get_tracer", "set_tracer",
    "validate_chrome_trace", "step", "op_stages", "SPAN_SECONDS",
    "MetricsRegistry", "default_registry", "set_default_registry",
    "validate_metrics",
    "imbalance_score", "pair_skew", "skew_report", "SkewTracker",
]
