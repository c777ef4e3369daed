"""Observability plane: tracing in model and host time + labeled metrics.

Two halves (contract R001 holds here too: the one real-time read is
``repro.core.clock.host_now``):

* :mod:`repro.obs.trace` — :class:`Tracer` spans riding the charge-
  attribution clock, each also stamped in host time; the process-wide
  host record behind :func:`host_spans`; deterministic JSONL +
  Perfetto-loadable Chrome trace exports; the per-task category tallies
  behind ``TaskStats.time_budget()``.
* :mod:`repro.obs.metrics` — :class:`MetricsRegistry` counters / gauges
  / histograms with fixed bucket bounds, absorbing the scattered
  per-plane counters via snapshot-time collectors, scraped as sorted
  Prometheus-flavoured text.
"""

# the planes of ``repro.core`` import this package's tracer: load them
# first, so that importing ``repro.obs`` on its own finds no half-made
# module
from .. import core  # noqa: F401
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      DEFAULT_BUCKETS)
from .trace import (CATEGORIES, NULL_TRACER, HostSpan, HostSpans, Span,
                    Tracer, host_spans, spanned, thread_span)

__all__ = [
    "CATEGORIES", "Counter", "DEFAULT_BUCKETS", "Gauge", "Histogram",
    "HostSpan", "HostSpans", "MetricsRegistry", "NULL_TRACER", "Span",
    "Tracer", "host_spans", "spanned", "thread_span",
]
