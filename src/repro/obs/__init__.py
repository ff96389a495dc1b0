"""repro.obs — the observability plane (metrics registry + tracing).

Two stdlib-only modules with no imports from the rest of ``repro`` (and
never ``jax``), so every layer (core, db, serve, stream, kernels) can
instrument without cycles:

* :mod:`repro.obs.metrics` — process-wide :data:`REGISTRY` of
  Counter/Gauge/Histogram families with weakly-held labeled children;
  rendered by the gateway's ``GET /metrics`` (Prometheus text format).
* :mod:`repro.obs.trace` — contextvar-propagated request :func:`span`\\ s
  collected by a bounded :class:`Tracer` ring per gateway, with a
  slow-query log; O(ns) no-ops when no trace is active.  A
  :func:`stage` is a coarse span that is always timed into the
  ``repro_stage_seconds`` histogram and annotated on the profiler's
  host plane.

See docs/api.md "Observability" for the metric catalog and tracing
semantics.
"""
from .metrics import (Counter, Gauge, Histogram, MetricFamily, Registry,
                      REGISTRY, obj_label)
from .trace import Tracer, current_ctx, record, span, stage, traced_iter

__all__ = ["Counter", "Gauge", "Histogram", "MetricFamily", "Registry",
           "REGISTRY", "obj_label", "Tracer", "current_ctx", "record",
           "span", "stage", "traced_iter"]
