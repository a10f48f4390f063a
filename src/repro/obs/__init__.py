"""Runtime observability for the serve stack.

Three small layers, dependency-free at import (the runtime twin of the
static serve-graph auditor in ``repro.analysis``):

* :mod:`repro.obs.trace` — a bounded ring-buffer tracer: engine-step
  spans (``admit`` / ``prefill_wave`` / ``tail_wave`` / ``decode_chunk``
  / ``spec_draft`` / ``spec_verify`` / ``swap_out`` / ``swap_in`` /
  ``cow`` / ``harvest`` plus host-side ``schedule`` / ``sync`` gaps) and
  per-request lifecycle events, correlated by request uid + step index.
  An enabled tracer mirrors every span into the profiler's trace as
  ``repro.<name>``.
* :mod:`repro.obs.export` — Chrome/Perfetto ``trace_event`` JSON export
  and the report functions behind ``tools/trace_report.py``.
* :mod:`repro.obs.metrics` — counter/gauge/histogram registry rendered
  as Prometheus text at ``GET /v1/metrics``.

``trace``/``metrics`` import nothing from ``repro.serve`` (the serve
layer imports *them*), so there is no import cycle; ``export`` is pulled
in explicitly by its consumers.
"""
from repro.obs.metrics import ServeMetrics, parse_prometheus
from repro.obs.trace import NULL_TRACER, Span, Tracer

__all__ = ["Tracer", "Span", "NULL_TRACER", "ServeMetrics",
           "parse_prometheus"]
