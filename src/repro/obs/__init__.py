"""Query-path observability (DESIGN.md §10).

Three surfaces over the same query path, all off by default:

  * :mod:`repro.obs.trace` — the ``QueryTrace`` pytree of on-device
    cascade counters (survivors after C9, after C10, after the series
    screen, verified rows, answers) that the engines' ``*_traced`` twins
    return alongside unchanged answers;
  * :mod:`repro.obs.spans` — the one span primitive, written into the
    ``jax.profiler`` trace (``repro.serve.*``, ``repro.engine.*``), and a
    bounded in-memory ring of span records with JSONL and
    Chrome-trace-event export;
  * :mod:`repro.obs.metrics` — the Prometheus-text metrics registry the
    serving layer exposes (``launch/serve.py --metrics``) and
  * :mod:`repro.obs.calibration` — per-dispatch predicted-vs-measured
    latency residuals with roofline-relative efficiency
    (``runtime/roofline.py``).

Nothing here imports the engines or the serving layer, so the package is
import-cycle-free: ``core``/``serve`` import ``obs``, never the reverse.
"""
from .calibration import CalibrationLog, DispatchRecord
from .metrics import MetricsRegistry, build_registry, start_metrics_server
from .spans import SpanRecorder, span
from .trace import (QueryTrace, excluded_c9, excluded_c10, merge_traces,
                    select_queries, tier_bytes, trace_totals)

__all__ = [
    "CalibrationLog", "DispatchRecord", "MetricsRegistry", "QueryTrace",
    "SpanRecorder", "build_registry", "excluded_c9", "excluded_c10",
    "merge_traces", "select_queries", "span", "start_metrics_server",
    "tier_bytes", "trace_totals",
]
