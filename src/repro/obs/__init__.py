"""Flight-recorder observability: tracing, metrics, exporters (§11).

Public surface:
  recorder — Recorder / NULL no-op, install()/current()/recording(),
             REPRO_TRACE env opt-in (from_env)
  metrics  — Metrics registry (Counter / Gauge / Histogram / Series)
  export   — Chrome trace-event (Perfetto) + CSV exporters, trace-schema
             validators, ``python -m repro.obs.export`` CLI
  host     — host-clock spans, always on: ``span(name)`` (also a
             ``jax.profiler.TraceAnnotation`` under a profiler session),
             ``host_summary(t0, t1)``, ``host_records()``
"""
from .host import HostSpans, SpanTotals, host_records, host_summary, span
from .metrics import Counter, Gauge, Histogram, Metrics, Series
from .recorder import (CAT_METRIC, CAT_SCHED, CAT_SEARCH, CAT_SIM, NULL,
                       NullRecorder, Recorder, TraceEvent, current, from_env,
                       install, recording)

__all__ = [
    "CAT_METRIC", "CAT_SCHED", "CAT_SEARCH", "CAT_SIM",
    "Counter", "Gauge", "Histogram", "Metrics", "Series",
    "HostSpans", "SpanTotals", "host_records", "host_summary", "span",
    "NULL", "NullRecorder", "Recorder", "TraceEvent",
    "current", "from_env", "install", "recording",
]
