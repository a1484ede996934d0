"""Serving observability layer (DESIGN.md §14).

- obs.trace: request-lifecycle + tick-phase Tracer, Chrome trace-event
  (Perfetto) export, and the schema checker CI gates traces on
- obs.metrics: labeled counter/gauge/histogram registry with snapshot/diff,
  Prometheus text exposition, and a JSONL emitter
- obs.profile: ``jax.named_scope`` annotations for the jitted serve steps,
  optional ``jax.profiler`` device-trace wiring, and the process's
  backend-compile counter
- obs.logs: the ``kv()`` structured-log formatter (``rid=/tenant=/tick=``)

Everything here is host-side bookkeeping that must never change tokens:
tests/test_obs.py pins greedy bit-exactness with tracing on vs off (plain
and speculative). What tracing costs when on is measured on the chip by the
benchmark's traced runs (PERF.md).
"""

from .logs import kv
from .metrics import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricFamily,
    MetricsRegistry,
    family_percentile,
)
from .profile import compile_count, device_trace, named_scope, watch_compiles
from .trace import (
    NULL_TRACER,
    PID_REQUESTS,
    PID_SCHED,
    TID_TICK,
    NullTracer,
    Tracer,
    trace_summary,
    validate_chrome_trace,
)

__all__ = [
    "Counter",
    "DEFAULT_BUCKETS",
    "Gauge",
    "Histogram",
    "MetricFamily",
    "MetricsRegistry",
    "NULL_TRACER",
    "NullTracer",
    "PID_REQUESTS",
    "PID_SCHED",
    "TID_TICK",
    "Tracer",
    "compile_count",
    "device_trace",
    "family_percentile",
    "kv",
    "named_scope",
    "trace_summary",
    "validate_chrome_trace",
    "watch_compiles",
]
