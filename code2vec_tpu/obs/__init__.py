"""Unified observability: metrics registry + span tracer + exporters.

One import surface for every instrumented layer:

    from code2vec_tpu import obs

    _H_SAVE = obs.histogram("checkpoint_save_seconds", "save wall time")
    with obs.span("checkpoint_save", hist=_H_SAVE):
        ...
    obs.counter("checkpoint_saves_total").inc()

- Metrics (`obs.metrics`): process-wide registry of counters/gauges/
  fixed-bucket histograms; Prometheus text + TB scalar export.
- Tracing (`obs.tracer`): `span(name)` wall-time spans into a ring
  buffer; Chrome trace-event JSON export (Perfetto-loadable). Where
  jax is imported the same span is also the annotation `c2v.<name>`
  in a running `jax.profiler` trace, on the device trace's clock, and
  jax's compile events feed `jax_compile_seconds` (and, for a span
  that asks, `jax_compiles_during`).
- Stalls and collections (`obs.stalls`): every collection of Python's
  collector into `python_gc_pause_seconds`, and a thread that measures
  how late it wakes into `host_stall_seconds` (the machine or the
  program stood still), running while a trainer or a server does.
- Exporters (`obs.exporters`): atomic Prometheus snapshot file
  (`--metrics_file`), localhost HTTP `/metrics` (`--metrics_port`),
  atomic JSON heartbeat (`--heartbeat_file`), and a dump of every
  registered metric into TensorBoard at log boundaries.

Everything is stdlib-only and safe to import from any layer (jax is
never imported from here, only used where it already is; no circular
deps): the data-reader worker threads, the checkpoint commit
path, and the serving bridge all record into the same registry.
"""

from __future__ import annotations

from code2vec_tpu.obs import (
    exporters, flight, metrics, reqtrace, stalls, tracer,
)
from code2vec_tpu.obs.flight import FlightRecorder, default_flight_recorder
from code2vec_tpu.obs.metrics import (
    DEFAULT_BUCKETS, Counter, Gauge, Histogram, MetricsRegistry,
    default_registry,
)
from code2vec_tpu.obs.reqtrace import RequestTrace
from code2vec_tpu.obs.stalls import default_host_watch
from code2vec_tpu.obs.tracer import (
    SpanTracer, compiles_during, default_tracer, log_compiles_from_now, span,
    startup_phase,
)

__all__ = [
    "Counter", "FlightRecorder", "Gauge", "Histogram", "MetricsRegistry",
    "RequestTrace", "SpanTracer",
    "DEFAULT_BUCKETS", "counter", "gauge", "histogram", "span",
    "startup_phase", "log_compiles_from_now", "compiles_during",
    "default_registry", "default_flight_recorder", "default_tracer",
    "default_host_watch",
    "exporters", "flight", "metrics", "reqtrace", "stalls", "tracer",
]


def counter(name: str, help: str = "", **labels) -> Counter:
    return default_registry().counter(name, help, **labels)


def gauge(name: str, help: str = "", **labels) -> Gauge:
    return default_registry().gauge(name, help, **labels)


def histogram(name: str, help: str = "", buckets=None, **labels) -> Histogram:
    return default_registry().histogram(name, help, buckets=buckets, **labels)
