"""Unified observability layer: metrics registry, span tracing, exporters
and the crash flight recorder.

One telemetry pipeline for everything the repo measures:

- :mod:`~deeplearning4j_tpu.obs.registry` — process-wide
  ``MetricsRegistry`` (counters / gauges / fixed-bucket histograms with
  p50/p95/p99, all with units + help text) absorbing the pre-existing
  ad-hoc stats (``CompileWatch``, ``TrainingStats``,
  ``ParallelInference.stats()``, ``CheckpointManager`` counters);
- :mod:`~deeplearning4j_tpu.obs.trace` — explicit-clock host-side span
  tracer on the profiler's clock (every span is also a
  ``jax.profiler.TraceAnnotation``; disabled ⇒ the annotation alone)
  instrumenting one span tree in every fit loop, serving dispatch,
  checkpoint commits and elastic generation boundaries, plus the synced
  bench ``Stopwatch``;
- :mod:`~deeplearning4j_tpu.obs.exporters` — Prometheus text format
  (served at ``/metrics`` by the existing ``UIServer``) and a JSONL event
  log through any ``StorageBackend``;
- :mod:`~deeplearning4j_tpu.obs.flight` — bounded in-memory ring of
  recent spans/events flushed to storage on crash, watchdog timeout or
  ``ELASTIC_RESTART_EXIT``, attached to ``CrashRecord`` post-mortems.

Turn it all on in three lines::

    from deeplearning4j_tpu import obs
    obs.configure_tracer(enabled=True, registry=obs.get_registry())
    obs.install_flight_recorder(store=backend, worker_id="w0")
"""

from deeplearning4j_tpu.obs.registry import (  # noqa: F401
    Counter, Gauge, Histogram, MetricError, MetricsRegistry,
    absorb_checkpoint_manager, absorb_compile_watch, absorb_index_endpoint,
    absorb_inference_stats, absorb_model_server, absorb_training_stats,
    get_registry,
    publish_stats_update, watch_grad_compression, watch_moe,
    watch_training_stats)
from deeplearning4j_tpu.obs.trace import (  # noqa: F401
    Stopwatch, Tracer, configure_tracer, get_tracer)
from deeplearning4j_tpu.obs.flight import (  # noqa: F401
    FlightRecorder, flush_flight_recorder, get_flight_recorder,
    install_flight_recorder, uninstall_flight_recorder)
from deeplearning4j_tpu.obs.exporters import (  # noqa: F401
    EventLog, prometheus_text, read_event_log)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricError", "MetricsRegistry",
    "get_registry", "absorb_compile_watch", "absorb_training_stats",
    "watch_training_stats", "watch_grad_compression", "watch_moe",
    "absorb_inference_stats", "absorb_checkpoint_manager",
    "absorb_index_endpoint",
    "publish_stats_update",
    "Tracer", "get_tracer", "configure_tracer", "Stopwatch",
    "FlightRecorder", "install_flight_recorder", "get_flight_recorder",
    "uninstall_flight_recorder", "flush_flight_recorder",
    "EventLog", "prometheus_text", "read_event_log",
]
