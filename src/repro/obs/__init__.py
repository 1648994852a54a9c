"""repro.obs — unified tracing + metrics layer (DESIGN.md §11).

Dependency-free observability used by every layer of the stack:

* :mod:`repro.obs.trace` — ``TraceRecorder``: nestable spans, instants
  and counter tracks exported as Chrome trace-event / Perfetto JSON.
  While enabled, each span is also a ``jax.profiler.TraceAnnotation``
  (the profiler's host plane, on the device trace's clock), and compiles
  and garbage collections become spans; ``named_scope`` names device
  ops.  ~Zero overhead when disabled.
* :mod:`repro.obs.metrics` — ``Metrics`` registry: counters, gauges and
  histograms with p50/p90/p99 summaries, JSONL snapshot export.
* :mod:`repro.obs.logger` — ``MetricsLogger`` sinks (stdout / JSONL)
  replacing the trainer's raw ``print``.

Instrumented layers: ``core/engine.py`` (per-op wave spans),
``train/trainer.py`` (data-wait/step/checkpoint spans),
``serve/engine.py`` (per-request queued→admitted→prefill→decode→evicted
lifecycle, TTFT/TPOT/queue-wait histograms, one ``engine_step`` span per
step holding its phases and counts), ``dist/`` (named scopes on ring
steps, pipeline ticks, bucketed sync chains; KVStore byte counters).
CLI wiring: ``--trace PATH`` / ``--metrics PATH`` on ``launch.train`` and
``launch.serve``; the chip benchmark (``bench/run_cell.py --trace 1``)
reads the spans against a device trace.
"""
from .logger import JsonlSink, MetricsLogger, StdoutSink
from .metrics import (Counter, Gauge, Histogram, Metrics, get_metrics,
                      reset_metrics)
from .trace import (TraceRecorder, enable, export, get_recorder, instant,
                    named_scope, set_recorder, span, tracing)

__all__ = [
    "TraceRecorder", "get_recorder", "set_recorder", "enable", "tracing",
    "span", "instant", "export", "named_scope",
    "Metrics", "Counter", "Gauge", "Histogram", "get_metrics",
    "reset_metrics",
    "MetricsLogger", "StdoutSink", "JsonlSink",
]
