"""Unified observability plane (DESIGN.md §9).

Three dependency-free pieces every runner reports into:

* ``obs.metrics`` — process-local registry of counters / gauges /
  fixed-bucket histograms with a flat ``snapshot()`` export and a
  coordinator-gated multihost merge path;
* ``obs.trace`` — round-lifecycle span tracer emitting Chrome-trace
  JSON, each span doubling as a ``jax.profiler.TraceAnnotation`` so
  device profiles line up with host spans, and the engines' bounded
  default recorder (``default_tracer``);
* ``obs.sink`` — JSONL event sink (coordinator-gated) plus the repo's
  stdlib-logging configurator.
"""
from repro.obs.metrics import (  # noqa: F401
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
    export_snapshot,
    merge_snapshots,
)
from repro.obs.sink import (  # noqa: F401
    InMemorySink,
    JsonlSink,
    configure_logging,
    emit_snapshot,
)
from repro.obs.trace import (  # noqa: F401
    NULL_TRACER,
    SPAN_APPLY,
    SPAN_CHECKPOINT,
    SPAN_COLLECT,
    SPAN_CONTRIBUTE,
    SPAN_GATHER,
    SPAN_HOST_SYNC,
    SPAN_NAMES,
    SPAN_PREPARE,
    SPAN_STAGE,
    Tracer,
    WindowedProfiler,
    default_tracer,
    span_coverage,
    validate_trace,
)
