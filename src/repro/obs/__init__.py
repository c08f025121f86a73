"""repro.obs — unified telemetry: span tracing, metrics, flight recorder.

One observability layer consumed by the solver, the compiler, the serve
engine, and the benchmarks:

``repro.obs.trace``
    Low-overhead span tracer with per-thread ring buffers and a Chrome
    trace-event / Perfetto JSON exporter.  Disabled by default; enable
    with ``REPRO_TRACE=1`` or :func:`trace.set_enabled`.  Once a module
    that imports jax registers the profiler sink
    (:func:`trace.set_profiler_sink`), spans also land in any active
    JAX profiler session.

``repro.obs.metrics``
    Process-wide registry of counters / gauges / histograms with
    single-writer per-thread shards merged at snapshot, plus JSON and
    Prometheus-text exposition.

``repro.obs.flight``
    Per-shard flight recorder: a bounded ring of per-request records
    with tail-sampling that pins the slowest-K requests' full per-stage
    breakdowns for postmortem p99 triage.

``repro.obs.solvelog``
    Structured per-solve result records (matrix statistics → adders /
    cost / depth / wall) kept in a bounded in-memory ring and optionally
    appended to a JSONL file — the training log for a future learned
    resource predictor.

Everything here is stdlib + optional numpy only; importing ``repro.obs``
never pulls in jax.
"""

from . import flight, metrics, solvelog, trace
from .flight import FlightRecorder
from .metrics import Histogram, MetricsRegistry, get_registry

__all__ = [
    "FlightRecorder",
    "Histogram",
    "MetricsRegistry",
    "flight",
    "get_registry",
    "metrics",
    "solvelog",
    "trace",
]
