"""`repro.obs`: end-to-end observability for the whole query path.

One registry, one trace, one export surface (DESIGN.md §8):

    registry   `Counter`/`Gauge`/`Histogram` with labels, lock-protected,
               snapshot/delta windowing.  `ServeStats`, `RouterStats`,
               `LatencyWindow`, and the plan cache's scope counters all emit
               here, so "three disconnected stats surfaces" is over.
    tracing    `span("router.serve")`-style context managers threaded
               through Router.submit -> Replica -> RetrievalEngine ->
               exec, recorded as TraceMe events in the JAX profiler's
               trace, on one clock with the device's operations;
               `device_profile(logdir)` records one (xplane + perfetto).
    stages     instrumented plan variants (`execute(..., instrument=True)`)
               time every exec stage with `block_until_ready` fences,
               feeding `repro_exec_stage_seconds{topology,stage}`.
    exposition `start_metrics_server(port)` serves Prometheus text format;
               `StatsLogger` prints the periodic one-liner.
    drift      `RecallDriftProbe` replays a pinned query sample against
               brute-force ground truth and gauges achieved recall.

Everything is opt-in and near zero-overhead when off: a span with no
profile recording is one TraceMe check, and un-instrumented plans compile
to the same operations whatever this package records (named scopes are
metadata).
"""
from .registry import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    registry,
)
from .trace import device_profile, span, stage
from .prom import MetricsServer, StatsLogger, render_text, start_metrics_server
from .drift import RecallDriftProbe, recall_at_k

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsServer",
    "RecallDriftProbe",
    "Registry",
    "StatsLogger",
    "device_profile",
    "recall_at_k",
    "registry",
    "render_text",
    "span",
    "stage",
    "start_metrics_server",
]
