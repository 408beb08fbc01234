"""p95_ms: the 95th percentile latency of all answered requests of the
window, from each request's due time to its answer (open loops)."""
from bench.loadgen import percentiles_ms


def read(run):
    return percentiles_ms(run.latencies_s)["p95_ms"]
