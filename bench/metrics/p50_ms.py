"""p50_ms: the median latency of all answered requests of the
window, from each request's due time to its answer (open loops)."""
from bench.loadgen import percentiles_ms


def read(run):
    return percentiles_ms(run.latencies_s)["p50_ms"]
