"""queue_wait_p95_ms.open (ms): the 95th percentile, over the requests
dispatched in the window, of the wait from `Router.submit` to the dispatch
of the request's batch: the samples of the router's
`repro_router_queue_wait_seconds` histogram; nothing where the program
keeps no such histogram."""
from bench.loadgen import percentiles_ms


def read(run):
    try:
        waits = run.reg.samples("repro_router_queue_wait_seconds")
    except KeyError:
        return None
    return percentiles_ms(waits)["p95_ms"]
