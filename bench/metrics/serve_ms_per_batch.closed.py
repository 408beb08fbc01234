"""serve_ms_per_batch.closed (ms): the engines' host wall per served batch,
from dispatch to the answer on the host, over the window: deltas of
`repro_serve_{embed,search}_seconds_total` over `repro_serve_batches_total`."""


def read(run):
    batches = run.reg.value("repro_serve_batches_total")
    if batches <= 0:
        return None
    secs = (run.reg.value("repro_serve_embed_seconds_total")
            + run.reg.value("repro_serve_search_seconds_total"))
    return secs * 1e3 / batches
