"""verify_device_ms.open (ms/batch): device time of the search program's
`verify` stage per whole run in the traced slice (`bench/stage_time.py`:
the operations under the `verify` scope, over the whole
`jit_search_pipeline` runs); nothing where the trace lost operation events
or the program names no stages."""
from bench.stage_time import stage_ms_per_execution


def read(run):
    return stage_ms_per_execution(run, "verify")
