"""batch_fill.open (%): live requests per served batch over `max_batch`,
from the window's `repro_router_batch_size` observations."""
import numpy as np


def read(run):
    sizes = run.reg.samples("repro_router_batch_size")
    if not sizes:
        return None
    return 100.0 * float(np.mean(sizes)) / run.max_batch
