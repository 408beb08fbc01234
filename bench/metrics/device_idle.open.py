"""device_idle.open (%): the share of the traced slice in which no
operation ran on the device (open loops); nothing where the trace lost
operation events."""


def read(run):
    t = run.trace
    if not t or not t["complete"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
