"""device_idle_pct.<regime>: share of the traced window in which no
operation ran on the device: 1 - (union of op intervals) / window."""


def read(run, name):
    if run.trace is None:
        return None
    return run.trace["idle_pct"]
