"""Share of the traced window in which no operation ran on the device:
100 · (1 − union of device-op intervals / window)."""
from chipbench import trace as tr


def read(run):
    if run.trace is None:
        return None
    return 100.0 * tr.idle_share(run.trace)
