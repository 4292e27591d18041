"""device_idle_share.serve: percent of the traced window in which no
operation ran on the device, mean over the cell's chips (served
traffic)."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
