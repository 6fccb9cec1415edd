"""Percent of the traced window in which no operation ran on the device:
one minus the union of the device operations' intervals over the window,
from the profiler trace (``bench/xplane.py``), averaged over the cell's
chips."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
