"""Milliseconds per sweep the host blocks on the device for the sweep's
changed counts: the ``kcore.sweep.wait`` spans over their number, across
the window's jobs."""
from bench.stages import ms_per_span


def read(run):
    return ms_per_span(run, "kcore.sweep.wait")
