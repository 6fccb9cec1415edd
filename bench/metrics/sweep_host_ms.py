"""Host milliseconds per sweep outside the wait on the device: the self
time of the ``kcore.sweep`` spans (dispatch, frontier update, the sweep
hook) over their number, across the window's jobs. The device idles
through most of it: it is the round trip between sweeps."""
from bench.stages import ms_per_span


def read(run):
    return ms_per_span(run, "kcore.sweep", "self_s")
