"""Seconds a job's conquer spends outside its sweeps: the
``kcore.conquer.setup`` spans (tile and state upload before the first
sweep) and the ``kcore.conquer.readout`` spans (coreness read-back and
inverse permutation), averaged over the window's jobs."""
from bench.stages import seconds_per_job


def read(run):
    return seconds_per_job(
        run, ("kcore.conquer.setup", "kcore.conquer.readout"))
