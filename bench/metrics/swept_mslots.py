"""Millions of padded neighbour slots a job's sweeps run over: the
``swept_slots`` count of the ``kcore.sweep`` spans (rows times width of
every tile a sweep ran), summed over the job's sweeps, averaged over the
window's jobs."""
from bench.stages import count_per_job


def read(run):
    slots = count_per_job(run, "kcore.sweep", "swept_slots")
    return None if slots is None else slots / 1e6
