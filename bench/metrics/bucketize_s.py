"""Host seconds a job spends laying its parts out as tiles: the
``kcore.divide.bucketize`` spans (``reorder_graph`` and ``bucketize``),
averaged over the window's jobs."""
from bench.stages import seconds_per_job


def read(run):
    return seconds_per_job(run, ("kcore.divide.bucketize",))
