"""Host seconds a job spends folding finalized parts out of the remaining
graph: the ``kcore.divide.fold`` spans (the E(v) fold and the shrink's
``induced_subgraph``), averaged over the window's jobs."""
from bench.stages import seconds_per_job


def read(run):
    return seconds_per_job(run, ("kcore.divide.fold",))
