"""Host seconds a job spends choosing and extracting its threshold parts:
the ``kcore.divide.candidates`` and ``kcore.divide.extract`` spans (the
candidate mask, then ``induced_subgraph`` and the part's ext), summed over
the job's parts, averaged over the window's jobs."""
from bench.stages import seconds_per_job


def read(run):
    return seconds_per_job(
        run, ("kcore.divide.candidates", "kcore.divide.extract"))
