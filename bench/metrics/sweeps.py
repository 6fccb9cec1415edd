"""Conquer sweeps of one job, summed over its parts (a count; the same for
every job of a cell)."""


def read(run):
    if not run.jobs:
        return None
    return sum(p.iterations for j in run.jobs
               for p in j.report.parts) / len(run.jobs)
