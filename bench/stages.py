"""What the per-layer metrics read of the program's own spans
(``DCKCoreReport.spans``, written by ``repro.core.spans``): per span name,
total seconds, self seconds (less the time child spans cover) and a count,
through ``DCKCoreReport.stage_seconds()``. A program without spans, one
older than them, gives None and never an error."""


def _stages(job):
    reader = getattr(job.report, "stage_seconds", None)
    return None if reader is None else reader()


def seconds_per_job(run, names, field="total_s"):
    """Seconds of the spans ``names`` summed over the window's jobs, over
    the jobs; None where no job has any of them."""
    if not run.jobs:
        return None
    total, seen = 0.0, False
    for job in run.jobs:
        stages = _stages(job)
        if stages is None:
            return None
        for name in names:
            if name in stages:
                total += getattr(stages[name], field)
                seen = True
    return total / len(run.jobs) if seen else None


def ms_per_span(run, name, field="total_s"):
    """Milliseconds of the spans ``name`` over their number, across the
    window's jobs; None where there is none."""
    total, n = 0.0, 0
    for job in run.jobs:
        stages = _stages(job)
        if stages is None:
            return None
        if name in stages:
            total += getattr(stages[name], field)
            n += stages[name].count
    return 1e3 * total / n if n else None


def count_per_job(run, name, key):
    """The count ``key`` of the spans ``name`` summed over the window's
    jobs, over the jobs; None where no span carries it."""
    if not run.jobs:
        return None
    total, seen = 0, False
    for job in run.jobs:
        for record in getattr(job.report, "spans", ()):
            if record.name == name and key in record.counts:
                total += record.counts[key]
                seen = True
    return total / len(run.jobs) if seen else None
