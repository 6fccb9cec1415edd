"""Host seconds of the divide side of one job: ``plan_thresholds`` as the
benchmark times it, plus ``DCKCoreReport.preprocess_time_s`` (extract,
E(v) fold, shrink, bucketize), averaged over the window's jobs."""


def read(run):
    if not run.jobs:
        return None
    return sum(j.plan_s + j.report.preprocess_time_s
               for j in run.jobs) / len(run.jobs)
