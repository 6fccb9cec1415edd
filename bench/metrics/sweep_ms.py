"""Host milliseconds per conquer sweep, host round trip included:
``PartReport.decompose_time_s`` summed over every part of every job of the
window, over the sweeps (``PartReport.iterations``) they made."""


def read(run):
    sweeps = sum(p.iterations for j in run.jobs for p in j.report.parts)
    if sweeps == 0:
        return None
    secs = sum(p.decompose_time_s for j in run.jobs for p in j.report.parts)
    return 1000.0 * secs / sweeps
