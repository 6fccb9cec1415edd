"""Mean wall seconds of one job: the window's host-clock length over the
jobs it completed. The window runs whole jobs back to back and ends with
the job in flight, so every job it started counts."""


def read(run):
    return run.window_s / len(run.jobs) if run.jobs else None
