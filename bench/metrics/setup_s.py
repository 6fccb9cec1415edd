"""Seconds from process start to the window's start: imports, the graph,
the warm-up job, and every compile or compile-cache load."""


def read(run):
    return run.setup_s
