"""Percent of the cell's HBM bandwidth that one job's least traffic would
take over the device-busy seconds of one job: busy seconds per chip over
all the cell's chips, against their summed bandwidth.

The least traffic is what any exact k-core decomposition must move, priced
as one sweep of ``sweep_tile_cost`` (``roofline/kcore_model.py``) at its
least: one read of every adjacency slot and of the estimate it names
(``2m * 8`` bytes) and one read and one write of every vertex's state
(``n * 20`` bytes, that function's row terms). It does not depend on the
engine, the frontier or the divide, so it reads the same work whatever does
it, and it cannot pass 100% unless an engine moves less than 8 bytes a
slot."""


def least_bytes(n: int, m: int) -> int:
    return 2 * m * 8 + n * 20


def read(run):
    if run.trace is None or run.peaks is None or not run.jobs:
        return None
    busy_per_job = run.trace.busy_s / len(run.jobs)
    if busy_per_job <= 0:
        return None
    return 100.0 * least_bytes(run.n, run.m) / busy_per_job \
        / (run.chips * run.peaks["hbm_bytes_per_s"])
