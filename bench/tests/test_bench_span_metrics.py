"""The per-layer readers of the program's spans, on fake runs whose spans
are made by hand."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run  # noqa: E402
from repro.core.dckcore import DCKCoreReport  # noqa: E402
from repro.core.spans import SpanRecord  # noqa: E402

SPAN_METRICS = ("extract_s", "fold_s", "bucketize_s", "conquer_io_s",
                "sweep_wait_ms", "sweep_host_ms", "swept_mslots")
MS = 1_000_000  # ns
DIVIDED_ONLY = ("kcore.divide.candidates", "kcore.divide.extract",
                "kcore.divide.fold")


def job_spans(scale=1, divided=True):
    """One job, times in ms: two parts of two sweeps each, the first one a
    threshold part where ``divided``."""
    recs = []

    def add(name, start, end, parent, **counts):
        if not divided and name in DIVIDED_ONLY:
            return None
        recs.append(SpanRecord(name, start * MS * scale, end * MS * scale,
                               parent, "MainThread", counts))
        return len(recs) - 1

    job = add("kcore.job", 0, 1000, -1)
    p1 = add("kcore.part", 0, 500, job, compiles=0, threshold=8)
    add("kcore.divide.candidates", 0, 10, p1)
    add("kcore.divide.extract", 10, 100, p1)
    add("kcore.divide.bucketize", 100, 150, p1)
    add("kcore.conquer.setup", 150, 170, p1)
    s = add("kcore.sweep", 170, 270, p1, swept_slots=3_000_000)
    add("kcore.sweep.wait", 175, 265, s)
    s = add("kcore.sweep", 270, 370, p1, swept_slots=1_000_000)
    add("kcore.sweep.wait", 280, 360, s)
    add("kcore.conquer.readout", 370, 380, p1)
    add("kcore.merge", 380, 390, p1)
    add("kcore.divide.fold", 390, 490, p1)
    p2 = add("kcore.part", 500, 1000, job, compiles=1)
    add("kcore.divide.bucketize", 500, 550, p2)
    add("kcore.conquer.setup", 550, 560, p2)
    s = add("kcore.sweep", 560, 760, p2, swept_slots=2_000_000)
    add("kcore.sweep.wait", 570, 750, s)
    s = add("kcore.sweep", 760, 960, p2, swept_slots=2_000_000)
    add("kcore.sweep.wait", 770, 950, s)
    add("kcore.conquer.readout", 960, 990, p2)
    return recs


def fake_run(*span_lists):
    jobs = [types.SimpleNamespace(report=DCKCoreReport(
        parts=[], total_time_s=1.0, preprocess_time_s=0.0, spans=s))
        for s in span_lists]
    return types.SimpleNamespace(jobs=jobs)


def read(name, r):
    return run.reader(name)(r)


def test_readers_on_two_divided_jobs():
    # The second job takes twice as long in every span.
    r = fake_run(job_spans(), job_spans(scale=2))
    assert read("extract_s", r) == pytest.approx((0.100 + 0.200) / 2)
    assert read("fold_s", r) == pytest.approx((0.100 + 0.200) / 2)
    assert read("bucketize_s", r) == pytest.approx((0.100 + 0.200) / 2)
    assert read("conquer_io_s", r) == pytest.approx((0.070 + 0.140) / 2)
    # Waits 90, 80, 180, 180 ms a job, then doubled: over 8 sweeps.
    assert read("sweep_wait_ms", r) == pytest.approx(530 * 3 / 8)
    # Sweep self time 10, 20, 20, 20 ms a job, then doubled.
    assert read("sweep_host_ms", r) == pytest.approx(70 * 3 / 8)
    # The counts do not scale with time: 8 Mslots a job.
    assert read("swept_mslots", r) == pytest.approx(8.0)


def test_a_whole_graph_job_has_no_extract_or_fold():
    r = fake_run(job_spans(divided=False))
    assert read("extract_s", r) is None
    assert read("fold_s", r) is None
    assert read("bucketize_s", r) == pytest.approx(0.100)
    assert read("conquer_io_s", r) == pytest.approx(0.070)
    assert read("swept_mslots", r) == pytest.approx(8.0)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_program_without_spans_reads_none(name):
    # A report from before the program had spans: no stage_seconds, no spans.
    old = types.SimpleNamespace(jobs=[types.SimpleNamespace(
        report=types.SimpleNamespace(parts=[], preprocess_time_s=0.0))])
    assert read(name, old) is None
    assert read(name, types.SimpleNamespace(jobs=[])) is None
    assert read(name, fake_run([])) is None


def test_every_span_metric_is_declared_with_its_cells():
    spec = run._json(os.path.join(ROOT, "BENCHMARK.json"))
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    for name in SPAN_METRICS:
        m = per_layer[name]
        assert m["moves"] == "solve_s" and m["better"] == "lower"
        assert m["workloads"] == (["kron-divided"]
                                  if name in ("extract_s", "fold_s")
                                  else ["kron-divided", "kron-whole"])
