"""Program spans (repro.core.spans): the records a run leaves on its report,
their nesting and counts, the timers read from them, the worker threads'
spans, the same names in a profiler trace, and compiles per part."""
from __future__ import annotations

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import spans
from repro.core.dckcore import PREFETCH_THREAD_PREFIX, dc_kcore
from repro.core.decompose import decompose
from repro.core.divide import timed_candidates
from repro.graph.build import bucketize
from repro.graph.generators import rmat
from repro.graph.oracle import peel_coreness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The module, not the function ``repro.core`` exports under the same name.
decompose_mod = importlib.import_module("repro.core.decompose")

DIVIDE = ("kcore.divide.candidates", "kcore.divide.extract",
          "kcore.divide.bucketize", "kcore.divide.fold")


@pytest.fixture(scope="module")
def graph():
    return rmat(10, 8, seed=1)


def by_name(report, name):
    return [r for r in report.spans if r.name == name]


def ancestors(records, r):
    out = []
    while r.parent >= 0:
        r = records[r.parent]
        out.append(r.name)
    return out


def parts_of(report):
    """Each ``kcore.part`` span with the spans nested in it."""
    recs = report.spans
    out = []
    for i, r in enumerate(recs):
        if r.name != "kcore.part":
            continue
        inside = []
        for c in recs:
            p = c.parent
            while p >= 0 and p != i:
                p = recs[p].parent
            if p == i:
                inside.append(c)
        out.append((r, inside))
    return out


# --------------------------------------------------------------------- #
# The recorder itself
# --------------------------------------------------------------------- #
def test_span_without_a_recorder_times_itself_and_records_nothing():
    assert spans.current() is None
    with spans.span("kcore.x") as sp:
        spans.count("n", 3)
    assert sp.seconds >= 0 and sp.end_ns >= sp.start_ns > 0
    assert sp.counts == {}


def test_nesting_counts_and_self_time():
    with spans.recording() as rec:
        with spans.span("a", k=1):
            with spans.span("b"):
                spans.count("rows", 2)
                spans.count("rows", 3)
            with spans.span("b"):
                pass
        with spans.span("c"):
            pass
    a, b1, b2, c = rec.records
    assert [r.name for r in rec.records] == ["a", "b", "b", "c"]
    assert (a.parent, b1.parent, b2.parent, c.parent) == (-1, 0, 0, -1)
    assert a.counts == {"k": 1} and b1.counts == {"rows": 5}
    st = spans.stage_seconds(rec.records)
    assert st["b"].count == 2
    assert st["b"].total_s == pytest.approx(b1.seconds + b2.seconds)
    assert st["a"].self_s == pytest.approx(
        a.seconds - b1.seconds - b2.seconds)
    assert st["c"].self_s == st["c"].total_s == pytest.approx(c.seconds)
    assert spans.current() is None  # unbound after the block


def test_rebinding_the_same_recorder_keeps_the_parent():
    with spans.recording() as rec:
        with spans.span("outer"):
            with spans.bound(rec), spans.span("inner"):
                pass
    assert [r.parent for r in rec.records] == [-1, 0]


def test_stage_seconds_of_hand_made_records():
    R = spans.SpanRecord
    recs = [R("job", 0, 100, -1, "main", {}),
            R("sweep", 10, 40, 0, "main", {}),
            R("sweep.wait", 20, 30, 1, "main", {}),
            R("sweep", 50, 60, 0, "main", {}),
            R("fold", 5, 95, -1, "worker", {})]
    st = spans.stage_seconds(recs)
    assert st["job"].self_s == pytest.approx(60e-9)
    assert st["sweep"].total_s == pytest.approx(40e-9)
    assert st["sweep"].self_s == pytest.approx(30e-9)
    assert st["sweep"].count == 2
    assert st["fold"].self_s == pytest.approx(90e-9)


# --------------------------------------------------------------------- #
# The spans of a run
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("thresholds", [[20, 8], []], ids=["divided", "whole"])
def test_span_names_nesting_and_sweep_count(graph, thresholds):
    core, report = dc_kcore(graph, thresholds=thresholds, engine="count")
    np.testing.assert_array_equal(core, peel_coreness(graph))
    recs = report.spans
    names = {r.name for r in recs}
    want = {"kcore.job", "kcore.part", "kcore.divide.bucketize",
            "kcore.conquer.setup", "kcore.sweep", "kcore.sweep.wait",
            "kcore.conquer.readout", "kcore.merge"}
    if thresholds:
        want |= {"kcore.divide.candidates", "kcore.divide.extract",
                 "kcore.divide.fold"}
    assert names == want
    (job,) = by_name(report, "kcore.job")
    assert job.parent == -1
    for r in recs:
        assert r.end_ns >= r.start_ns > 0
        assert r.thread == "MainThread"
        if r.parent >= 0:
            p = recs[r.parent]
            assert p.start_ns <= r.start_ns <= r.end_ns <= p.end_ns
    for w in by_name(report, "kcore.sweep.wait"):
        assert ancestors(recs, w) == ["kcore.sweep", "kcore.part",
                                      "kcore.job"]
    sweeps = by_name(report, "kcore.sweep")
    assert len(sweeps) == report.total_iterations
    assert len(by_name(report, "kcore.sweep.wait")) == len(sweeps)
    parts = parts_of(report)
    assert len(parts) == len(report.parts)
    for (part, inside), pr in zip(parts, report.parts):
        assert sum(c.name == "kcore.sweep" for c in inside) == pr.iterations
        assert part.counts["n_nodes"] == pr.n_nodes
        assert part.counts["n_edges"] == pr.n_edges
        if pr.threshold is None:
            assert "threshold" not in part.counts
        else:
            assert part.counts["threshold"] == pr.threshold
    for s, pr_rows in zip(sweeps, [r for p in report.parts
                                   for r in p.active_rows_per_iter]):
        assert s.counts["active_rows"] == pr_rows
    st = report.stage_seconds()
    assert st["kcore.job"].count == 1
    assert st["kcore.sweep"].count == report.total_iterations


@pytest.mark.parametrize("op,frontier", [("count", True), ("count", False),
                                         ("sorted", True)])
def test_swept_slots_are_rows_times_width_of_the_tiles_run(
        graph, monkeypatch, op, frontier):
    bg = bucketize(graph, max_bucket_rows=64)
    assert len(bg.buckets) > 4
    masks = []
    real_sweep = decompose_mod._sweep

    def spy(c, ext_pad, buckets, active, **kw):
        masks.append(np.asarray(active).copy())
        return real_sweep(c, ext_pad, buckets, active, **kw)

    monkeypatch.setattr(decompose_mod, "_sweep", spy)
    with spans.recording() as rec:
        res = decompose(bg, op=op, frontier=frontier)
    sweeps = [r for r in rec.records if r.name == "kcore.sweep"]
    assert len(sweeps) == len(masks) == res.iterations
    slots = np.array([b.neigh.shape[0] * b.neigh.shape[1]
                      for b in bg.buckets])
    rows = np.array([b.neigh.shape[0] for b in bg.buckets])
    for s, mask, active_rows, changed in zip(
            sweeps, masks, res.active_rows_per_iter, res.comm_per_iter):
        assert s.counts["changed_rows"] == changed
        assert s.counts["swept_slots"] == int(slots[mask].sum())
        assert s.counts["active_rows"] == int(rows[mask].sum()) == active_rows
        assert s.counts["active_tiles"] == int(mask.sum())
    if not frontier:
        assert all(s.counts["swept_slots"] == slots.sum() for s in sweeps)
    else:
        assert sweeps[-1].counts["swept_slots"] < slots.sum()
    names = [r.name for r in rec.records]
    assert names[0] == "kcore.conquer.setup"
    assert names[-1] == "kcore.conquer.readout"


def test_modeled_sweep_cost_is_priced_per_sweep(graph):
    res = decompose(bucketize(graph, max_bucket_rows=64), op="count")
    assert len(res.sweep_bytes_per_iter) == res.iterations
    assert res.sweep_bytes_per_iter[0] > res.sweep_bytes_per_iter[-1] > 0


def test_timed_candidates_returns_its_span_seconds(graph):
    ext = np.zeros(graph.n_nodes, np.int32)
    with spans.recording() as rec:
        mask, secs = timed_candidates(graph, ext, 8, "exact")
    (r,) = rec.records
    assert r.name == "kcore.divide.candidates"
    assert secs == r.seconds and mask.any()


def test_report_timers_equal_their_spans(graph):
    _, report = dc_kcore(graph, thresholds=[20, 8], engine="count")
    parts = parts_of(report)
    for (part, inside), pr in zip(parts, report.parts):
        cand = [c.seconds for c in inside
                if c.name == "kcore.divide.candidates"]
        ext = [c.seconds for c in inside if c.name == "kcore.divide.extract"]
        if pr.threshold is None:
            assert pr.extract_time_s == 0.0 and not cand and not ext
        else:
            assert pr.extract_time_s == cand[0] + ext[0]
    st = report.stage_seconds()
    divide = sum(st[n].total_s for n in DIVIDE)
    # Beyond its spans the divide time holds only the conquer's snapshot
    # consult, a few host statements a part.
    assert divide <= report.preprocess_time_s < divide + 0.05


def test_prefetch_worker_spans_carry_its_thread_name(graph):
    core, report = dc_kcore(graph, thresholds=[20, 8], engine="count",
                            overlap=True)
    np.testing.assert_array_equal(core, peel_coreness(graph))
    assert report.prefetch_hits + report.prefetch_misses >= 1
    worker = [r for r in report.spans
              if r.thread.startswith(PREFETCH_THREAD_PREFIX)]
    assert {"kcore.divide.fold", "kcore.divide.bucketize"} <= \
        {r.name for r in worker}
    for r in worker:
        # A worker's spans nest among themselves, never under the main
        # thread's.
        assert r.parent == -1 or report.spans[r.parent].thread == r.thread
    main = {r.name for r in report.spans if r.thread == "MainThread"}
    assert {"kcore.job", "kcore.part", "kcore.sweep"} <= main
    if report.prefetch_hits:
        # An adopted speculative shrink books its fold span's seconds.
        st = report.stage_seconds()
        assert report.preprocess_time_s >= \
            min(r.seconds for r in worker if r.name == "kcore.divide.fold")
        assert st["kcore.divide.fold"].count >= 1


def test_wave_slices_record_their_parts(graph):
    core, report = dc_kcore(graph, thresholds=[20, 8], engine="count",
                            part_parallel=2)
    np.testing.assert_array_equal(core, peel_coreness(graph))
    parts = by_name(report, "kcore.part")
    assert parts and all(p.thread.startswith("dckcore-conquer") for p in parts)
    assert sum(1 for r in report.spans if r.name == "kcore.sweep"
               and report.spans[r.parent].name == "kcore.part") == \
        len(by_name(report, "kcore.sweep"))


def test_checkpoint_span_only_with_checkpointing(graph, tmp_path):
    _, plain = dc_kcore(graph, thresholds=[20, 8], engine="count")
    assert not by_name(plain, "kcore.checkpoint")
    _, saved = dc_kcore(graph, thresholds=[20, 8], engine="count",
                        checkpoint_dir=str(tmp_path / "ck"))
    assert len(by_name(saved, "kcore.checkpoint")) == len(saved.parts)


def test_plan_span(graph):
    from repro.core.divide import plan_thresholds

    with spans.recording() as rec:
        thresholds = plan_thresholds(graph.degrees, graph.n_edges * 4)
    assert thresholds
    assert [r.name for r in rec.records] == ["kcore.plan"]


# --------------------------------------------------------------------- #
# Compiles per part
# --------------------------------------------------------------------- #
def test_compiles_are_counted_on_the_part_that_built_them(graph):
    calls = []

    def engine(bg, **kw):
        calls.append(bg.n_nodes)
        res = decompose(bg, op="count", **kw)
        if len(calls) % 3 == 2:
            # A fresh function: one new executable, in the second part.
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(5)).block_until_ready()
        return res

    dc_kcore(graph, thresholds=[20, 8], decompose_fn=engine)
    _, report = dc_kcore(graph, thresholds=[20, 8], decompose_fn=engine)
    parts = by_name(report, "kcore.part")
    assert [int(p.counts["compiles"]) for p in parts] == [0, 1, 0]
    assert parts[1].counts["compile_ms"] > 0
    assert parts[0].counts["compile_ms"] == 0


# --------------------------------------------------------------------- #
# The profiler's trace
# --------------------------------------------------------------------- #
def test_profiler_trace_holds_the_program_spans(graph, tmp_path):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench import xplane

    dc_kcore(graph, thresholds=[20, 8])  # compile outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.job"):
                _, report = dc_kcore(graph, thresholds=[20, 8])
    finally:
        jax.profiler.stop_trace()
    profile = jax.profiler.ProfileData.from_file(
        xplane.find_xplane(str(tmp_path)))
    traced = []
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("kcore.", "bench.")):
                    start = float(ev.start_ns)
                    traced.append(xplane.Span(
                        ev.name, start, start + float(ev.duration_ns)))
    names = [s.name for s in traced if s.name.startswith("kcore.")]
    for name in {r.name for r in report.spans}:
        assert names.count(name) == len(by_name(report, name)), name
    (job,) = [s for s in traced if s.name == "bench.job"]
    for s in traced:
        if s.name.startswith("kcore."):
            assert job.start_ns <= s.start_ns <= s.end_ns <= job.end_ns
    ops, _bench_spans = xplane.collect(profile, "cpu")
    summary = xplane.reduce(ops, traced,
                            xplane.window_of(traced, "bench.window"))
    assert any(gap.startswith("kcore.") for gap, _ in summary.idle_gaps)


# --------------------------------------------------------------------- #
# The CLI
# --------------------------------------------------------------------- #
def test_cli_prints_stage_seconds_and_compiles(monkeypatch, capsys):
    from repro.launch import kcore

    monkeypatch.setattr(sys, "argv", [
        "kcore", "--graph", "rmat:9:8", "--budget-gb", "0.00002",
        "--engine", "count", "--check"])
    kcore.main()
    out = capsys.readouterr().out
    (line,) = [ln for ln in out.splitlines() if ln.startswith("stages (s)")]
    for word in ("job", "plan", "candidates", "extract", "fold", "bucketize",
                 "conquer set-up", "read-out", "sweeps", "tiles", "rows",
                 "Mslots", "changed rows", "wait", "host", "merge",
                 "checkpoint"):
        assert word in line
    (comp,) = [ln for ln in out.splitlines()
               if ln.startswith("compiles per part:")]
    assert "core>=" in comp and "rest (n=" in comp and comp.count(";") >= 1
    assert "accelerator idle fraction" not in out
    assert "CONSISTENT" in out
