"""Whole runs of the harness on the CPU backend at a tiny scale: the result
line, the per-layer readers, the refusal to run without a TPU, the faults
that must turn ``correct`` false, and the control."""
import importlib
import json
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import control, graphs, job as jobs_mod, peaks, run  # noqa: E402
from bench.xplane import Summary  # noqa: E402

TINY = {"scale": 10}
SPEC = run._json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in SPEC["workloads"]]


def result_line(capsys, argv, **kw):
    assert run.main(argv, require_accelerator=False, overrides=TINY, **kw) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def args(cell, trace=0, seed=2**31 + 3):
    return ["--workload", cell, "--seed", str(seed), "--seconds", "0.2",
            "--trace", str(trace)]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_tiny_run_is_correct_and_reports_the_cell_metrics(capsys, cell, trace):
    out = result_line(capsys, args(cell, trace))
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert out["checks"]["wrong_nodes"] == {"value": 0, "limit": 0}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(out["device"])
    want = {m["name"] for m in run.metrics_of(SPEC, cell, bool(trace))}
    got = set(out["metrics"])
    if trace:
        # Off the chip there are no peaks to price kcore_hbm_share against.
        assert got == want - {"kcore_hbm_share"}
        assert 0 < out["device"]["busy_s"] < out["device"]["window_s"]
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        # Off the chip the device reports no memory peak.
        assert got == want - {"peak_hbm_gib"}


def test_no_tpu_means_no_result(capsys):
    assert run.main(args("kron-divided")) == 2
    assert capsys.readouterr().out == ""


def test_reports_of_a_tiny_job_feed_every_per_layer_reader():
    import jax

    from repro.graph.structs import Graph

    cfg = {**run._json(os.path.join(ROOT, "bench/configs/gap-kron.json")), **TINY}
    csr = graphs.make_graph(cfg, 9)
    g = Graph(indptr=csr.indptr, indices=csr.indices, n_nodes=csr.n)
    traffic = run._json(os.path.join(ROOT, "bench/traffic/divided.json"))
    job = jobs_mod.run_job(jax, g, jobs_mod.budget_bytes(traffic, csr.degrees),
                           jobs_mod.dc_kwargs(traffic))
    assert len(job.report.parts) >= 2
    trace = Summary(busy_s=0.5, window_s=2.0, n_devices=1, n_ops=10,
                    device_ops=[], idle_gaps=[])
    r = run.Run(setup_s=3.0, window_s=2.0, jobs=[job, job], n=csr.n, m=csr.m,
                chips=1, peak_bytes=2**20, peaks=peaks.lookup("TPU v5 lite"),
                trace=trace, window_built=0)
    for m in SPEC["per_layer"] + SPEC["end_to_end"]:
        value = run.reader(m["name"])(r)
        assert value is not None and math.isfinite(value), m["name"]
        assert value >= 0, m["name"]
    assert run.reader("device_idle_share")(r) == pytest.approx(75.0)
    assert run.reader("sweeps")(r) == job.report.total_iterations
    share = run.reader("kcore_hbm_share")(r)
    assert share == pytest.approx(
        100 * (16 * csr.m + 20 * csr.n) / 0.25 / 819e9)


def _state_unchanged(monkeypatch):
    import jax.numpy as jnp

    decompose = importlib.import_module("repro.core.decompose")

    def sweep(c, ext_pad, buckets, active, **_kw):
        nb = len(buckets)
        return c, jnp.zeros((nb,), jnp.int32), jnp.zeros((nb,), bool)

    monkeypatch.setattr(decompose, "_sweep", sweep)


def _half_left_out(monkeypatch):
    import jax.numpy as jnp

    decompose = importlib.import_module("repro.core.decompose")

    real = decompose._sweep

    def sweep(c, ext_pad, buckets, active, **kw):
        keep = jnp.arange(active.shape[0]) < active.shape[0] // 2
        return real(c, ext_pad, buckets, active & keep, **kw)

    monkeypatch.setattr(decompose, "_sweep", sweep)


def _exchange_left_out(monkeypatch):
    dckcore = importlib.import_module("repro.core.dckcore")

    real = dckcore.external_info
    monkeypatch.setattr(dckcore, "external_info",
                        lambda *a, **kw: np.zeros_like(real(*a, **kw)))


def _answer_altered(monkeypatch):
    dckcore = importlib.import_module("repro.core.dckcore")

    real = dckcore.decompose

    def decompose(bg, **kw):
        res = real(bg, **kw)
        res.coreness = res.coreness.copy()
        res.coreness[-1] += 1
        return res

    monkeypatch.setattr(dckcore, "decompose", decompose)


FAULTS = [(cell, fault) for cell in CELLS
          for fault in (_state_unchanged, _half_left_out, _answer_altered)]
# The E(v) fold passes estimates between parts, so only a divided cell has
# it to lose.
FAULTS += [("kron-divided", _exchange_left_out)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=lambda x: x if isinstance(x, str)
                         else x.__name__.strip("_"))
def test_a_broken_timed_path_is_not_correct(capsys, monkeypatch, cell, fault):
    fault(monkeypatch)
    out = result_line(capsys, args(cell))
    assert out["correct"] is False
    assert out["checks"]["wrong_nodes"]["value"] > 0
    assert out["failed"] == out["attempted"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_where_the_program_passes(capsys, cell):
    assert control.main(["--workload", cell, "--seeds", "4,5",
                         "--control-seeds", "4,5"],
                        require_accelerator=False, overrides=TINY) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["lower_wrong_nodes"] == 0
    assert summary["upper_wrong_nodes"] > 0
