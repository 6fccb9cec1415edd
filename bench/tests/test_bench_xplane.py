"""The trace reduction, on intervals made by hand and on a small trace that
the test records on the CPU backend."""
import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run, xplane  # noqa: E402
from bench.xplane import Op, Span  # noqa: E402


def test_union_merges_overlaps_and_touching():
    assert xplane._union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_reduce_busy_gaps_and_names():
    ops = {"/device:TPU:0": [
        Op("sort.1", 10, 30, "jit__sweep"),
        Op("add.2", 25, 40, "jit__sweep"),      # overlaps sort.1
        Op("copy.3", 70, 80, "jit_concatenate"),
        Op("late", 95, 120, "jit__sweep"),      # clipped at the window end
    ]}
    spans = [Span("bench.window", 0, 100), Span("bench.job", 1, 100),
             Span("bench.plan_thresholds", 40, 60)]
    s = xplane.reduce(ops, spans, (0, 100))
    assert s.busy_s == pytest.approx((30 + 10 + 5) / 1e9)
    assert s.window_s == pytest.approx(100 / 1e9)
    assert s.idle_share == pytest.approx(1 - 45 / 100)
    gaps = dict(s.idle_gaps)
    assert gaps["bench.job before jit__sweep"] == pytest.approx((10 + 15) / 1e9)
    assert gaps["bench.plan_thresholds before jit_concatenate"] == \
        pytest.approx(30 / 1e9)
    assert dict(s.device_ops)["jit__sweep/sort.1"] == pytest.approx(20 / 1e9)
    assert s.device_ops[0][0] == "jit__sweep/sort.1"


def test_reduce_averages_over_devices_and_caps_lists():
    ops = {f"/device:TPU:{d}": [Op(f"op{i}", 10 * i, 10 * i + 5, "m")
                                for i in range(20)] for d in range(2)}
    s = xplane.reduce(ops, [], (0, 200), chips=2)
    assert s.n_devices == 2
    assert s.busy_s == pytest.approx(100 / 1e9)
    assert len(s.device_ops) == xplane.TOP
    assert len(s.idle_gaps) <= xplane.TOP


def test_a_chip_idle_all_window_counts_in_the_average():
    # Four chips; the fourth runs nothing, so the trace has no ops for it.
    ops = {f"/device:TPU:{d}": [Op("sweep", 0, 60, "jit__sweep")]
           for d in range(3)}
    s = xplane.reduce(ops, [Span("bench.job", 0, 100)], (0, 100), chips=4)
    assert s.n_devices == 4
    assert s.busy_s == pytest.approx(3 * 60 / 4 / 1e9)
    assert s.idle_share == pytest.approx(1 - 180 / 400)
    assert dict(s.device_ops)["jit__sweep/sweep"] == pytest.approx(45 / 1e9)
    gaps = dict(s.idle_gaps)
    assert gaps["bench.job before window end"] == pytest.approx(3 * 40 / 4 / 1e9)
    assert gaps[xplane.IDLE_CHIP] == pytest.approx(100 / 4 / 1e9)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_reduce_refuses_more_devices_than_chips():
    ops = {f"/device:TPU:{d}": [Op("op", 0, 5, "m")] for d in range(2)}
    with pytest.raises(ValueError, match="2 devices"):
        xplane.reduce(ops, [], (0, 10), chips=1)


@pytest.mark.parametrize("chips", [1, 4])
def test_hbm_share_prices_every_chip(chips):
    read = run.reader("kcore_hbm_share")
    trace = xplane.Summary(busy_s=2.0, window_s=4.0, n_devices=chips,
                           n_ops=1, device_ops=[], idle_gaps=[])
    r = types.SimpleNamespace(trace=trace, jobs=[None, None], n=1000,
                              m=8000, chips=chips,
                              peaks={"hbm_bytes_per_s": 819e9})
    least = 2 * 8000 * 8 + 1000 * 20
    assert read(r) == pytest.approx(100 * least / 1.0 / (chips * 819e9))


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(ValueError, match="no device operation"):
        xplane.reduce({}, [], (0, 1))


def test_short_names():
    assert xplane.short_name(
        "%cond.568 = (s32[11725]{0}) conditional(s32[] %b), x") == "cond.568"
    assert xplane.short_name("jit__sweep(13198702613704328235)") == "jit__sweep"


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(x @ x, axis=1).sum())
    x = jnp.ones((128, 128), jnp.float32)
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.job"):
                f(x).block_until_ready()
            time.sleep(0.01)
    jax.profiler.stop_trace()
    s = xplane.summarize(str(tmp_path), "cpu", "bench.window")
    assert s.n_ops > 0
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.idle_share < 1
    assert any(name.startswith("jit__lambda/") for name, _ in s.device_ops)
    assert any(name.startswith("bench.") for name, _ in s.idle_gaps)
    assert sum(sec for _, sec in s.idle_gaps) <= s.window_s - s.busy_s + 1e-9
