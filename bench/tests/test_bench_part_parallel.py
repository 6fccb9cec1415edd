"""A part-parallel mix over four devices, added as data files only: a
checkout with one more traffic file and two more cells, run whole on four
virtual CPU devices in a child process (``part_parallel_child.py``). The
sound runs are correct and report the cell's metrics on two slices of two
devices, each fault of the distributed engine and the control turn
``correct`` false, a cell with fewer chips than the mix's devices gives no
result line, and the job builds the CLI's own run keywords."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run  # noqa: E402

CELL, ONE_CHIP, MIX = "kron-pp-trial", "kron-pp-one-chip", "pp-trial"
TRAFFIC = {"why": "kcore CLI --budget-gb at 0.6 of the whole-graph estimate "
                  "with --part-parallel 2 --devices 4",
           "budget_fraction": 0.6, "part_parallel": 2, "devices": 4}
FAULTS = ("state_unchanged", "half_left_out", "exchange_left_out",
          "answer_altered")


def checkout(dest):
    """The benchmark's files with the mix and its cells added."""
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = run._json(os.path.join(ROOT, "BENCHMARK.json"))
    for name, chips in ((CELL, 4), (ONE_CHIP, 1)):
        spec["workloads"].append({"name": name, "config": "gap-kron",
                                  "traffic": MIX, "chips": chips,
                                  "why": "a part-parallel mix on test devices"})
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    with open(os.path.join(dest, "bench", "traffic", MIX + ".json"), "w") as f:
        json.dump(TRAFFIC, f)
    return spec


@pytest.fixture(scope="module")
def child(tmp_path_factory):
    dest = str(tmp_path_factory.mktemp("checkout"))
    spec = checkout(dest)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED="0")
    flags = [t for t in env.get("XLA_FLAGS", "").split()
             if not t.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=4"])
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, os.path.join(dest, "bench", "tests",
                                      "part_parallel_child.py"),
         dest, CELL, ONE_CHIP],
        capture_output=True, text=True, env=env, cwd=dest, timeout=600)
    assert proc.returncode == 0, proc.stderr[-8000:]
    return spec, json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_part_parallel_run_is_correct_on_two_slices(child, trace):
    spec, out = child
    assert out["n_devices"] == 4
    sound = out[f"sound_t{trace}"]
    assert sound["rc"] == 0
    line = sound["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"]["wrong_nodes"] == {"value": 0, "limit": 0}
    assert line["device"]["count"] == 4
    assert sound["slices"] == [0, 1]
    want = {m["name"] for m in run.metrics_of(spec, CELL, bool(trace))}
    got = set(line["metrics"])
    if trace:
        assert got == want - {"kcore_hbm_share"}
        assert 0 < line["device"]["busy_s"] < line["device"]["window_s"]
    else:
        assert want == {"solve_s", "peak_hbm_gib", "setup_s"}
        assert got == want - {"peak_hbm_gib"}


def test_job_builds_the_cli_plan_and_slice_capacity(child):
    _spec, out = child
    cli, job = out["cli"], out["job"]
    assert job == cli
    plan = job["part_parallel_plan"]
    assert plan["shape"] == {"data": 2, "model": 2}
    assert plan["slices"] == [[0, 1], [2, 3]]
    assert job["slice_capacity_bytes"] == 2**20


@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_distributed_path_is_not_correct(child, fault):
    out = child[1][fault]
    assert out["rc"] == 0
    assert out["line"]["correct"] is False
    assert out["line"]["checks"]["wrong_nodes"]["value"] > 0
    assert out["line"]["failed"] == out["line"]["attempted"]


def test_control_reaches_the_distributed_engine(child):
    out = child[1]["control"]
    assert out["rc"] == 0
    assert out["summary"]["lower_wrong_nodes"] == 0
    assert out["summary"]["upper_wrong_nodes"] > 0


def test_mix_on_more_devices_than_chips_gives_no_result(child):
    out = child[1]["one_chip"]
    assert out["rc"] == 2
    assert out["stdout"] == ""
