"""CPU tests of the benchmark's data: generators, reference, peaks and the
files that ``BENCHMARK.json`` names."""
import glob
import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import graphs, peaks, reference  # noqa: E402
from bench.run import reader  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
CONFIGS = {c["name"]: c for c in SPEC["configs"]}
# Every configuration file, also one that no cell uses yet.
CONFIG_FILES = sorted(glob.glob(os.path.join(ROOT, "bench", "configs", "*.json")))
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def small(path, scale=10):
    with open(path) as f:
        return {**json.load(f), "scale": scale}


by_file = pytest.mark.parametrize("path", CONFIG_FILES, ids=os.path.basename)


@by_file
def test_generator_same_seed_same_graph(path):
    cfg = small(path)
    a, b = graphs.make_graph(cfg, 2**31 + 11), graphs.make_graph(cfg, 2**31 + 11)
    assert np.array_equal(a.indptr, b.indptr)
    assert np.array_equal(a.indices, b.indices)


@by_file
def test_other_seed_relabels_the_same_graph(path):
    cfg = small(path)
    a, b = graphs.make_graph(cfg, 1), graphs.make_graph(cfg, 2)
    assert a.m == b.m and a.n == b.n
    assert not np.array_equal(a.indices, b.indices)
    assert np.array_equal(np.sort(a.degrees), np.sort(b.degrees))
    ref_a = reference.coreness(a.indptr, a.indices)
    ref_b = reference.coreness(b.indptr, b.indices)
    assert np.array_equal(np.sort(ref_a), np.sort(ref_b))


@by_file
def test_csr_is_symmetric_simple_and_sorted(path):
    g = graphs.make_graph(small(path), 5)
    rows = np.repeat(np.arange(g.n), g.degrees)
    cols = g.indices.astype(np.int64)
    assert not np.any(rows == cols)
    fwd = np.sort(rows * g.n + cols)
    assert np.array_equal(fwd, np.sort(cols * g.n + rows))
    assert np.all(np.diff(fwd) > 0)  # sorted rows, no duplicate edge


@by_file
def test_reference_matches_peeling_oracle(path):
    from repro.graph.oracle import peel_coreness
    from repro.graph.structs import Graph

    g = graphs.make_graph(small(path, scale=11), 3)
    want = peel_coreness(Graph(indptr=g.indptr, indices=g.indices, n_nodes=g.n))
    assert np.array_equal(reference.coreness(g.indptr, g.indices), want)


def test_reference_on_hand_made_graphs():
    # A triangle with a pendant vertex, and an isolated vertex.
    keys = np.array([0 * 5 + 1, 0 * 5 + 2, 1 * 5 + 2, 2 * 5 + 3])
    g = graphs.csr_from_pairs(keys, 5)
    assert reference.coreness(g.indptr, g.indices).tolist() == [2, 2, 2, 1, 0]
    # A 4-clique: coreness 3 everywhere.
    keys = np.array([a * 4 + b for a in range(4) for b in range(a + 1, 4)])
    g = graphs.csr_from_pairs(keys, 4)
    assert reference.coreness(g.indptr, g.indices).tolist() == [3, 3, 3, 3]


def test_peaks_known_kind():
    row = peaks.lookup("TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["hbm_bytes"] == 16 * 2**30


def test_peaks_unknown_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.lookup("cpu")


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_every_cell_names_files_that_exist(cell):
    assert cell["config"] in CONFIGS
    assert os.path.isfile(os.path.join(ROOT, CONFIGS[cell["config"]]["file"]))
    assert os.path.isfile(
        os.path.join(ROOT, "bench", "traffic", cell["traffic"] + ".json"))
    assert cell["chips"] in (1, 4)
    assert len(cell["why"]) <= 200


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_every_metric_file_loads_by_name(metric):
    assert callable(reader(metric["name"]))


@pytest.mark.parametrize("config_name", sorted(CONFIGS))
def test_config_file_states_what_was_reduced(config_name):
    entry = CONFIGS[config_name]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == config_name
    assert cfg["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert cfg["published"][key] != cfg[key]


def test_benchmark_names_and_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert set(CONFIGS) == {w["config"] for w in SPEC["workloads"]}
    names = [x["name"] for x in SPEC["configs"] + SPEC["workloads"] + METRICS]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert "bound" not in m
