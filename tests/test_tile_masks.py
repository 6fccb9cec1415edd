"""Row tile masks: the conquer sweep's dirty bits without a per-slot push.

``_sweep`` ORs the tile masks of the rows that changed into the next
sweep's dirty tiles. These tests hold it, sweep by sweep, to a numpy sweep
that pushes a dirty bit to every neighbor slot of a changed row and reads
each tile's rows back (what the sweep computed before the masks), and hold
the masks and the bucket adjacency they OR to against their definitions.
"""
from __future__ import annotations

import importlib
import re

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hindex import hindex_of_sequence
from repro.graph.build import bucketize, external_info, induced_subgraph
from repro.graph.generators import rmat
from repro.graph.oracle import peel_coreness
from repro.graph.reorder import reorder_graph
from repro.graph.structs import (
    BucketedGraph,
    Graph,
    tile_mask_bits,
    tile_mask_words,
)

decompose_mod = importlib.import_module("repro.core.decompose")

CASES = ["auto", "uniform", "rcm", "divided", "seeded"]


def _graph():
    return rmat(11, 8, seed=7)


def _delete_edges(g: Graph, k: int, seed: int):
    """``g`` less ``k`` edges whose endpoints keep degree >= 2, and those
    endpoints."""
    rng = np.random.default_rng(seed)
    deg = g.degrees
    src = np.repeat(np.arange(g.n_nodes), deg)
    dst = g.indices.astype(np.int64)
    cand = np.nonzero((src < dst) & (deg[src] >= 4) & (deg[dst] >= 4))[0]
    drop = rng.choice(cand, size=k, replace=False)
    keep = np.ones(src.size, bool)
    keep[drop] = False
    pairs = set(zip(src[drop].tolist(), dst[drop].tolist()))
    keep &= np.array([(min(u, v), max(u, v)) not in pairs
                      for u, v in zip(src.tolist(), dst.tolist())])
    g2 = Graph.from_edges(src[keep], dst[keep], n_nodes=g.n_nodes)
    return g2, np.unique(np.concatenate([src[drop], dst[drop]]))


def _case(name: str):
    """``(bucketed part, starting estimates [n + 1], first active tiles,
    exact coreness of the part's nodes in layout order)``."""
    g = _graph()
    core = peel_coreness(g)
    if name == "divided":
        upper = core >= 10
        keep = ~upper
        part, _ids = induced_subgraph(g, keep)
        ext = external_info(g, keep, upper)
        bg = bucketize(part, ext, max_bucket_rows=16)
        assert bg.ext.any()
        want = core[keep]
    elif name == "seeded":
        g2, seeds = _delete_edges(g, 12, seed=3)
        bg = bucketize(g2)
        owner = bg.node_bucket_map()[:-1][seeds]
        active = np.zeros(len(bg.buckets), bool)
        active[owner[owner >= 0]] = True
        assert 0 < active.sum() < len(bg.buckets)
        start = np.concatenate([core, [-1]]).astype(np.int32)
        return bg, start, active, peel_coreness(g2)
    else:
        if name == "rcm":
            g = reorder_graph(g, "rcm")
            want = core[g.perm]
        else:
            want = core
        bg = bucketize(g, max_bucket_rows=16 if name == "uniform" else "auto")
    start = np.concatenate([bg.degrees + bg.ext, [-1]]).astype(np.int32)
    return bg, start, np.ones(len(bg.buckets), bool), want


def _push_sweep(c, ext_pad, bg: BucketedGraph, active):
    """One Gauss-Seidel sweep in numpy: changed rows push a dirty bit to
    every neighbor slot, and each tile reads back its own rows' bits."""
    n = bg.n_nodes
    c = c.copy()
    dirty = np.zeros(n + 1, bool)
    changed = np.zeros(len(bg.buckets), np.int32)
    for bi, b in enumerate(bg.buckets):
        if not active[bi]:
            continue
        real = b.node_ids < n
        ids, neigh = b.node_ids[real], b.neigh[real]
        cores = -np.sort(-c[neigh], axis=1)
        e = ext_pad[ids]
        ok = cores >= e[:, None] + np.arange(1, b.width + 1)[None, :]
        est = e + np.cumprod(ok, axis=1).sum(axis=1)
        row_changed = est != c[ids]
        changed[bi] = row_changed.sum()
        dirty[neigh[row_changed]] = True
        c[ids] = est
    dirty[n] = False  # pad slots
    dirty_next = np.array([dirty[b.node_ids[b.node_ids < n]].any()
                           for b in bg.buckets])
    return c, changed, dirty_next


@pytest.mark.parametrize("case", CASES)
def test_sweep_matches_numpy_push_reference(case):
    bg, start, active, want = _case(case)
    if case == "uniform":
        assert len(bg.buckets) > 64  # three mask words
    ext_pad = np.concatenate([bg.ext, [0]]).astype(np.int32)
    cand = max(1, hindex_of_sequence(bg.degrees.astype(np.int64) + bg.ext))
    buckets = decompose_mod._device_buckets(bg)
    adj = bg.bucket_adjacency()
    c, c_ref = jnp.asarray(start), start
    skipped = 0
    for _ in range(100):
        c, changed, dirty_next = decompose_mod._sweep(
            c, jnp.asarray(ext_pad), buckets, jnp.asarray(active),
            op="sorted", cand=cand)
        c_ref, changed_ref, dirty_ref = _push_sweep(c_ref, ext_pad, bg, active)
        np.testing.assert_array_equal(np.asarray(c), c_ref)
        np.testing.assert_array_equal(np.asarray(changed), changed_ref)
        np.testing.assert_array_equal(np.asarray(dirty_next), dirty_ref)
        if changed_ref.sum() == 0:
            break
        skipped += int((~dirty_ref).sum())
        active = dirty_ref & adj[changed_ref > 0].any(axis=0)
    else:
        pytest.fail("no fixed point in 100 sweeps")
    assert skipped > 0  # the dirty bits left some tile out
    np.testing.assert_array_equal(c_ref[:-1][bg.degrees > 0],
                                  want[bg.degrees > 0])


def _unique_adjacency(bg: BucketedGraph) -> np.ndarray:
    """The bucket adjacency as bucketize derived it before the masks."""
    nb = len(bg.buckets)
    owner = bg.node_bucket_map()
    adj = np.zeros((nb, nb), dtype=bool)
    np.fill_diagonal(adj, True)
    for bi, b in enumerate(bg.buckets):
        touched = np.unique(owner[b.neigh.ravel()])
        adj[bi, touched[touched >= 0]] = True
    return adj | adj.T


@pytest.mark.parametrize("case", CASES[:4])
def test_bucket_adjacency_from_masks_equals_unique_derivation(case):
    bg = _case(case)[0]
    np.testing.assert_array_equal(bg.bucket_adj, _unique_adjacency(bg))


def test_hand_built_graph_derives_its_masks():
    bg = bucketize(_graph(), max_bucket_rows=16)
    bare = BucketedGraph(n_nodes=bg.n_nodes, buckets=bg.buckets, ext=bg.ext,
                         degrees=bg.degrees)
    derived = bare.row_tile_masks()
    nb = len(bg.buckets)
    owner = bg.node_bucket_map()
    for b, mask, recorded in zip(bg.buckets, derived, bg.tile_masks):
        assert mask.dtype == np.uint32
        assert mask.shape == (b.n_rows, tile_mask_words(nb)) and nb > 64
        np.testing.assert_array_equal(mask, recorded)
        for r in range(0, b.n_rows, 7):
            want = np.zeros(nb, bool)
            tiles = owner[b.neigh[r]]
            want[tiles[tiles >= 0]] = True
            np.testing.assert_array_equal(tile_mask_bits(mask[r], nb), want)


def test_sweep_program_scatters_no_slots():
    """With the frontier on, the only scatter of each tile is its row write
    of the int32 estimates: none takes ``[rows, width]`` slots."""
    bg = bucketize(_graph(), max_bucket_rows=64)
    buckets = decompose_mod._device_buckets(bg)
    n = bg.n_nodes
    text = decompose_mod._sweep.lower(
        jnp.zeros(n + 1, jnp.int32), jnp.zeros(n + 1, jnp.int32), buckets,
        jnp.ones(len(buckets), bool), op="sorted", cand=8,
    ).compile().as_text()
    scatters = re.findall(r"= (\S+) scatter\(", text)
    assert len(scatters) == len(bg.buckets)
    assert all(s.startswith(f"s32[{n + 1}]") for s in scatters)
