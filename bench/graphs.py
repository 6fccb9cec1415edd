"""Graph generators of the benchmark's configurations, kept with the yardstick.

Each configuration names a generator and its parameters. The edge structure
is drawn once from the configuration's fixed ``structure_seed`` (the GAP
suite builds each of its graphs from one fixed seed); the run's ``--seed``
then draws a random relabeling of the vertices, as the Graph500
specification permutes vertex labels. So every seed gives an isomorphic
graph in another vertex order: the same degree classes, tile shapes and
part sizes, and therefore the same compiled programs and the same work,
while the inputs the program sees still differ from seed to seed.

Both generators are copies of ``repro.graph.generators`` (R-MAT and
G(n, m)); the CSR build is written here, so the data the reference reads
is made by the benchmark alone.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class CSR:
    """Undirected simple graph: sorted neighbor rows, both directions."""

    indptr: np.ndarray   # [n + 1] int64
    indices: np.ndarray  # [2m] int32
    n: int

    @property
    def m(self) -> int:
        """Undirected edges."""
        return int(self.indices.size // 2)

    @property
    def degrees(self) -> np.ndarray:
        return np.diff(self.indptr)


def kron_edges(scale: int, edge_factor: int, a: float, b: float, c: float,
               rng: np.random.Generator):
    """Graph500 Kronecker (R-MAT) edge list: ``edge_factor * 2**scale``
    pairs, one quadrant choice per bit."""
    m = (1 << scale) * edge_factor
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for bit in range(scale):
        r = rng.random(m)
        src |= (r >= (a + b)).astype(np.int64) << bit
        dst |= (((r >= a) & (r < a + b)) | (r >= (a + b + c))).astype(
            np.int64) << bit
    return src, dst


def urand_edges(scale: int, edge_factor: int, rng: np.random.Generator):
    """Uniform random G(n, m): ``edge_factor * 2**scale`` pairs drawn
    uniformly over ``2**scale`` vertices."""
    n = 1 << scale
    m = n * edge_factor
    return (rng.integers(0, n, size=m, dtype=np.int64),
            rng.integers(0, n, size=m, dtype=np.int64))


def undirected_pairs(src: np.ndarray, dst: np.ndarray, n: int) -> np.ndarray:
    """Keys ``lo * n + hi`` of the distinct undirected non-loop edges."""
    lo, hi = np.minimum(src, dst), np.maximum(src, dst)
    keep = lo != hi
    return np.unique(lo[keep] * n + hi[keep])


def csr_from_pairs(keys: np.ndarray, n: int) -> CSR:
    """Symmetric CSR with rows sorted by neighbor id."""
    lo, hi = np.divmod(keys, n)
    both = np.sort(np.concatenate([lo * n + hi, hi * n + lo]))
    rows, cols = np.divmod(both, n)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    return CSR(indptr=indptr, indices=cols.astype(np.int32), n=n)


def make_graph(config: dict, seed: int) -> CSR:
    """The configuration's graph with its vertices relabeled by ``seed``."""
    scale, ef = int(config["scale"]), int(config["edge_factor"])
    n = 1 << scale
    rng = np.random.default_rng(int(config["structure_seed"]))
    kind = config["generator"]
    if kind == "kron":
        src, dst = kron_edges(scale, ef, float(config["a"]), float(config["b"]),
                              float(config["c"]), rng)
    elif kind == "urand":
        src, dst = urand_edges(scale, ef, rng)
    else:
        raise ValueError(f"unknown generator {kind!r}")
    keys = undirected_pairs(src, dst, n)
    del src, dst
    perm = np.random.default_rng(int(seed)).permutation(n).astype(np.int64)
    lo, hi = np.divmod(keys, n)
    return csr_from_pairs(perm[lo] * n + perm[hi], n)
