"""Plain reference coreness, independent of the program under test.

Peels the graph level by level: at level ``k`` every remaining vertex whose
remaining degree is at most ``k`` has coreness ``k``; removing it lowers its
remaining neighbors' degrees, which may bring them down to ``k`` too. The
level rises once nothing of degree ``<= k`` is left. That is the definition
of the k-core (the largest subgraph of minimum degree ``k``) applied
directly, in numpy, one frontier at a time. It reads only the benchmark's
own CSR and imports nothing of the program.
"""
from __future__ import annotations

import numpy as np


def _neighbors(indptr: np.ndarray, indices: np.ndarray,
               rows: np.ndarray) -> np.ndarray:
    """Concatenated neighbor lists of ``rows``."""
    starts = indptr[rows]
    lens = indptr[rows + 1] - starts
    total = int(lens.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int64)
    offsets = np.repeat(starts - np.cumsum(lens) + lens, lens)
    return indices[offsets + np.arange(total)].astype(np.int64)


def coreness(indptr: np.ndarray, indices: np.ndarray) -> np.ndarray:
    """Exact coreness of every vertex, ``[n]`` int32."""
    n = indptr.size - 1
    deg = np.diff(indptr).astype(np.int64)
    core = np.full(n, -1, dtype=np.int64)
    alive = np.ones(n, dtype=bool)
    left = n
    k = 0
    while left:
        k = max(k, int(deg[alive].min()))
        frontier = np.nonzero(alive & (deg <= k))[0]
        while frontier.size:
            core[frontier] = k
            alive[frontier] = False
            left -= frontier.size
            nbrs = _neighbors(indptr, indices, frontier)
            nbrs = nbrs[alive[nbrs]]
            hit, count = np.unique(nbrs, return_counts=True)
            deg[hit] -= count
            frontier = hit[deg[hit] <= k]
    return core.astype(np.int32)
