"""Jit'd wrapper for the partial-counts kernel (row padding + tiling)."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.counts.counts import partial_counts_pallas


@partial(jax.jit, static_argnames=("cand", "interpret"))
def partial_counts_op(neigh: jax.Array, ext: jax.Array, *, cand: int,
                      interpret: Optional[bool] = None) -> jax.Array:
    n, w = neigh.shape
    tile_n = 8
    pad = (-n) % tile_n
    if pad:
        neigh = jnp.pad(neigh, ((0, pad), (0, 0)), constant_values=-1)
        ext = jnp.pad(ext, (0, pad))
    out = partial_counts_pallas(neigh, ext, cand=cand, interpret=interpret)
    return out[:n]
