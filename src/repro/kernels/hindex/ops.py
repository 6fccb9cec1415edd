"""Jit'd wrapper around the hindex Pallas kernel.

Chooses tile sizes from a VMEM budget, pads rows to the tile multiple, and
exposes a drop-in replacement for :func:`repro.core.hindex.hindex_count`
(the ``op="kernel"`` path of the decompose engines).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.hindex.hindex import hindex_pallas, vmem_bytes_estimate

# Conservative per-core VMEM working budget (v5e has 128 MiB VMEM; leave
# headroom for Mosaic's own buffers and double buffering).
_VMEM_BUDGET = 8 * 1024 * 1024
# Neighbor slots per grid step; wider rows take several steps.
_SLOT_CHUNK = 512


def pick_tile_n(width: int, cand: int = 0, cand_chunk: int = 128,
                budget: int = _VMEM_BUDGET) -> int:
    """Largest power-of-two row tile whose footprint fits ``budget``; the
    kernel holds at most ``_SLOT_CHUNK`` slots of a row at once."""
    chunk = min(width, _SLOT_CHUNK)
    tile_n = 256
    while tile_n > 8 and vmem_bytes_estimate(
            tile_n, chunk, cand_chunk, cand) > budget:
        tile_n //= 2
    return tile_n


@partial(jax.jit, static_argnames=("cand", "interpret"))
def hindex_op(
    neigh_cores: jax.Array,
    ext: jax.Array,
    cur: jax.Array,
    *,
    cand: int,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """H-index for one padded bucket. Pads rows to the tile multiple.

    Args:
      neigh_cores: [n, w] int32, padded slots -1.
      ext: [n] int32 external information.
      cur: [n] int32 current estimates (kernel predication hint).
      cand: candidate window (degeneracy bound U; >= k_max for exactness).
      interpret: ``None`` = interpret only on the CPU backend.
    """
    n, w = neigh_cores.shape
    tile_n = pick_tile_n(w, min(cand, w))
    n_pad = (-n) % tile_n
    if n_pad:
        neigh_cores = jnp.pad(neigh_cores, ((0, n_pad), (0, 0)), constant_values=-1)
        ext = jnp.pad(ext, (0, n_pad))
        cur = jnp.pad(cur, (0, n_pad))
    out = hindex_pallas(
        neigh_cores, ext, cur, cand=cand, tile_n=tile_n,
        slot_chunk=_SLOT_CHUNK, interpret=interpret,
    )
    return out[:n]
