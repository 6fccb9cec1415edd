"""Jit'd launch glue for the fused sweep kernel.

Chooses tile sizes from the VMEM budget (tile-dependent terms only — the
resident estimate/dirty vectors are tile-independent), pads rows to the
tile multiple with sentinel ids, and exposes the bucket-level op the
``engine="fused"`` decompose path dispatches per bucket / per compacted
width group.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.fused.fused import fused_sweep_pallas, fused_vmem_bytes_estimate

# Same conservative working budget as kernels.hindex.ops.
_VMEM_BUDGET = 8 * 1024 * 1024

# What Mosaic answers when asked to compile the kernel for a TPU.
MOSAIC_REFUSAL = "NotImplementedError: Only 2D gather is supported"


def require_fused_platform() -> None:
    """Refuse the fused kernel on any backend but the CPU.

    Mosaic does not lower the kernel's in-kernel gathers (``c[neigh]``,
    ``ext_pad[ids]``), and running the interpreter on an accelerator would
    hide that. Callers check before the first sweep.
    """
    backend = jax.default_backend()
    if backend != "cpu":
        raise NotImplementedError(
            f"engine='fused' (and int16, which needs it) cannot run on the "
            f"{backend} backend: Mosaic refuses the fused sweep kernel "
            f"({MOSAIC_REFUSAL}, from its in-kernel gathers). Use "
            f"engine='kernel' or 'sorted'."
        )


def pick_fused_tile_n(width: int, cand_chunk: int = 128,
                      budget: int = _VMEM_BUDGET) -> int:
    """Largest power-of-two tile whose tile-DEPENDENT footprint fits."""
    tile_n = 256
    while tile_n > 8 and fused_vmem_bytes_estimate(
            tile_n, width, cand_chunk, n_state=0) > budget:
        tile_n //= 2
    return tile_n


@partial(jax.jit, static_argnames=("cand", "track_dirty", "interpret"))
def fused_sweep_op(
    c: jax.Array,
    ext_pad: jax.Array,
    ids: jax.Array,
    neigh: jax.Array,
    *,
    cand: int,
    track_dirty: bool = True,
    interpret: Optional[bool] = None,
):
    """Fused gather + h-index + dirty push for one bucket.

    Args:
      c: [n+1] current estimates (int16/int32), slot n = -1 sentinel.
      ext_pad: [n+1] int32 ext, slot n = 0.
      ids: [rows] int32 node ids (pad rows = n).
      neigh: [rows, width] int32 neighbor ids (pad slots = n).
      cand: candidate window (degeneracy bound; clamped to width).
    Returns:
      ``(est [rows] int32, row_changed [rows] int32, dirty [n+1] int8)``.
    """
    rows, width = neigh.shape
    sentinel = c.shape[0] - 1
    tile_n = pick_fused_tile_n(width)
    n_pad = (-rows) % tile_n
    if n_pad:
        # Sentinel-padded rows gather -1 estimates, produce est 0 and
        # row_changed 0, and push nothing.
        ids = jnp.pad(ids, (0, n_pad), constant_values=sentinel)
        neigh = jnp.pad(neigh, ((0, n_pad), (0, 0)), constant_values=sentinel)
    est, changed, dirty = fused_sweep_pallas(
        c, ext_pad, ids, neigh, cand=cand, tile_n=tile_n,
        track_dirty=track_dirty, interpret=interpret,
    )
    return est[:rows, 0], changed[:rows, 0], dirty
