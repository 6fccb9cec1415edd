"""Pallas TPU kernel: fused gather + h-index + dirty-bit push per row tile.

The unfused sweep (``core.decompose._sweep``) issues several dispatches per
bucket — an O(rows*width) gather, the h-index, a changed-row compare, then a
``[rows, width]`` scatter-max to push dirty bits — and every intermediate
round-trips through HBM. This kernel does all of it in one pass over the
neighbor tile while it is resident in VMEM:

  * **gather**: the full estimate vector ``c`` ([n+1], sentinel slot last)
    is an input block; neighbor estimates are gathered in-kernel, so the
    ``[tile_n, width]`` gathered matrix is never materialized to HBM;
  * **h-index**: the same sort-free suffix-count form as the standalone
    hindex kernel (candidate window ``cand``, static ``cand_chunk`` chunks,
    chunks above the tile's current-estimate max predicated off);
  * **changed + push**: ``est != cur`` is computed on the spot and pushed to
    every neighbor of a changed row as a segment-max over the flattened
    neighbor ids (the segment-reduce formulation of the dirty-bit push —
    one reduction keyed by neighbor id instead of a scatter-max of a
    broadcast ``[rows, width]`` byte matrix). The per-node dirty vector is
    an output block revisited by every grid step: zero-initialised on step
    0 (``pl.when``) and max-accumulated afterwards.

The kernel runs only in interpret mode, on the CPU backend, where block
loads are plain XLA slices and the kernel doubles as the executable spec.
Mosaic refuses it for the TPU (``NotImplementedError: Only 2D gather is
supported``, from the in-kernel gathers ``c[neigh]`` and ``ext_pad[ids]``),
so :func:`repro.kernels.fused.ops.require_fused_platform` stops it there.
A TPU version keeps the estimate vector in ANY/HBM with DMA'd gathers
instead of whole-``[n+1]`` VMEM blocks. The estimate vector may be int16
(the opt-in halved-wire mode — see ``core.decompose``); all arithmetic is
widened to int32 in-kernel, only the resident state is narrow.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _fused_sweep_kernel(
    c_ref, ext_pad_ref, ids_ref, neigh_ref,
    est_ref, changed_ref, dirty_ref,
    *, cand: int, cand_chunk: int, track_dirty: bool,
):
    """One row tile: gather -> suffix-count h-index -> dirty push."""
    c = c_ref[...]            # [n+1] estimates (int16 or int32), slot n = -1
    ids = ids_ref[...]        # [tile_n, 1] int32 node ids (sentinel-padded)
    neigh = neigh_ref[...]    # [tile_n, width] int32 neighbor ids
    n1 = c.shape[0]
    sentinel = n1 - 1
    tile_n, width = neigh.shape

    # Fused gathers: neighbor estimates + this tile's ext/cur rows. Pad
    # rows (ids == sentinel) gather the -1 sentinel row and ext 0.
    x = c[neigh].astype(jnp.int32)                    # [tile_n, width]
    ext = ext_pad_ref[...][ids]                       # [tile_n, 1] int32
    cur = c[ids].astype(jnp.int32)                    # [tile_n, 1]

    # Suffix-count h-index over the candidate window (same schedule as
    # kernels.hindex: chunks above the tile's current max are dead work
    # because estimates only decrease).
    cur_max = jnp.max(cur - ext)
    best = jnp.zeros((tile_n, 1), dtype=jnp.int32)
    for lo in range(0, cand, cand_chunk):
        w = min(cand_chunk, cand - lo)
        i = lo + 1 + jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)

        def chunk(best, i=i, lo=lo, w=w):
            thr = ext + i
            cnt = jnp.sum(
                (x[:, :, None] >= thr[:, None, :]).astype(jnp.int32), axis=1
            )
            feasible = cnt >= i
            chunk_best = jnp.max(jnp.where(feasible, i, 0), axis=1, keepdims=True)
            return jnp.maximum(best, chunk_best)

        best = jax.lax.cond(lo < cur_max, chunk, lambda b: b, best)
    est = ext + best                                   # [tile_n, 1]
    row_changed = (est != cur) & (ids != sentinel)     # [tile_n, 1]

    est_ref[...] = est
    changed_ref[...] = row_changed.astype(jnp.int32)

    @pl.when(pl.program_id(0) == 0)
    def _init_dirty():
        dirty_ref[...] = jnp.zeros_like(dirty_ref)

    if track_dirty:
        # Segment-reduce push: max the changed flag into each neighbor's
        # slot, keyed by flattened neighbor id. Sentinel slots absorb the
        # pad traffic (never read back).
        flat_ids = neigh.reshape(-1)
        flat_val = jnp.broadcast_to(row_changed, neigh.shape).reshape(-1)
        contrib = jax.ops.segment_max(
            flat_val.astype(jnp.int8), flat_ids, num_segments=n1
        )
        dirty_ref[...] = jnp.maximum(dirty_ref[...], contrib)


def fused_sweep_pallas(
    c: jax.Array,
    ext_pad: jax.Array,
    ids: jax.Array,
    neigh: jax.Array,
    *,
    cand: int,
    tile_n: int = 8,
    cand_chunk: int = 128,
    track_dirty: bool = True,
    interpret: Optional[bool] = None,
):
    """Fused sweep over one bucket tile set.

    Args:
      c: [n+1] current estimates (int16 or int32), slot n pinned to -1.
      ext_pad: [n+1] int32 external information, slot n = 0.
      ids: [rows] int32 node ids, pad rows = n (the sentinel).
      neigh: [rows, width] int32 neighbor ids, pad slots = n.
      cand: candidate window (clamped to the bucket width).
    Returns:
      ``(est [rows, 1] int32, changed [rows, 1] int32, dirty [n+1] int8)``.
      ``dirty`` is all-zero when ``track_dirty=False``.
    """
    rows, width = neigh.shape
    if rows % tile_n != 0:
        raise ValueError(f"rows {rows} not a multiple of tile_n {tile_n}")
    n1 = c.shape[0]
    cand = int(min(max(cand, 1), width))
    ids2 = ids.reshape(rows, 1).astype(jnp.int32)

    kernel = functools.partial(
        _fused_sweep_kernel, cand=cand, cand_chunk=cand_chunk,
        track_dirty=track_dirty,
    )
    grid = (rows // tile_n,)
    est, changed, dirty = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n1,), lambda g: (0,)),        # full c
            pl.BlockSpec((n1,), lambda g: (0,)),        # full ext_pad
            pl.BlockSpec((tile_n, 1), lambda g: (g, 0)),
            pl.BlockSpec((tile_n, width), lambda g: (g, 0)),
        ],
        out_specs=(
            pl.BlockSpec((tile_n, 1), lambda g: (g, 0)),
            pl.BlockSpec((tile_n, 1), lambda g: (g, 0)),
            pl.BlockSpec((n1,), lambda g: (0,)),        # full dirty, accumulated
        ),
        out_shape=(
            jax.ShapeDtypeStruct((rows, 1), jnp.int32),
            jax.ShapeDtypeStruct((rows, 1), jnp.int32),
            jax.ShapeDtypeStruct((n1,), jnp.int8),
        ),
        interpret=resolve_interpret(interpret),
    )(c, ext_pad.astype(jnp.int32), ids2, neigh.astype(jnp.int32))
    return est, changed, dirty


def fused_vmem_bytes_estimate(
    tile_n: int, width: int, cand_chunk: int, n_state: int, wire_bytes: int = 4
) -> int:
    """Static VMEM footprint estimate for one fused grid step.

    The tile-dependent terms mirror the hindex kernel (neighbor block,
    gathered block, compare intermediate); the state terms (``c`` +
    ``dirty`` blocks, ``n_state`` slots each) are tile-independent — on TPU
    they would stay in ANY/HBM with DMA'd gathers, so ops.py sizes the tile
    from the tile-dependent terms only but reports the full estimate.
    """
    block = tile_n * width * 4          # neighbor ids
    gathered = tile_n * width * 4       # in-kernel gathered estimates
    compare = tile_n * width * cand_chunk
    partials = tile_n * cand_chunk * 4 * 2
    push = tile_n * width * 1           # flattened segment values
    state = n_state * (wire_bytes + 1)  # c + dirty blocks
    return block + gathered + compare + partials + push + state
