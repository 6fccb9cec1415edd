"""Pallas TPU kernel: blocked h-index with external information.

This is the compute hot-spot of the conquer step (paper Algorithm 2): per
graph node, the largest ``i`` such that at least ``i`` neighbors hold an
estimate ``>= ext + i``. The paper's Scala implementation sorts each
neighbor list per iteration; sorting is hostile to the TPU VPU, so the
kernel uses the sort-free suffix-count form — dense compare-and-reduce over
a ``[tile_n, width]`` VMEM block against a candidate window, which maps onto
8x128 vector registers with no data-dependent control flow.

Tiling:
  * a 2-D grid: node tiles of ``tile_n`` rows x neighbor-slot chunks of
    ``slot_chunk`` slots. Suffix counts accumulate in a VMEM scratch over
    the slot chunks, and the last chunk picks the estimate, so the VMEM
    footprint and the kernel body are independent of the bucket width
    (hub buckets are 2^17+ slots wide);
  * the candidate axis is walked in ``cand_chunk``-wide chunks by a
    ``fori_loop`` (one loop body, whatever the candidate window), so
    compile time does not grow with ``cand`` either;
  * chunks whose candidates all exceed the tile's current-estimate maximum
    are predicated off with ``pl.when`` — as the fixed point converges,
    estimates shrink and most chunks are skipped (dynamic work saving with a
    static schedule).

The candidate window ``cand`` is the degeneracy bound U (h-index of the
degree sequence, >= k_max), not the bucket width — exactness is preserved
(estimates stay upper bounds; see DESIGN.md) while the compare volume drops
from O(w^2) to O(w * U).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _hindex_kernel(neigh_ref, ext_ref, cur_ref, out_ref, acc_ref, *,
                   cand: int, cand_chunk: int):
    """One (node tile, slot chunk) step of out[n] = ext[n] + best candidate."""
    x = neigh_ref[...]  # [tile_n, slot_chunk] int32, -1 padded
    ext = ext_ref[...]  # [tile_n, 1] int32
    # Estimates never exceed the tile's current max (monotone decrease), so
    # candidate chunks above it are dead work (candidates are offsets
    # i = c - ext).
    cur_max = jnp.max(cur_ref[...] - ext)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def chunk(j, carry):
        lo = pl.multiple_of(j * cand_chunk, cand_chunk)

        @pl.when(lo < cur_max)
        def _count():
            i = lo + 1 + jax.lax.broadcasted_iota(jnp.int32, (1, cand_chunk), 1)
            thr = ext + i  # [tile_n, cand_chunk]
            # [tile_n, slot_chunk, cand_chunk] compare, reduce over slots.
            cnt = jnp.sum(
                (x[:, :, None] >= thr[:, None, :]).astype(jnp.int32), axis=1
            )
            acc_ref[:, pl.ds(lo, cand_chunk)] += cnt

        return carry

    jax.lax.fori_loop(0, acc_ref.shape[1] // cand_chunk, chunk, 0)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _pick():
        i = 1 + jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 1)
        feasible = (acc_ref[...] >= i) & (i <= cand)
        out_ref[...] = ext + jnp.max(
            jnp.where(feasible, i, 0), axis=1, keepdims=True
        )


def hindex_pallas(
    neigh_cores: jax.Array,
    ext: jax.Array,
    cur: jax.Array,
    *,
    cand: int,
    tile_n: int = 8,
    cand_chunk: int = 128,
    slot_chunk: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Blocked h-index. ``neigh_cores``: [n, w] int32 (-1 pad); ``ext``,
    ``cur``: [n] int32. Returns [n] int32 new estimates.

    Rows wider than ``slot_chunk`` are padded with -1 slots to a multiple
    of it. ``interpret=None`` interprets the kernel body on the CPU backend
    and compiles it with Mosaic on a TPU
    (:func:`repro.kernels.resolve_interpret`); a bool forces either.
    """
    n, w = neigh_cores.shape
    if n % tile_n != 0:
        raise ValueError(f"rows {n} not a multiple of tile_n {tile_n}")
    cand = int(min(max(cand, 1), w))
    cand_pad = -(-cand // cand_chunk) * cand_chunk
    neigh = neigh_cores.astype(jnp.int32)
    if w > slot_chunk:
        neigh = jnp.pad(neigh, ((0, 0), (0, (-w) % slot_chunk)),
                        constant_values=-1)
    else:
        slot_chunk = w
    ext2 = ext.reshape(n, 1).astype(jnp.int32)
    cur2 = cur.reshape(n, 1).astype(jnp.int32)

    kernel = functools.partial(_hindex_kernel, cand=cand, cand_chunk=cand_chunk)
    grid = (n // tile_n, neigh.shape[1] // slot_chunk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, slot_chunk), lambda g, k: (g, k)),
            pl.BlockSpec((tile_n, 1), lambda g, k: (g, 0)),
            pl.BlockSpec((tile_n, 1), lambda g, k: (g, 0)),
        ],
        out_specs=pl.BlockSpec((tile_n, 1), lambda g, k: (g, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((tile_n, cand_pad), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(neigh, ext2, cur2)
    return out.reshape(n)


def vmem_bytes_estimate(tile_n: int, width: int, cand_chunk: int,
                        cand: int = 0) -> int:
    """Static VMEM footprint estimate used by ops.py to pick tile_n.

    ``width`` is the slot chunk the kernel holds at once, not the bucket
    width."""
    block = tile_n * width * 4  # neighbor tile
    compare = tile_n * width * cand_chunk  # bool intermediate
    partial = tile_n * cand_chunk * 4 * 2
    acc = tile_n * (-(-cand // cand_chunk) * cand_chunk) * 4  # count scratch
    return block + compare + partial + acc
