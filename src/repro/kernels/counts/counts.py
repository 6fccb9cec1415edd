"""Pallas TPU kernel: per-shard h-index partial counts.

The distributed engine splits each node's neighbor slots over the "model"
axis; every shard computes suffix counts over its local slots and the
engine psums them (core/distributed.py). This kernel is that local compute
with explicit VMEM tiling: grid over (node tiles x candidate tiles x
neighbor-slot chunks), the counts accumulating in the output block across
the slot chunks, so the compare footprint ``tile_n x slot_chunk x tile_c``
and the kernel body stay the same whatever the bucket width.

Compared to the fused hindex kernel (kernels/hindex), the output here is
the [n, cand] count matrix — the collective payload — rather than the
final estimate, because feasibility can only be decided after the psum.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _counts_kernel(neigh_ref, ext_ref, out_ref):
    x = neigh_ref[...]  # [tile_n, slot_chunk]
    ext = ext_ref[...]  # [tile_n, 1]
    tile_c = out_ref.shape[1]
    c0 = pl.program_id(1) * tile_c
    i = c0 + 1 + jax.lax.broadcasted_iota(jnp.int32, (1, tile_c), 1)
    thr = ext + i  # [tile_n, tile_c]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.sum(
        (x[:, :, None] >= thr[:, None, :]).astype(jnp.int32), axis=1
    )


def partial_counts_pallas(
    neigh: jax.Array,
    ext: jax.Array,
    *,
    cand: int,
    tile_n: int = 8,
    tile_c: int = 128,
    slot_chunk: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """neigh: [n, w_local] int32 (-1 pad); ext: [n] -> [n, cand] int32.

    Rows wider than ``slot_chunk`` are padded with -1 slots to a multiple
    of it. ``interpret=None`` interprets only on the CPU backend."""
    n, w = neigh.shape
    if n % tile_n != 0:
        raise ValueError(f"rows {n} not a multiple of tile_n {tile_n}")
    cand_pad = -(-cand // tile_c) * tile_c
    neigh = neigh.astype(jnp.int32)
    if w > slot_chunk:
        neigh = jnp.pad(neigh, ((0, 0), (0, (-w) % slot_chunk)),
                        constant_values=-1)
    else:
        slot_chunk = w
    ext2 = ext.reshape(n, 1).astype(jnp.int32)
    out = pl.pallas_call(
        _counts_kernel,
        grid=(n // tile_n, cand_pad // tile_c, neigh.shape[1] // slot_chunk),
        in_specs=[
            pl.BlockSpec((tile_n, slot_chunk), lambda gn, gc, gk: (gn, gk)),
            pl.BlockSpec((tile_n, 1), lambda gn, gc, gk: (gn, 0)),
        ],
        out_specs=pl.BlockSpec((tile_n, tile_c), lambda gn, gc, gk: (gn, gc)),
        out_shape=jax.ShapeDtypeStruct((n, cand_pad), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(neigh, ext2)
    return out[:, :cand]
