"""Roofline cost model for the k-core conquer sweep.

``flops_model.py`` models the LM workloads the roofline harness was built
for; this module is its k-core counterpart: per-bucket HBM bytes and
compare-FLOPs for one sweep, in both the unfused multi-dispatch form
(gather materialized, dirty bits OR-reduced from the changed rows' tile
masks) and the fused single-kernel form (``kernels.fused`` — the neighbor
tile is read once, no gathered intermediate ever hits HBM, dirty bits are
pushed per slot). ``core.decompose`` accumulates these
per live sweep from the active-frontier mask, so a run reports modeled
achieved-vs-roofline bandwidth next to its wall time (fig17), and the
opt-in int16 estimate mode shows up as a measured bytes-moved reduction
(``wire_bytes=2``).

The model counts traffic, not cache luck: every operand is charged one trip
at its natural width. FLOPs are the suffix-count compares (one op per
neighbor-slot x candidate), the term that dominates Algorithm 2.
"""
from __future__ import annotations

from typing import Iterable, Sequence, Tuple

from repro.roofline import hw


def sweep_tile_cost(
    rows: int,
    width: int,
    cand: int,
    *,
    wire_bytes: int = 4,
    fused: bool = True,
    track_dirty: bool = True,
    mask_words: int = 1,
) -> Tuple[int, int]:
    """(HBM bytes, compare FLOPs) for one ``[rows, width]`` bucket sweep.

    ``wire_bytes`` is the estimate dtype width (4, or 2 in int16 mode):
    the gathered neighbor estimates and the current/new estimate rows move
    at that width; ids/ext stay 4-byte. ``cand`` is clamped to ``width``
    exactly as the kernels clamp it. ``mask_words`` is the uint32 words of
    a row's tile mask, which the unfused sweep reads for its dirty bits.
    """
    cand = max(1, min(int(cand), int(width)))
    neigh = rows * width * 4                 # neighbor-id tile, read once
    gather = rows * width * wire_bytes       # gathered estimates (c reads)
    row_io = rows * (4 + 4 + 2 * wire_bytes + 4)  # ids + ext + cur/est + changed
    nbytes = neigh + gather + row_io
    if not fused:
        # Multi-dispatch sweep: the [rows, width] gathered matrix is
        # materialized (store + re-load by the h-index); the dirty bits
        # OR-reduce the rows' tile masks.
        nbytes += 2 * rows * width * 4
        if track_dirty:
            nbytes += rows * mask_words * 4
    elif track_dirty:
        nbytes += rows * width * 1  # the kernel's int8 per-slot dirty push
    flops = rows * width * cand + rows * cand  # compares + feasibility
    return int(nbytes), int(flops)


def sweep_cost(
    shapes: Iterable[Sequence[int]],
    cand: int,
    *,
    wire_bytes: int = 4,
    fused: bool = True,
    track_dirty: bool = True,
    mask_words: int = 1,
) -> Tuple[int, int]:
    """Sum :func:`sweep_tile_cost` over ``(rows, width)`` bucket shapes."""
    tb = tf = 0
    for rows, width in shapes:
        b, f = sweep_tile_cost(
            rows, width, cand, wire_bytes=wire_bytes, fused=fused,
            track_dirty=track_dirty, mask_words=mask_words,
        )
        tb += b
        tf += f
    return tb, tf


def roofline_time_s(
    nbytes: int,
    flops: int,
    *,
    hbm_bw: float = hw.HBM_BW,
    peak_flops: float = hw.PEAK_FLOPS_BF16,
) -> float:
    """Roofline lower bound for one sweep on the target chip."""
    return max(nbytes / hbm_bw, flops / peak_flops)


def achieved_bw_fraction(
    nbytes: int, wall_s: float, *, hbm_bw: float = hw.HBM_BW
) -> float:
    """Achieved fraction of target-chip HBM bandwidth for measured wall."""
    if wall_s <= 0:
        return 0.0
    return (nbytes / wall_s) / hbm_bw
