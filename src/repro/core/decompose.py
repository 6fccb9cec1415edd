"""Single-device k-core decomposition engine (jit).

This is the conquer step's compute engine: the h-index fixed point of paper
Algorithms 1/2 over a :class:`~repro.graph.structs.BucketedGraph` part.
Estimates start at ``deg + ext`` and monotonically decrease to the exact
coreness (paper Corollary 2 / Montresor et al.).

The state vector ``c`` has ``n + 1`` entries: slot ``n`` is the ``-1``
sentinel that padded neighbor slots gather from, so padding never needs a
mask in the inner loop. Per iteration, per degree-bucket:

    gathered = c[bucket.neigh]                  # [nb, width]
    new      = hindex(gathered, ext[bucket])    # Algorithm 2
    c        = c.at[bucket.node_ids].set(new)   # pad rows hit slot n

Four interchangeable sweep engines (``op=``):
  * ``"sorted"`` — descending sort + prefix scan (paper's literal loop).
  * ``"count"``  — sort-free suffix counts (pure jnp).
  * ``"kernel"`` — the Pallas TPU h-index kernel, with the
    degeneracy-bounded candidate window; compiled by Mosaic on a TPU,
    interpreted on the CPU backend.
  * ``"fused"``  — the fused Pallas sweep kernel (``kernels.fused``), CPU
    backend only (Mosaic refuses it; ``decompose`` raises elsewhere):
    gather + h-index + dirty-bit push in ONE kernel per row tile, the
    gathered matrix never materialized. With few tiles each bucket keeps
    its own ``lax.cond``-gated launch (bit-identical trajectory to the
    engines above); past ``fused_compaction_min_tiles`` tiles the cond
    chain is replaced by a dense active-row-index compaction — per sweep,
    the active tiles of each width group are compacted into one launch
    (estimate reads are Jacobi within the group, Gauss-Seidel across
    groups). The fixed point is unique, so final coreness stays
    bit-identical in every mode; per-sweep trajectories are identical
    except under compaction with ``gauss_seidel=True`` when a width group
    holds more than one active tile.

``int16=True`` (fused only) keeps the resident estimate vector int16 for
2x effective memory bandwidth; an overflow guard falls back to int32
whenever any starting estimate (``deg + ext``) reaches ``2**15`` —
estimates only decrease, so below that bound int16 can never wrap. The
result reports the dtype actually used (``est_dtype``).

**Active-frontier sweep scheduling** (Montresor et al.: after the first few
rounds only a small frontier still changes): each sweep returns a per-bucket
changed-count vector plus a per-bucket dirty flag, and the next sweep skips
— behind ``lax.cond``, so the gather and h-index are not executed — every
bucket that is quiescent. Two sound filters compose:

  1. the static ``bucket_adj`` bitmap (recorded once at bucketize time):
     a bucket none of whose adjacent buckets changed cannot change;
  2. dirty bits from the rows that changed: each row carries a static
     tile mask (the buckets its neighbors live in), and the changed rows'
     masks are OR-reduced on device, so a bucket none of whose OWN rows has
     a changed neighbor cannot change. This is the row-exact refinement
     that makes skipping effective on power-law graphs, where degree-class
     adjacency is dense.

A node's estimate is a function of its neighbors' estimates only, so both
filters are sound, not heuristic, and the fixed point is bit-identical to
the full-sweep schedule. ``frontier=False`` restores always-full sweeps
(the baseline the benchmarks compare against). Frontier granularity is the
bucket *tile* — bucketize splits degree classes into bounded row-tiles.

The *communication amount* (paper Section 5.4 metric: number of updated
estimates communicated per iteration) is counted on every step; it is the
quantity Figures 8 and 10 plot and what the divide step reduces. The
frontier adds the matching *work* metric: gathered rows per sweep.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hindex import hindex_count, hindex_of_sequence, hindex_sorted
from repro.core.spans import span
from repro.graph.structs import BucketedGraph, tile_mask_words
from repro.roofline.kcore_model import sweep_cost


@dataclasses.dataclass
class DecomposeResult:
    """Outcome of one part decomposition.

    ``coreness`` is always reported in **original**-id order: engines
    running on a reordered layout (``BucketedGraph.perm`` set) gather
    ``coreness[inv_perm]`` before returning, so reordering never leaks.
    """

    coreness: np.ndarray  # [n_nodes] int32
    iterations: int
    comm_amount: int  # total changed estimates across iterations
    comm_per_iter: List[int]
    peak_bytes: int  # device bytes of graph tiles + state
    wall_time_s: float
    # Work metric (frontier scheduling): bucket rows gathered+h-indexed per
    # sweep, and what one always-full sweep would have gathered.
    active_rows_per_iter: List[int] = dataclasses.field(default_factory=list)
    rows_per_full_sweep: int = 0
    # Measured collective traffic (distributed engine): per-device ICI bytes
    # the sweep's collectives actually moved each iteration, from the live
    # frontier mask and the padded device-array shapes — including the
    # frontier's own dirty-bit psum, which the analytic
    # ``sweep_collective_bytes`` model omits. Empty for single-device runs
    # (they issue no collectives).
    collective_bytes_per_iter: List[int] = dataclasses.field(default_factory=list)
    # Modeled HBM traffic / compare-FLOPs per live sweep
    # (roofline.kcore_model, from the active-frontier mask and the engine's
    # fused/unfused dispatch shape) — what fig17 plots against the roofline.
    sweep_bytes_per_iter: List[int] = dataclasses.field(default_factory=list)
    sweep_flops_per_iter: List[int] = dataclasses.field(default_factory=list)
    # Estimate dtype the sweep actually ran with ("int16" only when the
    # opt-in mode passed the overflow guard) and, for op="fused", which
    # dispatch shape ran ("cond" | "compaction").
    est_dtype: str = "int32"
    fused_mode: str = ""

    @property
    def sweep_bytes(self) -> int:
        """Total modeled sweep HBM bytes across all iterations."""
        return int(sum(self.sweep_bytes_per_iter))

    @property
    def sweep_flops(self) -> int:
        """Total modeled sweep compare-FLOPs across all iterations."""
        return int(sum(self.sweep_flops_per_iter))

    @property
    def gathered_rows(self) -> int:
        """Total rows gathered across all sweeps (the work-done counter)."""
        return int(sum(self.active_rows_per_iter))

    @property
    def full_sweep_rows(self) -> int:
        """Rows the always-full-sweep schedule would have gathered."""
        return int(self.rows_per_full_sweep * self.iterations)

    @property
    def collective_bytes(self) -> int:
        """Total measured per-device collective bytes across all sweeps."""
        return int(sum(self.collective_bytes_per_iter))


def _device_buckets(bg: BucketedGraph):
    return [
        (jnp.asarray(b.node_ids), jnp.asarray(b.neigh), jnp.asarray(mask))
        for b, mask in zip(bg.buckets, bg.row_tile_masks())
    ]


def _apply_op(gathered, ext_rows, cur_rows, op: str, cand: int):
    if op == "sorted":
        return hindex_sorted(gathered, ext_rows)
    if op == "count":
        return hindex_count(gathered, ext_rows, cand_chunk=min(256, cand))
    if op == "kernel":
        from repro.kernels.hindex import hindex_op

        return hindex_op(gathered, ext_rows, cur_rows, cand=cand)
    raise ValueError(f"unknown op {op!r}")


@partial(jax.jit, static_argnames=("op", "cand", "frozen_reads", "track_dirty"))
def _sweep(c, ext_pad, buckets, active, op: str = "sorted", cand: int = 1 << 30,
           frozen_reads: bool = False, track_dirty: bool = True):
    """One sweep over the active buckets.

    ``buckets`` holds one ``(node_ids [rows], neigh [rows, width],
    mask [rows, W] uint32)`` tuple per bucket, ``mask`` being the rows'
    tile masks (:meth:`BucketedGraph.row_tile_masks`).

    Returns ``(new_c, changed [n_buckets], dirty_next [n_buckets])``:
    ``changed[i]`` counts rows of bucket ``i`` whose estimate changed (the
    paper's communication amount, per bucket); ``dirty_next[j]`` is True iff
    some row of bucket ``j`` has a neighbor that changed this sweep.
    Adjacency is symmetric, so that is bit ``j`` of the OR of the changed
    rows' tile masks: each bucket ORs its changed rows' masks into a ``[W]``
    word accumulator, ``rows x W`` words and no per-slot work. A node's
    estimate is a function of its neighbors' estimates, so ``dirty_next``
    is exactly the set of buckets that could change next sweep.

    ``active`` is the [n_buckets] bool frontier mask; inactive buckets skip
    gather + h-index at runtime (``lax.cond``) and report 0 changed rows.
    ``track_dirty=False`` (the always-full-sweep baseline) compiles the
    mask OR out entirely and returns an all-False ``dirty_next``.

    ``frozen_reads=False`` is Gauss-Seidel: later buckets read estimates
    already updated this sweep (within-sweep propagation, like the paper's
    in-place parameter-server updates) — strictly fewer iterations.
    ``True`` gives textbook Jacobi (what a pull-based PS round does).
    """
    sentinel = c.shape[0] - 1
    frozen = c
    new_c = c
    n_words = buckets[0][2].shape[1] if buckets else 1
    dirty = jnp.zeros((n_words,), jnp.uint32)  # bit j: tile j has a changed neighbor
    changed_parts = []
    for bi, (node_ids, neigh, mask) in enumerate(buckets):

        def update(nc, dt, node_ids=node_ids, neigh=neigh, mask=mask):
            src = frozen if frozen_reads else nc
            gathered = src[neigh]  # sentinel slot -> -1
            ext_rows = ext_pad[node_ids]
            cur_rows = src[node_ids]
            est = _apply_op(gathered, ext_rows, cur_rows, op, cand)
            # Pad rows (node_ids == sentinel) scatter into slot n, which is
            # re-pinned below, and never count as changed.
            row_changed = (est != cur_rows) & (node_ids != sentinel)
            ch = jnp.sum(row_changed).astype(jnp.int32)
            if track_dirty:
                hit = jnp.where(row_changed[:, None], mask, jnp.uint32(0))
                dt = dt | jax.lax.reduce(hit, np.uint32(0), jax.lax.bitwise_or, (0,))
            nc = nc.at[node_ids].set(est)
            nc = nc.at[-1].set(-1)  # re-pin sentinel
            return nc, dt, ch

        new_c, dirty, ch = jax.lax.cond(
            active[bi], update, lambda nc, dt: (nc, dt, jnp.int32(0)), new_c, dirty
        )
        changed_parts.append(ch)
    changed = (
        jnp.stack(changed_parts) if changed_parts else jnp.zeros((0,), jnp.int32)
    )
    if track_dirty and buckets:
        tile = np.arange(len(buckets))
        bit = (dirty[tile // 32] >> (tile % 32).astype(np.uint32)) & 1
        dirty_next = bit.astype(bool)
    else:
        dirty_next = jnp.zeros((len(buckets),), bool)
    return new_c, changed, dirty_next


@partial(jax.jit, static_argnames=("cand", "frozen_reads", "track_dirty"))
def _sweep_fused(c, ext_pad, buckets, active, cand: int = 1 << 30,
                 frozen_reads: bool = False, track_dirty: bool = True):
    """One fused-engine sweep, cond dispatch (few tiles).

    Same contract and per-bucket sequencing as :func:`_sweep`, but each
    bucket's gather + h-index + dirty push is one fused kernel launch
    (``kernels.fused.fused_sweep_op``) instead of separate dispatches, so
    the trajectory — estimates, changed counts, dirty bits — is
    bit-identical to the unfused engines sweep by sweep. ``c`` may be
    int16 (opt-in estimate mode); the kernel widens in-register.
    """
    sentinel = c.shape[0] - 1
    frozen = c
    new_c = c
    dirty = jnp.zeros((c.shape[0],), jnp.int8)
    changed_parts = []
    for bi, (node_ids, neigh, _mask) in enumerate(buckets):

        def update(nc, dt, node_ids=node_ids, neigh=neigh):
            from repro.kernels.fused import fused_sweep_op

            src = frozen if frozen_reads else nc
            est, row_changed, d = fused_sweep_op(
                src, ext_pad, node_ids, neigh, cand=cand,
                track_dirty=track_dirty,
            )
            ch = jnp.sum(row_changed).astype(jnp.int32)
            if track_dirty:
                dt = jnp.maximum(dt, d)
            nc = nc.at[node_ids].set(est.astype(nc.dtype))
            nc = nc.at[-1].set(-1)  # re-pin sentinel
            return nc, dt, ch

        new_c, dirty, ch = jax.lax.cond(
            active[bi], update, lambda nc, dt: (nc, dt, jnp.int32(0)), new_c, dirty
        )
        changed_parts.append(ch)
    changed = (
        jnp.stack(changed_parts) if changed_parts else jnp.zeros((0,), jnp.int32)
    )
    if track_dirty and buckets:
        dirty_next = jnp.stack(
            [
                jnp.any((dirty[node_ids] > 0) & (node_ids != sentinel))
                for node_ids, _neigh, _mask in buckets
            ]
        )
    else:
        dirty_next = jnp.zeros((len(buckets),), bool)
    return new_c, changed, dirty_next


class _FusedGroups:
    """Width-grouped resident layout for the dense active-row-index
    compaction dispatch of the fused engine.

    With hundreds of tiles the per-bucket ``lax.cond`` chain dominates
    compile and dispatch time (both branches stay resident in XLA). This
    layout concatenates every tile of a width class into one resident
    ``[rows+1, width]`` array (ascending width == bucketize's emission
    order; the extra row is an all-sentinel pad target), and each sweep
    compacts the ACTIVE tiles' row indices into one dense index vector per
    group — one fused launch per width class, work proportional to the
    live frontier. The index vector is padded to a power of two so jit
    retraces stay logarithmic in frontier size.
    """

    def __init__(self, bg: BucketedGraph):
        n = bg.n_nodes
        nb = len(bg.buckets)
        by_width: dict = {}
        for bi, b in enumerate(bg.buckets):
            by_width.setdefault(b.width, []).append(bi)
        self.n_buckets = nb
        self.groups = []
        self.memory_bytes = 0
        for width in sorted(by_width):
            bis = by_width[width]
            ids = np.concatenate(
                [np.asarray(bg.buckets[bi].node_ids, np.int32) for bi in bis]
                + [np.full(1, n, np.int32)]
            )
            neigh = np.concatenate(
                [np.asarray(bg.buckets[bi].neigh, np.int32) for bi in bis]
                + [np.full((1, width), n, np.int32)]
            )
            tile_all = np.concatenate(
                [np.full(bg.buckets[bi].n_rows, bi, np.int32) for bi in bis]
                + [np.full(1, nb, np.int32)]
            )
            ranges, start = [], 0
            for bi in bis:
                r = bg.buckets[bi].n_rows
                ranges.append((bi, start, r))
                start += r
            self.groups.append({
                "ids": jnp.asarray(ids),
                "neigh": jnp.asarray(neigh),
                "tile_all": jnp.asarray(tile_all),
                "ranges": ranges,
                "pad_row": start,  # the all-sentinel row
            })
            self.memory_bytes += ids.nbytes + neigh.nbytes + tile_all.nbytes

    @staticmethod
    def active_rows(grp, active: np.ndarray, n_buckets: int):
        """Dense row-index compaction of ``grp``'s active tiles.

        Returns ``(row_idx, tile_of_row)`` int32 arrays padded to a power
        of two with the group's sentinel pad row, or ``None`` when no tile
        of this group is active.
        """
        sel = [(bi, s, r) for bi, s, r in grp["ranges"] if active[bi]]
        if not sel:
            return None
        row_idx = np.concatenate([np.arange(s, s + r, dtype=np.int32)
                                  for _bi, s, r in sel])
        tile_of = np.concatenate([np.full(r, bi, np.int32)
                                  for bi, _s, r in sel])
        k = row_idx.size
        k_pad = max(8, 1 << (k - 1).bit_length())
        if k_pad > k:
            # Pad rows gather the all-sentinel row (changed=0) and key the
            # throwaway segment-count slot n_buckets.
            row_idx = np.pad(row_idx, (0, k_pad - k),
                             constant_values=grp["pad_row"])
            tile_of = np.pad(tile_of, (0, k_pad - k),
                             constant_values=n_buckets)
        return row_idx, tile_of


@partial(jax.jit, static_argnames=("cand", "track_dirty", "n_counts"))
def _fused_compact_step(nc, src, ext_pad, ids_w, neigh_w, row_idx, tile_of_row,
                        changed, dirty, *, cand: int, track_dirty: bool,
                        n_counts: int):
    """One compacted fused launch over the active rows of a width group.

    ``src`` is the estimate vector the gather reads (``nc`` itself for
    Gauss-Seidel across groups, the sweep's frozen snapshot for Jacobi);
    per-bucket changed counts come back as a segment-sum keyed by
    ``tile_of_row`` (pad rows key -1 -> dropped by segment_sum).
    """
    from repro.kernels.fused import fused_sweep_op

    ids_a = ids_w[row_idx]
    neigh_a = neigh_w[row_idx]
    est, row_changed, d = fused_sweep_op(
        src, ext_pad, ids_a, neigh_a, cand=cand, track_dirty=track_dirty,
    )
    changed = changed + jax.ops.segment_sum(
        row_changed, tile_of_row, num_segments=n_counts
    )
    if track_dirty:
        dirty = jnp.maximum(dirty, d)
    nc = nc.at[ids_a].set(est.astype(nc.dtype))
    nc = nc.at[-1].set(-1)  # re-pin sentinel
    return nc, changed, dirty


@partial(jax.jit, static_argnames=("n_buckets",))
def _fused_compact_dirty_next(dirty, ids_list, tile_list, *, n_buckets: int):
    """Per-bucket dirty read-back over the resident group layouts."""
    sentinel = dirty.shape[0] - 1
    out = jnp.zeros((n_buckets + 1,), jnp.int32)
    for ids_w, tile_all in zip(ids_list, tile_list):
        flag = ((dirty[ids_w] > 0) & (ids_w != sentinel)).astype(jnp.int32)
        out = out.at[tile_all].max(flag)  # pad row keys slot n_buckets
    return out[:n_buckets] > 0


def _compaction_sweep(groups: _FusedGroups, c, ext_pad, active: np.ndarray,
                      cand: int, frozen_reads: bool, track_dirty: bool):
    """One fused-engine sweep, compaction dispatch (many tiles).

    Width groups run ascending (bucketize order): Gauss-Seidel across
    groups when ``frozen_reads=False``, textbook Jacobi (reads frozen at
    sweep start) otherwise. Within one group's single launch the reads are
    always Jacobi — see the engine docstring for when that changes the
    per-sweep trajectory (never the fixed point).
    """
    nb = groups.n_buckets
    frozen = c
    changed = jnp.zeros((nb + 1,), jnp.int32)
    dirty = jnp.zeros((c.shape[0],), jnp.int8)
    for grp in groups.groups:
        compacted = _FusedGroups.active_rows(grp, active, nb)
        if compacted is None:
            continue
        row_idx, tile_of = compacted
        src = frozen if frozen_reads else c
        c, changed, dirty = _fused_compact_step(
            c, src, ext_pad, grp["ids"], grp["neigh"],
            jnp.asarray(row_idx), jnp.asarray(tile_of), changed, dirty,
            cand=cand, track_dirty=track_dirty, n_counts=nb + 1,
        )
    if track_dirty:
        dirty_next = _fused_compact_dirty_next(
            dirty,
            tuple(g["ids"] for g in groups.groups),
            tuple(g["tile_all"] for g in groups.groups),
            n_buckets=nb,
        )
    else:
        dirty_next = jnp.zeros((nb,), bool)
    return c, changed[:nb], dirty_next


def decompose(
    bg: BucketedGraph,
    *,
    op: str = "sorted",
    max_iter: Optional[int] = None,
    gauss_seidel: bool = True,
    frontier: bool = True,
    init_coreness: Optional[np.ndarray] = None,
    seed_nodes: Optional[np.ndarray] = None,
    on_sweep=None,
    int16: bool = False,
    fused_compaction_min_tiles: int = 64,
) -> DecomposeResult:
    """Run the h-index fixed point on one part until no estimate changes.

    ``frontier`` enables active-frontier sweep scheduling (sound bucket
    skipping via the bucket-adjacency bitmap); ``False`` re-sweeps every
    bucket every iteration. ``init_coreness`` resumes from a snapshot
    (fixed-point iterations are restartable from ANY valid upper bound of
    the true coreness — the fault-tolerance hook for the paper's 27.5h-scale
    runs); ``on_sweep(iteration, coreness)`` is the snapshot callback,
    called after every sweep with an int32 original-id-order array view
    (lazy device array — ``np.asarray`` it to materialize; no host sync is
    forced on sweeps whose snapshot the hook discards) —
    :func:`repro.core.dckcore.dc_kcore` feeds its sweep-granularity
    checkpoints from it.

    If ``bg`` was built from a reordered graph (``bg.perm`` set), the
    reordering is invisible here: ``init_coreness`` is taken in original-id
    order and permuted in, ``on_sweep`` views and the returned ``coreness``
    are permuted back — a snapshot taken under one ordering restarts
    correctly under any other.

    ``seed_nodes`` restricts the INITIAL active frontier to the buckets
    owning the given nodes (original-id boolean mask or id array) instead
    of every bucket — the incremental engine's entry point: with a valid
    ``init_coreness`` upper bound and a seed set that covers every node
    whose estimate must move (see :mod:`repro.core.incremental` for the
    soundness argument), the fixed point reached is identical to a full
    sweep, but quiescent regions are never touched. Requires
    ``frontier=True`` (the dirty-bit propagation is what re-activates
    neighbors of changed seeds).

    ``op="fused"`` dispatches the fused Pallas sweep kernel; ``int16``
    (fused only) opts into the halved-width estimate vector behind the
    overflow guard, and ``fused_compaction_min_tiles`` sets the tile count
    at which the per-bucket ``lax.cond`` chain is replaced by the dense
    active-row-index compaction (see module docstring). Snapshot traffic
    (``init_coreness`` in, ``on_sweep`` views and ``coreness`` out) is
    int32 regardless, so every resume/checkpoint consumer is dtype-blind.
    """
    n = bg.n_nodes
    t0 = time.perf_counter()
    with span("kcore.conquer.setup"):
        if op == "fused":
            from repro.kernels.fused import require_fused_platform

            require_fused_platform()
        est_dtype = jnp.int32
        if int16:
            if op != "fused":
                raise ValueError("int16=True requires op='fused' (the fused "
                                 "kernel widens in-register; the unfused "
                                 "engines assume int32 state)")
            max_start = int(
                (bg.degrees.astype(np.int64) + np.asarray(bg.ext, np.int64))
                .max(initial=0)
            )
            # Overflow guard: estimates start at deg + ext and only decrease,
            # so int16 is exact iff every start fits. Fall back, never wrap.
            if max_start < (1 << 15):
                est_dtype = jnp.int16
        ext = jnp.asarray(bg.ext, dtype=jnp.int32)
        ext_pad = jnp.concatenate([ext, jnp.zeros((1,), jnp.int32)])
        if init_coreness is not None:
            start = np.asarray(init_coreness)
            if bg.perm is not None:
                start = start[bg.perm]  # original-id order -> layout order
            start = jnp.asarray(start, est_dtype)
        else:
            start = (jnp.asarray(bg.degrees, jnp.int32) + ext).astype(est_dtype)
        c = jnp.concatenate([start, jnp.full((1,), -1, est_dtype)])
        # Candidate-window bound (exact; see hindex_of_sequence docstring).
        cand = max(1, hindex_of_sequence(bg.degrees.astype(np.int64) + bg.ext))

        fused_mode = ""
        groups = None
        if op == "fused":
            fused_mode = (
                "compaction" if len(bg.buckets) >= fused_compaction_min_tiles
                else "cond"
            )
        if fused_mode == "compaction":
            groups = _FusedGroups(bg)
            buckets = []
            tiles_bytes = groups.memory_bytes
        else:
            buckets = _device_buckets(bg)
            tiles_bytes = (sum(a.nbytes for tile in buckets for a in tile)
                           + bg.ext.nbytes + bg.degrees.nbytes)

        wire = 2 if est_dtype == jnp.int16 else 4
        state_bytes = int(c.size * wire + ext_pad.size * 4)
        peak = tiles_bytes + state_bytes

        n_buckets = len(bg.buckets)
        bucket_rows = np.array([b.n_rows for b in bg.buckets], dtype=np.int64)
        bucket_widths = list(bg.widths)
        adj = bg.bucket_adjacency()
        active = np.ones(n_buckets, dtype=bool)
        if seed_nodes is not None:
            if not frontier:
                raise ValueError("seed_nodes requires frontier=True (seed "
                                 "restriction relies on dirty-bit scheduling "
                                 "to re-activate neighbors)")
            seeds = np.asarray(seed_nodes)
            if seeds.dtype == bool:
                if seeds.shape != (n,):
                    raise ValueError(f"seed mask shape {seeds.shape} != ({n},)")
                seeds = np.nonzero(seeds)[0]
            if bg.inv_perm is not None:
                # Seeds arrive as original ids; the owner map is in layout
                # order, and original id o sits at layout row inv_perm[o].
                seeds = np.asarray(bg.inv_perm)[seeds]
            owner = bg.node_bucket_map()[:-1][seeds]
            active = np.zeros(n_buckets, dtype=bool)
            active[owner[owner >= 0]] = True  # -1: deg-0 rows own no bucket
        bucket_slots = bucket_rows * np.array(bucket_widths, dtype=np.int64)

        limit = max_iter if max_iter is not None else max(4, n)
        # Hoisted once: re-uploading the O(n) permutation every sweep would
        # put an H2D transfer in the hot loop just to build the on_sweep
        # view.
        inv_perm_dev = (
            jnp.asarray(bg.inv_perm)
            if on_sweep is not None and bg.inv_perm is not None else None
        )
    comm_per_iter: List[int] = []
    active_rows_per_iter: List[int] = []
    active_per_iter: List[np.ndarray] = []
    total = 0
    it = 0
    while it < limit:
        active_rows = int(bucket_rows[active].sum())
        active_rows_per_iter.append(active_rows)
        active_per_iter.append(active)
        with span("kcore.sweep", active_tiles=int(active.sum()),
                  active_rows=active_rows,
                  swept_slots=int(bucket_slots[active].sum())) as sweep_span:
            if fused_mode == "compaction":
                c, changed_vec, dirty_next = _compaction_sweep(
                    groups, c, ext_pad, active, cand,
                    frozen_reads=not gauss_seidel, track_dirty=frontier,
                )
            elif fused_mode == "cond":
                c, changed_vec, dirty_next = _sweep_fused(
                    c, ext_pad, buckets, jnp.asarray(active),
                    cand=cand, frozen_reads=not gauss_seidel,
                    track_dirty=frontier,
                )
            else:
                c, changed_vec, dirty_next = _sweep(
                    c, ext_pad, buckets, jnp.asarray(active),
                    op=op, cand=cand, frozen_reads=not gauss_seidel,
                    track_dirty=frontier,
                )
            with span("kcore.sweep.wait"):
                changed_vec = np.asarray(changed_vec)
            changed = int(changed_vec.sum())
            sweep_span.counts["changed_rows"] = changed
            comm_per_iter.append(changed)
            total += changed
            it += 1
            if on_sweep is not None:
                # Contract (shared with the distributed engine): int32
                # values in original-id order. The view stays a lazy device
                # array — no host sync is forced here — so a hook that
                # samples every k-th sweep (the sweep-granularity
                # checkpoints of repro.core.dckcore) pays np.asarray only
                # when it keeps one.
                view = c[:-1]
                if view.dtype != jnp.int32:
                    view = view.astype(jnp.int32)  # int16: contract is int32
                if inv_perm_dev is not None:
                    view = view[inv_perm_dev]  # -> original-id order
                on_sweep(it, view)
            if changed == 0:
                break
            if frontier:
                # Next frontier: buckets with a dirty row (a neighbor
                # changed), intersected with the static bucket-adjacency
                # certificate — dirty bits refine the bitmap, never widen it.
                reach = adj[changed_vec > 0].any(axis=0)
                active = np.asarray(dirty_next) & reach
    with span("kcore.conquer.readout"):
        coreness = np.asarray(c[:-1]).astype(np.int32, copy=False)
        if bg.inv_perm is not None:
            # layout order -> original-id order
            coreness = coreness[bg.inv_perm]
    # Modeled HBM traffic / FLOPs of each sweep's live shape (fig17's
    # achieved-vs-roofline input; int16 halves the wire terms), priced
    # once the sweeps are done.
    sweep_bytes_per_iter: List[int] = []
    sweep_flops_per_iter: List[int] = []
    for mask in active_per_iter:
        mb, mf = sweep_cost(
            [(int(bucket_rows[bi]), bucket_widths[bi])
             for bi in np.nonzero(mask)[0]],
            cand, wire_bytes=wire, fused=(op == "fused"),
            track_dirty=frontier, mask_words=tile_mask_words(n_buckets),
        )
        sweep_bytes_per_iter.append(mb)
        sweep_flops_per_iter.append(mf)
    return DecomposeResult(
        coreness=coreness,
        iterations=it,
        comm_amount=total,
        comm_per_iter=comm_per_iter,
        peak_bytes=int(peak),
        wall_time_s=time.perf_counter() - t0,
        active_rows_per_iter=active_rows_per_iter,
        rows_per_full_sweep=bg.rows_per_full_sweep,
        sweep_bytes_per_iter=sweep_bytes_per_iter,
        sweep_flops_per_iter=sweep_flops_per_iter,
        est_dtype="int16" if est_dtype == jnp.int16 else "int32",
        fused_mode=fused_mode,
    )
