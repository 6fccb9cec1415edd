"""Distributed conquer engine: shard_map k-core decomposition.

TPU-native mapping of the paper's parameter-server loop (Section 4.3.2,
Figure 6):

  paper step                      | here
  --------------------------------+----------------------------------------
  (1) vertex-centric data loading | bucket rows block-sharded over the node
                                  | mesh axes; neighbor slots sharded over
                                  | the slot ("model") axes
  (2) pull coreness from PS       | local gather from the replicated part
                                  | coreness vector
  (3) estimate coreness (Alg 2)   | partial suffix-counts per slot shard,
                                  | psum over slot axes, feasibility argmax
  (4) push updated coreness       | all_gather of the per-shard estimates
                                  | over the node axes
  (5) PS in-place update          | functional scatter into the replicated
                                  | vector

The replicated coreness vector is the PS analogue; its size is the *part*
node count, which is exactly what the divide step caps — the peak-HBM story
of the paper carries over unchanged.

Collective traffic is counted analytically per sweep (ring all-gather /
reduce-scatter terms) by :func:`sweep_collective_bytes`; the paper's
"communication amount" (changed estimates) is counted on-device like the
single-device engine.

Active-frontier sweep scheduling mirrors the single-device engine: the
replicated frontier mask gates each bucket's gather, h-index, psum AND
all_gather behind ``lax.cond`` (every device branches on the same
replicated predicate), so both compute and collective bytes shrink with
the frontier. Dirty bits are pushed at bucket granularity through the
replicated ``node_tile`` map and unioned across the mesh by one
[n_buckets] psum per sweep — no state-sized collective is ever added.
The skip soundness argument is the same static bucket-adjacency bitmap +
row-exact dirty-bit refinement documented in ``repro.core.decompose``.
"""
from __future__ import annotations

import dataclasses
import math
import time
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.decompose import DecomposeResult
from repro.core.hindex import hindex_of_sequence
from repro.core.spans import span
from repro.graph.structs import BucketedGraph


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """How the graph maps onto the device mesh."""

    mesh: Mesh
    node_axes: Tuple[str, ...]  # bucket rows sharded over these
    slot_axes: Tuple[str, ...]  # neighbor slots sharded over these

    @property
    def n_node_shards(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.node_axes)

    @property
    def n_slot_shards(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.slot_axes)


def _pad_to(x: np.ndarray, mult: int, axis: int, fill) -> np.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return np.pad(x, widths, constant_values=fill)


def shard_buckets(bg: BucketedGraph, plan: MeshPlan, wire_dtype=jnp.int32):
    """Device-put bucket arrays with their distributed shardings."""
    ns, ms = plan.n_node_shards, plan.n_slot_shards
    mesh = plan.mesh
    row_spec = NamedSharding(mesh, P(plan.node_axes))
    tile_spec = NamedSharding(mesh, P(plan.node_axes, plan.slot_axes))
    out = []
    for b in bg.buckets:
        ids = _pad_to(b.node_ids, ns, 0, bg.n_nodes)
        neigh = _pad_to(_pad_to(b.neigh, ns, 0, bg.n_nodes), ms, 1, bg.n_nodes)
        out.append(
            (
                jax.device_put(ids.astype(np.int32), row_spec),
                jax.device_put(neigh.astype(np.int32), tile_spec),
            )
        )
    return out


def _ring_bucket_bytes(padded_rows: int, ns: int, ms: int, cand: int,
                       wire_bytes: int, include_ids: bool) -> int:
    """Per-device ICI bytes of ONE bucket's sweep collectives (ring model).

    The single shape-level formula every collective-bytes accounting in
    this module derives from — the analytic planning model, the measured
    per-iteration counter, and the dry-run's planned schedule can then
    never disagree about what one bucket costs:

    * psum of the ``[rows_loc, cand]`` int32 count partials over the slot
      axes: a ring all-reduce moves ``2 (m-1)/m`` of the operand;
    * all_gather of the ``[rows_loc]`` estimates (``wire_bytes`` wide) over
      the node axes: ``(n-1)`` local shards per device — plus, when
      ``include_ids``, the int32 ids all_gather issued alongside it.

    ``padded_rows`` must already be the node-shard-padded row count.
    """
    rows_loc = padded_rows // ns
    total = 0
    if ms > 1:
        total += int(2 * (ms - 1) / ms * rows_loc * cand * 4)
    if ns > 1:
        total += int((ns - 1) * rows_loc * (wire_bytes + (4 if include_ids else 0)))
    return total


def _dirty_psum_bytes(n_buckets: int, mesh_size: int) -> int:
    """Per-device bytes of the frontier's [n_buckets] dirty-bit psum."""
    if mesh_size <= 1:
        return 0
    return int(2 * (mesh_size - 1) / mesh_size * n_buckets * 4)


def sweep_collective_bytes(bg: BucketedGraph, plan: MeshPlan, cand: int,
                           wire_bytes: int = 4,
                           active: Optional[np.ndarray] = None) -> int:
    """Analytic per-device ICI bytes of one sweep (ring-algorithm model).

    Two collective terms per *active* bucket:

    * psum of the ``[rows_loc, cand]`` int32 count partials over the slot
      axes — a ring all-reduce moves ``2 (m-1)/m`` of the operand per
      device (``m`` = slot shards);
    * all_gather of the ``[rows_loc]`` estimates over the node axes — a
      ring all-gather moves ``(n-1)`` local shards per device (``n`` =
      node shards), each ``wire_bytes`` wide (int16 wire halves exactly
      this term).

    ``active`` restricts the count to the frontier's buckets — skipped
    buckets skip their collectives too, so per-sweep collective bytes
    shrink with the frontier.

    This is the *planning* model: it works from ``bg`` alone (no device
    arrays needed), which is what the dry-run feasibility tables use at
    the paper's 136B-edge scales. It deliberately excludes the frontier's
    own [n_buckets] dirty-bit psum. The *measured* counterpart — computed
    per iteration from the live frontier mask and the actual padded device
    shapes, dirty psum included — is :func:`measured_sweep_bytes`, which
    :func:`decompose_distributed` records into
    ``DecomposeResult.collective_bytes_per_iter``.
    """
    ns, ms = plan.n_node_shards, plan.n_slot_shards
    total = 0
    for bi, b in enumerate(bg.buckets):
        if active is not None and not active[bi]:
            continue
        rows = math.ceil(b.n_rows / ns) * ns
        total += _ring_bucket_bytes(rows, ns, ms, cand, wire_bytes,
                                    include_ids=False)
    return total


def measured_sweep_bytes(dev_buckets, plan: MeshPlan, cand: int,
                         wire_bytes: int, active: np.ndarray,
                         frontier: bool) -> int:
    """Per-device ICI bytes one sweep actually moves, from live state.

    Unlike the analytic :func:`sweep_collective_bytes` model this reads the
    *device* bucket arrays (whose rows :func:`shard_buckets` re-padded to
    the node-shard multiple), takes the actual per-iteration frontier mask,
    and counts two terms the analytic model omits:

    * the int32 ``ids_loc`` all_gather each active bucket issues alongside
      its estimate gather (node ids are re-gathered transiently every
      sweep rather than replicated — keeping them resident would put the
      whole row-id vector back into per-device HBM, the budget the divide
      step exists to cap);
    * the frontier's [n_buckets] dirty-bit psum over the whole mesh (a
      ``2 (k-1)/k`` ring all-reduce, ``k`` = mesh size).

    This is the counter :func:`decompose_distributed` accumulates per
    iteration into ``DecomposeResult.collective_bytes_per_iter``.
    """
    ns, ms = plan.n_node_shards, plan.n_slot_shards
    total = 0
    for bi, (ids, _neigh) in enumerate(dev_buckets):
        if not active[bi]:
            continue
        # est_full (wire dtype) + ids_full (int32) ring all-gathers.
        total += _ring_bucket_bytes(ids.shape[0], ns, ms, cand, wire_bytes,
                                    include_ids=True)
    if frontier:
        # dirty_next psum: [n_buckets] int32 over every mesh axis; runs
        # whenever the frontier sweep runs, active or not.
        total += _dirty_psum_bytes(len(dev_buckets), ns * ms)
    return total


def planned_collective_schedule(
    bucket_rows: Sequence[int],
    plan: MeshPlan,
    cand: int,
    *,
    wire_bytes: int = 4,
    n_iters: int = 30,
    full_sweeps: int = 3,
    decay: float = 0.6,
    frontier: bool = True,
) -> List[int]:
    """Modeled per-iteration collective bytes for a run that never sweeps.

    The dry-run feasibility tables need collective traffic without running
    a single sweep, so this derives it from a *planned* frontier schedule
    over the bucket shapes: the first ``full_sweeps`` iterations sweep
    every bucket (estimates are still far from their fixed point
    everywhere), after which the live row fraction decays geometrically by
    ``decay`` per sweep and the frontier concentrates in the LAST buckets
    of the list — bucketize emits degree classes ascending, and on
    power-law graphs the dense classes (hubs) converge last (Montresor et
    al.; paper Fig 8). Each planned iteration is costed with the same
    per-bucket ring formula as the measured counter (ids all_gather and
    dirty-bit psum included), so on a ``frontier=False`` run — where the
    planned schedule is exact, every sweep full — the model reproduces
    ``DecomposeResult.collective_bytes_per_iter`` byte for byte (the
    pinning test of tests/test_distributed_kcore.py).

    ``bucket_rows`` are the UNpadded per-bucket row counts (node-shard
    padding is applied here, as :func:`shard_buckets` would).
    """
    ns, ms = plan.n_node_shards, plan.n_slot_shards
    nb = len(bucket_rows)
    padded = [math.ceil(r / ns) * ns for r in bucket_rows]
    dirty = _dirty_psum_bytes(nb, ns * ms) if frontier else 0
    return [
        sum(_ring_bucket_bytes(padded[bi], ns, ms, cand, wire_bytes,
                               include_ids=True) for bi in live)
        + dirty
        for live in planned_live_sets(padded, n_iters=n_iters,
                                      full_sweeps=full_sweeps, decay=decay,
                                      frontier=frontier)
    ]


def planned_live_sets(
    padded_rows: Sequence[int],
    *,
    n_iters: int = 30,
    full_sweeps: int = 3,
    decay: float = 0.6,
    frontier: bool = True,
) -> List[List[int]]:
    """The planned frontier schedule itself: live bucket indices per sweep.

    This is the live-set rule :func:`planned_collective_schedule` prices —
    extracted so other cost models (the part-parallel scheduler's HBM
    term in ``repro.core.partsched``) price the *same* schedule. The first
    ``full_sweeps`` iterations keep every bucket live; afterwards the live
    row budget decays geometrically by ``decay`` and is filled from the
    LAST buckets of the list downward (densest degree classes converge
    last on power-law graphs). ``padded_rows`` must already carry the
    node-shard padding.
    """
    nb = len(padded_rows)
    total_rows = sum(padded_rows) or 1
    out: List[List[int]] = []
    for it in range(n_iters):
        if not frontier or it < full_sweeps:
            live = list(range(nb))
        else:
            budget = total_rows * (decay ** (it - full_sweeps + 1))
            live, acc = [], 0
            for bi in range(nb - 1, -1, -1):  # densest classes stay live
                live.append(bi)
                acc += padded_rows[bi]
                if acc >= budget:
                    break
        out.append(live)
    return out


def _partial_counts(gathered, ext_rows, cand: int, cand_chunk: int = 256):
    """Suffix counts over the LOCAL slot shard: cnt[r, i] for i in [1, cand]."""
    chunks = []
    for lo in range(0, cand, cand_chunk):
        w = min(cand_chunk, cand - lo)
        i = lo + 1 + jnp.arange(w, dtype=jnp.int32)
        thr = ext_rows[:, None] + i[None, :]
        chunks.append(
            jnp.sum((gathered[:, :, None] >= thr[:, None, :]).astype(jnp.int32), axis=1)
        )
    return jnp.concatenate(chunks, axis=1) if len(chunks) > 1 else chunks[0]


def make_sweep_fn(plan: MeshPlan, cand: int, wire_dtype=jnp.int32,
                  use_kernel: bool = False, frontier: bool = True):
    """Build the jitted shard_map sweep:
    ``(c, ext_pad, active, node_tile, buckets) -> (c', changed, dirty_next)``.

    ``active`` is the replicated [n_buckets] bool frontier mask: inactive
    buckets skip gather, h-index, AND their psum/all_gather behind
    ``lax.cond`` — per-sweep collective bytes shrink with the frontier.
    ``node_tile`` maps node id -> owning bucket ([n + 1], sentinel/deg-0
    rows -> n_buckets). ``changed[i]`` counts rows of bucket ``i`` whose
    estimate changed (replicated arithmetic, no extra collective);
    ``dirty_next[j]`` is True iff some changed row has a neighbor in bucket
    ``j`` — each device pushes shard-local dirty bits at bucket granularity
    and one tiny [n_buckets] psum unions them across the mesh.
    ``frontier=False`` (the always-full-sweep baseline) compiles the dirty
    push and its psum out and returns an all-False ``dirty_next``.

    ``use_kernel=True`` computes the per-shard partial counts with the
    Pallas kernel (kernels/counts) instead of the pure-jnp path."""
    mesh = plan.mesh
    node_axes, slot_axes = plan.node_axes, plan.slot_axes
    all_axes = tuple(node_axes) + tuple(slot_axes)
    rep = P()  # replicated
    row_p = P(node_axes)
    tile_p = P(node_axes, slot_axes)

    def counts(gathered, ext_rows):
        if use_kernel:
            from repro.kernels.counts import partial_counts_op

            return partial_counts_op(gathered, ext_rows, cand=cand)
        return _partial_counts(gathered, ext_rows, cand)

    def sweep(c, ext_pad, active, node_tile, buckets):
        n_buckets = len(buckets)
        sentinel = c.shape[0] - 1
        new_c = c
        # Shard-local per-bucket dirty partials (slot n_buckets = dump row
        # for sentinel-padded neighbors); unioned by one [nb] psum below.
        tile_dirty = jnp.zeros((n_buckets + 1,), jnp.int32)
        changed_parts = []
        for bi, (ids_loc, neigh_loc) in enumerate(buckets):

            def update(nc, td, ids_loc=ids_loc, neigh_loc=neigh_loc):
                gathered = nc[neigh_loc].astype(jnp.int32)  # wire may be int16
                ext_rows = ext_pad[ids_loc]
                cnt = counts(gathered, ext_rows)
                if plan.n_slot_shards > 1:
                    cnt = jax.lax.psum(cnt, slot_axes)
                i = 1 + jnp.arange(cand, dtype=jnp.int32)
                feasible = cnt >= i[None, :]
                est = ext_rows + jnp.max(jnp.where(feasible, i[None, :], 0), axis=1)
                est = est.astype(wire_dtype)
                # Push dirty bits: each changed local row marks the buckets
                # owning its local neighbor slots (union across devices via
                # the final psum). Work stays proportional to the frontier.
                if frontier:
                    row_changed = (est.astype(nc.dtype) != nc[ids_loc]) & (
                        ids_loc != sentinel
                    )
                    td = td.at[node_tile[neigh_loc].astype(jnp.int32)].max(
                        jnp.broadcast_to(
                            row_changed[:, None], neigh_loc.shape
                        ).astype(jnp.int32)
                    )
                if plan.n_node_shards > 1:
                    est_full = jax.lax.all_gather(est, node_axes, tiled=True)
                    ids_full = jax.lax.all_gather(ids_loc, node_axes, tiled=True)
                else:
                    est_full, ids_full = est, ids_loc
                prev_full = nc[ids_full]
                ch = jnp.sum(
                    (est_full.astype(nc.dtype) != prev_full)
                    & (ids_full != sentinel)
                ).astype(jnp.int32)
                nc = nc.at[ids_full].set(est_full.astype(nc.dtype))
                nc = nc.at[-1].set(-1)
                return nc, td, ch

            new_c, tile_dirty, ch = jax.lax.cond(
                active[bi],
                update,
                lambda nc, td: (nc, td, jnp.int32(0)),
                new_c,
                tile_dirty,
            )
            changed_parts.append(ch)
        changed = (
            jnp.stack(changed_parts)
            if changed_parts
            else jnp.zeros((0,), jnp.int32)
        )
        dirty_next = tile_dirty[:n_buckets]
        if frontier and len(all_axes) > 0:
            dirty_next = jax.lax.psum(dirty_next, all_axes)
        return new_c, changed, dirty_next > 0

    def build(n_buckets: int):
        """shard_map needs exact pytree in_specs — build per bucket count.

        check_vma=False: outputs ARE replicated by construction (psum over
        slot axes + all_gather over node axes before every scatter), but the
        static checker cannot see through the scatter."""
        return jax.jit(
            jax.shard_map(
                sweep,
                mesh=mesh,
                in_specs=(rep, rep, rep, rep, [(row_p, tile_p)] * n_buckets),
                out_specs=(rep, rep, rep),
                check_vma=False,
            )
        )

    return build


def node_tile_map(bg: BucketedGraph) -> np.ndarray:
    """[n + 1] node -> owning bucket; sentinel/deg-0 -> n_buckets.

    int16 whenever the bucket count allows (it always does in practice:
    buckets are degree classes x bounded row-tiles). At the paper's WX-136B
    scale the replicated map is 2 bytes/node — the same budget class as the
    int16 coreness wire, which is what keeps the divided parts inside the
    16 GiB/chip feasibility story."""
    nb = len(bg.buckets)
    dtype = np.int16 if nb < np.iinfo(np.int16).max else np.int32
    m = bg.node_bucket_map()
    return np.where(m < 0, nb, m).astype(dtype)


def decompose_distributed(
    bg: BucketedGraph,
    plan: MeshPlan,
    *,
    wire_dtype=jnp.int32,
    use_kernel: bool = False,
    frontier: bool = True,
    max_iter: Optional[int] = None,
    init_coreness: Optional[np.ndarray] = None,
    on_sweep=None,
) -> DecomposeResult:
    """Distributed fixed point; same contract as
    :func:`repro.core.decompose.decompose` (including ``frontier``,
    ``init_coreness`` warm restart and the ``on_sweep(iteration, coreness)``
    snapshot hook — both speak **original**-id order int32, the hook view
    staying a lazy device array, so a snapshot taken by this engine
    restarts the single-device one and vice versa; with an int16 wire,
    snapshots widen to int32 on the way out and narrow back on the way
    in)."""
    n = bg.n_nodes
    t0 = time.perf_counter()
    with span("kcore.conquer.setup"):
        cand = max(1, hindex_of_sequence(bg.degrees.astype(np.int64) + bg.ext))

        mesh = plan.mesh
        rep_sh = NamedSharding(mesh, P())
        ext = jnp.asarray(bg.ext, dtype=jnp.int32)
        ext_pad = jax.device_put(
            jnp.concatenate([ext, jnp.zeros((1,), jnp.int32)]), rep_sh
        )
        if init_coreness is not None:
            start = np.asarray(init_coreness)
            if bg.perm is not None:
                start = start[bg.perm]  # original-id order -> layout order
            start = jnp.asarray(start, jnp.int32).astype(wire_dtype)
        else:
            start = (jnp.asarray(bg.degrees, jnp.int32) + ext).astype(wire_dtype)
        c = jax.device_put(
            jnp.concatenate([start, jnp.full((1,), -1, wire_dtype)]),
            rep_sh,
        )
        node_tile = jax.device_put(jnp.asarray(node_tile_map(bg)), rep_sh)
        buckets = shard_buckets(bg, plan, wire_dtype)
        sweep = make_sweep_fn(plan, cand, wire_dtype, use_kernel, frontier)(len(buckets))

        # Peak per-device bytes: sharded tiles + replicated state (coreness,
        # ext, and the node -> bucket frontier map).
        ns, ms = plan.n_node_shards, plan.n_slot_shards
        tile_bytes = sum(int(ids.size * 4 / ns + neigh.size * 4 / (ns * ms)) for ids, neigh in buckets)
        state_bytes = int(
            c.size * c.dtype.itemsize
            + ext_pad.size * 4
            + node_tile.size * node_tile.dtype.itemsize
        )
        peak = tile_bytes + state_bytes

        n_buckets = len(bg.buckets)
        bucket_rows = np.array([b.n_rows for b in bg.buckets], dtype=np.int64)
        adj = bg.bucket_adjacency()
        active = np.ones(n_buckets, dtype=bool)
        bucket_slots = bucket_rows * np.array(bg.widths, dtype=np.int64)

        wire_bytes = jnp.dtype(wire_dtype).itemsize
        limit = max_iter if max_iter is not None else max(4, n)
        # Hoisted once: no per-sweep H2D upload just to build the hook view.
        inv_perm_dev = (
            jnp.asarray(bg.inv_perm)
            if on_sweep is not None and bg.inv_perm is not None else None
        )
    comm_per_iter: List[int] = []
    active_rows_per_iter: List[int] = []
    collective_bytes_per_iter: List[int] = []
    total = 0
    it = 0
    while it < limit:
        active_rows = int(bucket_rows[active].sum())
        active_rows_per_iter.append(active_rows)
        with span("kcore.sweep", active_tiles=int(active.sum()),
                  active_rows=active_rows,
                  swept_slots=int(bucket_slots[active].sum())) as sweep_span:
            collective_bytes_per_iter.append(
                measured_sweep_bytes(buckets, plan, cand, wire_bytes, active,
                                     frontier)
            )
            c, changed_vec, dirty_next = sweep(
                c, ext_pad, jnp.asarray(active), node_tile, buckets
            )
            with span("kcore.sweep.wait"):
                changed_vec = np.asarray(changed_vec)
            changed = int(changed_vec.sum())
            sweep_span.counts["changed_rows"] = changed
            comm_per_iter.append(changed)
            total += changed
            it += 1
            if on_sweep is not None:
                # Lazy int32 view in original-id order (same contract as
                # the single-device engine): the hook materializes only the
                # snapshots it keeps.
                view = c[:-1].astype(jnp.int32)
                if inv_perm_dev is not None:
                    view = view[inv_perm_dev]
                on_sweep(it, view)
            if changed == 0:
                break
            if frontier:
                reach = adj[changed_vec > 0].any(axis=0)
                active = np.asarray(dirty_next) & reach
    with span("kcore.conquer.readout"):
        coreness = np.asarray(c[:-1]).astype(np.int32)
        if bg.inv_perm is not None:
            # layout order -> original-id order
            coreness = coreness[bg.inv_perm]
    return DecomposeResult(
        coreness=coreness,
        iterations=it,
        comm_amount=total,
        comm_per_iter=comm_per_iter,
        peak_bytes=int(peak),
        wall_time_s=time.perf_counter() - t0,
        active_rows_per_iter=active_rows_per_iter,
        rows_per_full_sweep=bg.rows_per_full_sweep,
        collective_bytes_per_iter=collective_bytes_per_iter,
    )


def make_distributed_decompose(plan: MeshPlan, **kw):
    """Adapter: DecomposeFn for :func:`repro.core.dckcore.dc_kcore`."""
    return partial(decompose_distributed, plan=plan, **kw)


def device_external_info(
    g,
    keep_mask: np.ndarray,
    upper_mask: np.ndarray,
    plan: MeshPlan,
    chunk_slots: Optional[int] = None,
    stats=None,
) -> Tuple[np.ndarray, int]:
    """Device-resident E(v) boundary fold: :func:`repro.graph.build.
    external_info` computed on the mesh, plus the collective bytes it moved.

    This is the Montresor message discipline at the part boundary — when a
    part finalizes, the only information its neighbors need is *how many*
    of their neighbors now sit in the finalized upper set, i.e. the E(v)
    increment. The host pipeline folds that with a chunked numpy pass;
    in part-parallel mode the mesh is already holding the graph's working
    set, so each adjacency chunk's slots are sharded over every mesh axis,
    each device counts the contributions of its local slots, and one
    [rows] psum per chunk unions the partial counts — the boundary
    exchange is a collective, never a host round-trip.

    Bit-exactness contract (differentially tested): the returned vector
    equals the host pass at every ``chunk_slots``, because integer
    bincounts are associative across any slot partition; and when
    ``stats`` is given, the bookkeeping numbers mirror the host pass's
    arithmetic exactly (same transient model, priced from the same shapes)
    so checkpointed divide stats cannot reveal which fold ran.

    Returns ``(ext, bytes_moved)``: E(v) per surviving node in
    ``keep_mask`` order, and the per-device ICI bytes of the psums (a
    ``2 (k-1)/k`` ring over the ``k``-device mesh; 0 when ``k == 1``).
    """
    from repro.graph.build import _iter_adjacency_chunks, _resolve_chunk_slots

    keep_mask = np.asarray(keep_mask, dtype=bool)
    upper_mask = np.asarray(upper_mask, dtype=bool)
    n = g.n_nodes
    mesh = plan.mesh
    k = int(mesh.size)
    all_axes = tuple(plan.node_axes) + tuple(plan.slot_axes)
    rep_sh = NamedSharding(mesh, P())
    slot_sh = NamedSharding(mesh, P(all_axes if all_axes else None))
    # Sentinel-padded masks: pad slots point src at a real row (their
    # contribution is masked off by upper_pad[n] = False on the cols side).
    keep_dev = jax.device_put(jnp.asarray(keep_mask), rep_sh)
    upper_dev = jax.device_put(
        jnp.asarray(np.concatenate([upper_mask, [False]])), rep_sh
    )

    @partial(jax.jit, static_argnames=("lo", "rows"))
    def fold_chunk(src_dev, cols_dev, keep, upper, *, lo: int, rows: int):
        def body(src_loc, cols_loc, keep, upper):
            contributes = keep[src_loc] & upper[cols_loc]
            part = jnp.zeros((rows,), jnp.int32).at[src_loc - lo].add(
                contributes.astype(jnp.int32)
            )
            if k > 1:
                part = jax.lax.psum(part, all_axes)
            return part

        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(all_axes), P(all_axes), P(), P()),
            out_specs=P(),
            check_vma=False,
        )(src_dev, cols_dev, keep, upper)

    ext_full = np.zeros(n, dtype=np.int64)
    budget = _resolve_chunk_slots(chunk_slots)
    # Host-pass transient model, mirrored term for term (see the
    # bit-exactness contract above): persistent = masks + accumulator,
    # per-chunk = int64 src + 2x bool slot masks.
    persistent = keep_mask.nbytes + upper_mask.nbytes + ext_full.nbytes
    contributed = 0
    bytes_moved = 0
    for lo, hi, src, cols in _iter_adjacency_chunks(g, budget):
        src_pad = _pad_to(src.astype(np.int32), k, 0, lo)
        cols_pad = _pad_to(np.asarray(cols, dtype=np.int32), k, 0, n)
        part = fold_chunk(
            jax.device_put(src_pad, slot_sh),
            jax.device_put(cols_pad, slot_sh),
            keep_dev,
            upper_dev,
            lo=int(lo),
            rows=int(hi - lo),
        )
        ext_full[lo:hi] = np.asarray(part)
        if k > 1:
            bytes_moved += int(2 * (k - 1) / k * (hi - lo) * 4)
        if stats is not None:
            stats.n_chunks += 1
            stats.input_slots += int(src.size)
            contributed += int(ext_full[lo:hi].sum())
            stats.bump(persistent + src.nbytes + src.size * 2)
    if stats is not None:
        stats.kept_slots += contributed
        stats.note_pass(2 * g.n_edges, contributed, slot_bytes=9, kept_bytes=8)
    return ext_full[keep_mask].astype(np.int32), bytes_moved
