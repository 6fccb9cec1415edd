"""DC-kCore orchestrator — a staged divide / conquer / checkpoint pipeline.

Implements the full pipeline of paper Section 4 for an arbitrary number of
parts (Section 5.6 evaluates 2-4):

  1. Sort thresholds descending: ``t_p > ... > t_1``.
  2. For each threshold ``t`` on the *remaining* graph: extract candidates
     (Exact- or Rough-Divide), build the part with its external information,
     decompose it (conquer), and finalize every node whose value is >= ``t``
     (Exact finalizes all by construction). Update ``ext`` of the remaining
     nodes with their freshly-finalized neighbors and shrink the remaining
     graph.
  3. Decompose the final remaining part and finalize everything.
  4. Merge: scatter part coreness back through the id maps.

Parts still *conquer* one at a time, so the peak device footprint is the
max over parts instead of the whole graph — the paper's resource story.
But the loop is organized as three explicit stages per part:

* **divide/prefetch** — candidate selection + the chunked
  ``induced_subgraph`` / ``external_info`` passes plus the part's
  reorder+bucketize. Pure-numpy host work; with ``overlap=True`` a single
  worker thread runs the *next* part's divide (and the shrink of the
  current remaining graph) while the current part sweeps on the device.
* **conquer** — device sweeps through the pluggable engine, with
  sweep-granularity snapshots via the engine's ``on_sweep`` hook.
* **checkpoint** — the part-boundary state save and the sweep snapshots,
  routed through one persistent :class:`~repro.ckpt.CheckpointManager`
  per directory. With ``overlap=True`` these saves are async (the write
  happens on the manager's thread while the next part sweeps); purges go
  through ``CheckpointManager.clear_steps`` which waits out any pending
  save, so a purge can never race an in-flight write.

**Prefetch is speculative — correctness first.** The worker assumes every
candidate of the conquering part finalizes (exact by construction for
Exact-Divide, a bet for Rough-Divide). After the conquer the prediction is
checked against the actual finalized set: on a hit the prefetched shrink
and next-part plan are adopted (byte-identical to the sequential fold,
because every divide pass is deterministic and the masks coincide); on a
miss everything speculative is discarded and recomputed synchronously,
exactly as the sequential path would. ``overlap=True`` therefore changes
wall-clock only — coreness is byte-identical to ``overlap=False``.

**Per-part checkpointing.** The paper's headline stability claim (136B
edges, 27.5h runs) only holds if a failed part does not forfeit the parts
already decomposed. The loop state between parts is an explicit
:class:`PipelineState`; with ``checkpoint_dir`` set it is saved atomically
after every part, and ``resume=True`` re-enters at the first unfinished
part:

* the checkpoint holds the *host merge state* — coreness, the finalized
  mask, ``ext`` of the remaining nodes, the remaining-id map, the
  threshold cursor and the per-part reports (JSON extra);
* it deliberately does NOT hold the remaining graph or any device tiles —
  the remaining graph is recomputed from the original graph and the
  finalized mask (induced-subgraph composition is byte-stable), and parts
  rebuild their tiles anyway;
* a killed run leaves at most a ``step_*.tmp`` directory, which restore
  ignores — resume always starts from the last *complete* part boundary
  and reproduces byte-identical coreness (every stage is deterministic).
  An *async* save that was still in flight at the crash either fully
  landed (write-then-rename) or is ignored as ``.tmp`` — same guarantee.
  When the crash is an exception (the fault-injection tests), the
  pipeline drains pending saves and joins its prefetch worker before
  re-raising, so the on-disk state at "crash" time is deterministic.

**Sweep-granularity checkpointing.** A part boundary is a coarse resume
unit — a part at paper scale sweeps for hours. ``sweep_checkpoint_every=k``
saves a :class:`SweepSnapshot` (the conquer engine's estimate vector, fed
by its ``on_sweep`` hook) every ``k`` sweeps through the same atomic
``CheckpointManager`` path under ``<checkpoint_dir>/sweeps``; resume then
re-enters *mid-part* at the last completed sweep via ``init_coreness`` —
the fixed point is exact from any valid upper bound, so the final coreness
stays byte-identical. Stale or half-written snapshots are detected
(cursor/fingerprint/plan/part-size validation) and resume falls back to
the part boundary; snapshots of a finished part are purged at its
boundary save, so disk stays bounded at one state + one snapshot.

**Divide transient.** All extraction passes between parts run chunked
(``divide_chunk`` adjacency slots, default
:data:`~repro.graph.build.DEFAULT_DIVIDE_CHUNK_SLOTS`), so the host
transient of the divide step is bounded by the chunk budget — never by
the edge count — and each part reports its observed peak. The prefetch
worker uses its own :class:`~repro.graph.build.DivideStats` instance
(folded into the part's via :meth:`DivideStats.merge`), so the worker and
the main thread share no mutable state.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
import logging
import os
import re
import shutil
import time
import zlib
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.core.decompose import DecomposeResult, decompose
from repro.core.divide import timed_candidates
from repro.core.spans import (
    SpanRecord,
    StageTime,
    bound,
    count,
    current,
    recording,
    span,
    stage_seconds,
)
from repro.graph.build import (
    DivideStats,
    _resolve_chunk_slots,
    bucketize,
    external_info,
    induced_subgraph,
)
from repro.graph.reorder import bitmap_density, reorder_graph
from repro.graph.structs import BucketedGraph, Graph

STATE_FORMAT = 1
SWEEP_FORMAT = 1

# The prefetch worker thread carries this name prefix; the test suite
# asserts none outlive a test (a leaked thread = a missing close()).
PREFETCH_THREAD_PREFIX = "dckcore-prefetch"


class MergeIncompleteError(RuntimeError):
    """The final merge left nodes without a coreness value.

    This is the pipeline's last correctness gate (every node must be
    finalized by exactly one part); a bare ``assert`` here would vanish
    under ``python -O`` and let a broken merge return garbage silently.
    """


def graph_fingerprint(g: Graph) -> Dict[str, int]:
    """Cheap identity of a graph for checkpoint/resume validation: node and
    edge counts plus a CRC of the degree sequence. O(n), no edge traversal —
    collisions require an identical degree sequence, at which point the
    resume-time remaining-id assertion is the backstop."""
    deg = np.ascontiguousarray(g.degrees, dtype=np.int64)
    return {
        "n_nodes": int(g.n_nodes),
        "n_edges": int(g.n_edges),
        "deg_crc32": int(zlib.crc32(deg.tobytes())),
    }


def _clear_checkpoints(path: str) -> None:
    """Remove every step dir (half-written ``.tmp`` and quarantined
    ``.corrupt`` included) under ``path`` — a fresh run must not leave
    stale higher-numbered steps from a previous run for a later
    ``resume=True`` to pick up. Only safe when no async save targets
    ``path``; live managers purge via ``clear_steps``."""
    if not os.path.isdir(path):
        return
    for d in os.listdir(path):
        if re.fullmatch(r"step_\d+(\.tmp|\.corrupt)?", d):
            shutil.rmtree(os.path.join(path, d), ignore_errors=True)


@dataclasses.dataclass
class PartReport:
    name: str
    threshold: Optional[int]
    n_nodes: int
    n_edges: int
    iterations: int
    comm_amount: int
    peak_bytes: int
    extract_time_s: float
    decompose_time_s: float
    finalized: int
    # Work metric (active-frontier scheduling): rows actually gathered +
    # h-indexed across all sweeps, vs what always-full sweeps would gather.
    gathered_rows: int = 0
    full_sweep_rows: int = 0
    active_rows_per_iter: List[int] = dataclasses.field(default_factory=list)
    # Measured per-device collective bytes across the part's sweeps (0 for
    # the single-device engine — it issues no collectives).
    collective_bytes: int = 0
    # Fraction of set bits in the part's bucket-adjacency bitmap: how often
    # the static frontier filter could NOT rule out a tile (lower = sparser
    # = locality-aware reordering worked).
    bitmap_density: float = 1.0
    # Seconds the pipeline was BLOCKED on this part's boundary save (the
    # full save on the blocking path; wait-out-previous + host snapshot on
    # the async path). 0 when checkpointing is disabled.
    save_time_s: float = 0.0
    # Wall seconds of the COMPLETED boundary save (write + rename + GC),
    # stamped by the checkpoint manager when the write lands — on the
    # async path this is the honest persistence cost, most of it hidden
    # behind the next part's sweeps.
    save_wall_s: float = 0.0
    # Peak transient host bytes of the part's divide passes (candidate
    # extraction + induced subgraph + ext fold + shrink), bounded by the
    # chunk budget — see repro.graph.build.DivideStats.
    divide_transient_bytes: int = 0
    # Sweep number the part's conquer was warm-restarted at from a
    # sweep-granularity snapshot (0 = started from scratch).
    resumed_at_sweep: int = 0
    # True when this part's divide ran speculatively on the prefetch
    # worker (and the speculation was adopted).
    prefetched: bool = False
    # Part-parallel placement (``dc_kcore(part_parallel=...)``): which mesh
    # slice conquered this part, which wave it ran in, and the scheduler's
    # modeled cost (collective + HBM bytes) that placed it. Defaults mark
    # the sequential path (and keep old checkpoints restorable).
    slice_index: int = -1
    wave: int = -1
    modeled_cost_bytes: int = 0
    # Failed conquer attempts of this part that were retried by the wave
    # executor's fault-tolerance layer (0 on the fail-fast default path).
    retries: int = 0


@dataclasses.dataclass
class DCKCoreReport:
    parts: List[PartReport]
    total_time_s: float
    preprocess_time_s: float
    resumed_parts: int = 0  # parts restored from checkpoint, not re-run
    overlap: bool = False   # divide/checkpoint overlapped with conquer?
    prefetch_hits: int = 0    # speculative shrinks adopted
    prefetch_misses: int = 0  # speculative shrinks discarded + recomputed
    # Part-parallel conquer (0 = sequential): slice count, wall seconds the
    # wave executor was running, per-slice busy seconds (sweep wall summed
    # over the slice's parts), speculative conquers discarded after a
    # mispredicted wave, and the collective bytes the device-resident E(v)
    # boundary folds moved (0 when the fold ran on the host).
    part_parallel: int = 0
    conquer_wall_s: float = 0.0
    slice_busy_s: List[float] = dataclasses.field(default_factory=list)
    speculation_discards: int = 0
    boundary_exchange_bytes: int = 0
    # Fault-tolerance accounting (dc_kcore(slice_timeout_s=/max_retries=)):
    # failed conquer attempts that were retried, slices blacklisted after
    # exhausting their retries (or hanging past the watchdog timeout),
    # waves that finished on fewer slices than planned, checkpoint steps
    # quarantined as corrupt during restore, and the raw event log
    # (retry/blacklist/replan/quarantine entries, in order).
    retries: int = 0
    blacklisted_slices: List[int] = dataclasses.field(default_factory=list)
    degraded_waves: int = 0
    quarantined_steps: int = 0
    fault_events: List[dict] = dataclasses.field(default_factory=list)
    # The run's program spans (repro.core.spans), from every thread of it.
    spans: List[SpanRecord] = dataclasses.field(default_factory=list)

    def stage_seconds(self) -> Dict[str, StageTime]:
        """Total seconds, self seconds and count of the run's spans, by
        span name (``kcore.part``, ``kcore.divide.extract``, ``kcore.sweep``
        ...)."""
        return stage_seconds(self.spans)

    @property
    def total_comm(self) -> int:
        return sum(p.comm_amount for p in self.parts)

    @property
    def peak_bytes(self) -> int:
        return max((p.peak_bytes for p in self.parts), default=0)

    @property
    def total_iterations(self) -> int:
        return sum(p.iterations for p in self.parts)

    @property
    def total_gathered_rows(self) -> int:
        """Total sweep work across parts (frontier-scheduled)."""
        return sum(p.gathered_rows for p in self.parts)

    @property
    def total_full_sweep_rows(self) -> int:
        """Work the always-full-sweep schedule would have done."""
        return sum(p.full_sweep_rows for p in self.parts)

    @property
    def total_collective_bytes(self) -> int:
        """Measured per-device collective bytes summed over all parts."""
        return sum(p.collective_bytes for p in self.parts)

    @property
    def total_save_time_s(self) -> float:
        """Wall time the pipeline was BLOCKED on per-part checkpoint saves
        (the full save cost when saves are blocking; near zero when async)."""
        return sum(p.save_time_s for p in self.parts)

    @property
    def total_save_wall_s(self) -> float:
        """Wall time of the COMPLETED per-part saves — the honest cost of
        persisting, whether or not the pipeline waited for it."""
        return sum(p.save_wall_s for p in self.parts)

    @property
    def total_decompose_time_s(self) -> float:
        """Wall time the conquer engine was actually sweeping."""
        return sum(p.decompose_time_s for p in self.parts)

    @property
    def idle_fraction(self) -> float:
        """Host share of the run's wall clock spent outside the conquer
        engine's calls (divide passes, bucketize, checkpoint saves, merge)
        — the stall ``overlap=True`` exists to shrink. It is host time over
        host time: the device's idle share comes only from a profiler
        trace."""
        if self.total_time_s <= 0:
            return 0.0
        return max(0.0, 1.0 - self.total_decompose_time_s / self.total_time_s)

    @property
    def slice_utilization(self) -> List[float]:
        """Per-slice busy fraction of the wave executor's wall clock —
        how evenly the LPT schedule filled the slices (empty when
        sequential)."""
        if self.conquer_wall_s <= 0:
            return [0.0 for _ in self.slice_busy_s]
        return [min(1.0, b / self.conquer_wall_s) for b in self.slice_busy_s]


@dataclasses.dataclass
class PipelineState:
    """Host state of a DC-kCore run at a part boundary — the checkpoint unit.

    ``parts_done`` is the RNG-free cursor: how many thresholds of the
    (descending, deduplicated) plan have been consumed. ``complete`` marks
    that the final "rest" part also finished — a resume of a complete state
    returns the stored result without touching the graph.
    """

    coreness: np.ndarray       # [n] int32, -1 where unfinalized
    finalized: np.ndarray      # [n] bool
    ext_remaining: np.ndarray  # [n_remaining] int32, remaining-local order
    remaining_ids: np.ndarray  # [n_remaining] int64, remaining-local -> orig
    thresholds: List[int]      # the descending plan (consistency-checked)
    fingerprint: Dict[str, int] = dataclasses.field(default_factory=dict)
    parts_done: int = 0
    complete: bool = False
    reports: List[PartReport] = dataclasses.field(default_factory=list)

    @staticmethod
    def fresh(g: Graph, thresholds: Sequence[int]) -> "PipelineState":
        n_nodes = g.n_nodes
        return PipelineState(
            coreness=np.full(n_nodes, -1, dtype=np.int32),
            finalized=np.zeros(n_nodes, dtype=bool),
            ext_remaining=np.zeros(n_nodes, dtype=np.int32),
            remaining_ids=np.arange(n_nodes, dtype=np.int64),
            thresholds=[int(t) for t in thresholds],
            fingerprint=graph_fingerprint(g),
        )

    # -- checkpoint wire format ----------------------------------------- #
    def arrays(self) -> dict:
        """The array pytree saved per part (scalars/reports ride in extra)."""
        return {
            "coreness": self.coreness,
            "finalized": self.finalized,
            "ext_remaining": self.ext_remaining,
            "remaining_ids": self.remaining_ids,
        }

    def extra(self) -> dict:
        return {
            "format": STATE_FORMAT,
            "parts_done": int(self.parts_done),
            "complete": bool(self.complete),
            "thresholds": [int(t) for t in self.thresholds],
            "fingerprint": dict(self.fingerprint),
            "reports": [dataclasses.asdict(p) for p in self.reports],
        }

    def save(
        self,
        checkpoint_dir: str,
        manager=None,
        blocking: bool = True,
        on_done: Optional[Callable[[int, float], None]] = None,
    ) -> float:
        """Atomic save at the current part boundary; returns the wall
        seconds the caller was blocked (the full save when ``blocking``,
        wait-out-previous + host snapshot when async).

        Step number = parts completed so far (the rest part counts one
        past the last threshold), so ``latest_step`` is the cursor. A
        part's own save timings are only known after (or, async, *while*)
        its save runs, so they are persisted one boundary later (the next
        save serializes the updated report); the final part's save cost
        exists only in the live report.

        ``manager`` lets the pipeline reuse one persistent
        :class:`~repro.ckpt.CheckpointManager` (required for async saves —
        something must stay alive to be waited on); without it a throwaway
        blocking manager is used. The previous in-flight save is waited
        out *before* ``extra()`` serializes the reports, so a pending
        ``on_done`` stamping the previous report's completed-save time
        always lands first. Restore reads the newest step that passes
        integrity checks, so retention is the manager's ``retain``
        (default 2): the latest boundary plus one predecessor a corrupted
        latest can fall back to — disk stays bounded at ``retain``
        checkpoints (the state arrays are O(n); at paper scale a P-part
        run must not hold P of them). A crash between rename and prune
        leaves one extra step; resume still picks the newest intact."""
        from repro.ckpt import CheckpointManager

        if manager is None:
            manager = CheckpointManager(checkpoint_dir)
            blocking = True
        t0 = time.perf_counter()
        manager.wait()
        step = self.parts_done + (1 if self.complete else 0)
        manager.save(
            self.arrays(), step, extra=self.extra(),
            blocking=blocking, on_done=on_done,
        )
        return time.perf_counter() - t0

    @staticmethod
    def restore(checkpoint_dir: str, n_nodes: int,
                events: Optional[List[dict]] = None) -> Optional["PipelineState"]:
        """Latest *intact* checkpoint under ``checkpoint_dir`` (``None`` if
        there is none — half-written ``step_*.tmp`` dirs are ignored by
        :func:`repro.ckpt.latest_step`). A corrupt step (CRC mismatch, bit
        rot) is quarantined to ``step_*.corrupt`` and restore falls back
        to the previous retained step; ``events`` (if given) collects one
        ``{"event": "quarantine", ...}`` record per quarantined step for
        the run report."""
        from repro.ckpt import latest_step, restore_pytree_with_fallback

        if latest_step(checkpoint_dir) is None:
            return None
        template = {
            "coreness": np.zeros(0, np.int32),
            "finalized": np.zeros(0, bool),
            "ext_remaining": np.zeros(0, np.int32),
            "remaining_ids": np.zeros(0, np.int64),
        }

        def on_corrupt(step, exc):
            if events is not None:
                events.append({
                    "event": "quarantine", "path": checkpoint_dir,
                    "step": int(step), "error": str(exc),
                })

        try:
            arrays, _step, extra = restore_pytree_with_fallback(
                checkpoint_dir, template, on_corrupt=on_corrupt
            )
        except FileNotFoundError:
            # Every step was corrupt (all quarantined): resume from scratch
            # — the part boundary discipline's last fallback.
            return None
        if extra.get("format") != STATE_FORMAT:
            raise ValueError(
                f"checkpoint format {extra.get('format')!r} != {STATE_FORMAT}"
            )
        if arrays["coreness"].shape[0] != n_nodes:
            raise ValueError(
                f"checkpoint is for a {arrays['coreness'].shape[0]}-node graph, "
                f"got {n_nodes} nodes"
            )
        return PipelineState(
            coreness=arrays["coreness"],
            finalized=arrays["finalized"],
            ext_remaining=arrays["ext_remaining"],
            remaining_ids=arrays["remaining_ids"],
            thresholds=[int(t) for t in extra["thresholds"]],
            fingerprint={k: int(v) for k, v in extra["fingerprint"].items()},
            parts_done=int(extra["parts_done"]),
            complete=bool(extra["complete"]),
            reports=[PartReport(**r) for r in extra["reports"]],
        )


def _sweep_dir(checkpoint_dir: str) -> str:
    return os.path.join(checkpoint_dir, "sweeps")


@dataclasses.dataclass
class SweepSnapshot:
    """Mid-part checkpoint: one conquer sweep's coreness estimates.

    The conquer engines' fixed point is restartable from ANY valid upper
    bound of the true coreness, so a snapshot of the estimate vector taken
    by the ``on_sweep`` hook is a complete mid-part resume point: re-enter
    the part with ``init_coreness=snapshot`` and the remaining sweeps run
    to the same (exact) fixed point — final coreness is byte-identical to
    the uninterrupted run no matter where the crash landed.

    Saved through the same atomic ``CheckpointManager`` path as
    :class:`PipelineState`, under ``<checkpoint_dir>/sweeps`` with the
    sweep number as the step (monotone across crash/resume cycles: a
    resumed part offsets its sweep numbering by the restored snapshot's),
    retention = the manager's ``retain`` (default 2, so a corrupt latest
    snapshot falls back to its predecessor — any snapshot is a valid
    upper bound, so an older one is merely a slower resume point, never a
    wrong one). A snapshot is only *valid* for the part it was taken in:
    restore checks the pipeline cursor, graph fingerprint, threshold plan
    and part size, and anything stale — a snapshot from an already-finished
    part, another run, or a half-written ``.tmp`` — is ignored, falling
    back to the part-boundary checkpoint. Snapshots of a finished part are
    purged at its boundary save, so disk stays bounded at one snapshot.

    ``coreness`` is numpy int32 in **part-local original-id order** (what
    ``on_sweep`` hands out), so a snapshot taken under one engine, node
    ordering or tile policy restarts correctly under any other.
    """

    coreness: np.ndarray       # [n_part] int32, part-local original order
    parts_done: int            # pipeline cursor when taken
    sweep: int                 # sweep number within the part
    n_part: int
    threshold: Optional[int]   # None for the rest part
    thresholds: List[int]
    fingerprint: Dict[str, int]

    # Step numbering must be monotone across the WHOLE run, not just within
    # a part: the CheckpointManager retains the highest-numbered steps,
    # so if a later part's snapshots restarted at step 1, one stale
    # higher-numbered snapshot surviving a crash between a boundary save
    # and the sweeps purge would win the GC and silently swallow every new
    # save. parts_done-major, sweep-minor ordering closes that window.
    _PART_STRIDE = 1 << 40

    @property
    def step(self) -> int:
        return self.parts_done * SweepSnapshot._PART_STRIDE + self.sweep

    def save(
        self,
        sweep_dir: str,
        manager=None,
        blocking: bool = True,
        on_done: Optional[Callable[[int, float], None]] = None,
    ) -> float:
        """Save the snapshot; returns seconds the caller was blocked.

        ``manager`` reuses a persistent :class:`CheckpointManager` (the
        overlapped pipeline's async path — the save runs on the manager's
        thread while the part keeps sweeping); without it a throwaway
        blocking manager is used."""
        from repro.ckpt import CheckpointManager

        if manager is None:
            manager = CheckpointManager(sweep_dir)
            blocking = True
        t0 = time.perf_counter()
        extra = {
            "format": SWEEP_FORMAT,
            "parts_done": int(self.parts_done),
            "sweep": int(self.sweep),
            "n_part": int(self.n_part),
            "threshold": None if self.threshold is None else int(self.threshold),
            "thresholds": [int(t) for t in self.thresholds],
            "fingerprint": dict(self.fingerprint),
        }
        manager.save(
            {"part_coreness": np.asarray(self.coreness, dtype=np.int32)},
            self.step, extra=extra, blocking=blocking, on_done=on_done,
        )
        return time.perf_counter() - t0

    @staticmethod
    def restore(sweep_dir: str,
                events: Optional[List[dict]] = None) -> Optional["SweepSnapshot"]:
        """Latest intact snapshot under ``sweep_dir``; ``None`` when there
        is none or it is unreadable/from another format — sweep snapshots
        are an optimization, so a bad one degrades to part-boundary resume
        instead of failing the run. A *corrupt* snapshot (CRC mismatch) is
        quarantined to ``.corrupt`` and the previous retained one is tried
        first — any snapshot is a valid upper bound, so falling back one
        step is still an exact resume point. The degradation is logged
        (one line, path + reason) so a resume that unexpectedly fell back
        to the part boundary is diagnosable; ``events`` collects one
        quarantine record per corrupt step."""
        from repro.ckpt import latest_step, restore_pytree_with_fallback

        if latest_step(sweep_dir) is None:
            return None

        def on_corrupt(step, exc):
            if events is not None:
                events.append({
                    "event": "quarantine", "path": sweep_dir,
                    "step": int(step), "error": str(exc),
                })

        try:
            arrays, _step, extra = restore_pytree_with_fallback(
                sweep_dir, {"part_coreness": np.zeros(0, np.int32)},
                on_corrupt=on_corrupt,
            )
        except FileNotFoundError:
            return None  # every snapshot corrupt — part-boundary resume
        except Exception as exc:
            logging.getLogger(__name__).warning(
                "sweep snapshot %s unreadable (%s: %s) — resuming from the "
                "part boundary instead", sweep_dir, type(exc).__name__, exc,
            )
            return None
        if extra.get("format") != SWEEP_FORMAT:
            logging.getLogger(__name__).warning(
                "sweep snapshot %s has format %r (expected %r) — resuming "
                "from the part boundary instead",
                sweep_dir, extra.get("format"), SWEEP_FORMAT,
            )
            return None
        return SweepSnapshot(
            coreness=arrays["part_coreness"],
            parts_done=int(extra["parts_done"]),
            sweep=int(extra["sweep"]),
            n_part=int(extra["n_part"]),
            threshold=(None if extra["threshold"] is None else int(extra["threshold"])),
            thresholds=[int(t) for t in extra["thresholds"]],
            fingerprint={k: int(v) for k, v in extra["fingerprint"].items()},
        )

    def matches(self, state: "PipelineState", cursor: int,
                n_part: int, threshold: Optional[int]) -> bool:
        """Is this snapshot a resume point for the part about to run?"""
        return (
            self.parts_done == cursor
            and self.n_part == n_part == self.coreness.shape[0]
            and self.threshold == threshold
            and self.thresholds == state.thresholds
            and self.fingerprint == state.fingerprint
        )


# Conquer-engine adapter. Called as ``fn(bg)`` normally; when
# ``dc_kcore(sweep_checkpoint_every=...)`` is set it is called as
# ``fn(bg, init_coreness=..., on_sweep=...)`` — a custom engine must accept
# those kwargs (both built-in engines and make_distributed_decompose do;
# a plain ``lambda bg: ...`` only works without sweep checkpointing).
DecomposeFn = Callable[..., DecomposeResult]
PartHook = Callable[[int, PartReport], None]
SweepSavedHook = Callable[[int, int, float], None]


@dataclasses.dataclass
class PartPlan:
    """Divide-stage output: everything the conquer stage needs for one part.

    ``threshold is None`` marks the final "rest" part (everything left,
    no candidate mask). ``part_g is None`` marks an *empty* threshold part
    (no candidates at this threshold — the cursor advances, nothing runs).
    ``speculative`` records that the plan was built by the prefetch worker
    on the *predicted* remaining graph; it is only ever executed after the
    prediction was validated.
    """

    cursor: int
    name: str
    threshold: Optional[int]
    part_g: Optional[Graph]
    part_local_ids: Optional[np.ndarray]
    part_ext: Optional[np.ndarray]
    cand_mask: Optional[np.ndarray]
    dstats: DivideStats
    extract_time_s: float
    bg: Optional[BucketedGraph] = None
    bucketize_time_s: float = 0.0
    speculative: bool = False

    @property
    def is_rest(self) -> bool:
        return self.threshold is None

    @property
    def is_empty(self) -> bool:
        return self.part_g is None


@dataclasses.dataclass
class _Prefetch:
    """Prefetch-worker output: the speculative shrink of the remaining
    graph (assuming every candidate of part ``base_cursor`` finalizes)
    plus, when there is one, the next part's plan built on that shrink."""

    base_cursor: int
    shrink_graph: Graph
    shrink_keep_ids: np.ndarray   # remaining-local ids kept by the shrink
    ext_next: np.ndarray          # ext of the kept nodes after the fold
    shrink_stats: DivideStats
    shrink_time_s: float
    plan: Optional[PartPlan] = None


# A ``kcore.part`` span opens with these counts, so that the executables
# JAX builds while it is open are counted on it (repro.core.spans).
_PART_COUNTS = {"compiles": 0, "compile_ms": 0.0}


def _count_part(plan: PartPlan) -> None:
    """Size and threshold of ``plan``'s part, on the open ``kcore.part``
    span (no threshold on the rest part)."""
    g = plan.part_g
    count("n_nodes", 0 if g is None else g.n_nodes)
    count("n_edges", 0 if g is None else g.n_edges)
    if plan.threshold is not None:
        count("threshold", plan.threshold)


def _recorded(run):
    """Run ``run`` (:func:`dc_kcore`) under a fresh span recorder, inside
    a ``kcore.job`` span, and put the records on its report."""

    @functools.wraps(run)
    def wrapper(*args, **kwargs):
        with recording() as recorder:
            with span("kcore.job"):
                core, report = run(*args, **kwargs)
        report.spans = recorder.records
        return core, report

    return wrapper


class _PartPipeline:
    """The staged scheduler behind :func:`dc_kcore`.

    One instance per run. The main thread owns ``state`` and the conquer
    stage; the (optional, single) prefetch worker only ever READS the
    graph/ext snapshots passed to it at submit time — the main thread
    rebinds ``state.ext_remaining`` / ``state.remaining_ids`` /
    ``self.remaining_graph`` to fresh arrays instead of mutating them, so
    a worker holding the old references is always safe. Checkpoint I/O
    lives on the two persistent managers; ``close()`` drains both and
    joins the worker on every exit path (success or crash), which is what
    makes the fault-injection tests deterministic.
    """

    def __init__(
        self, *,
        state: PipelineState,
        remaining_graph: Graph,
        thresholds: List[int],
        strategy: str,
        decompose_fn: DecomposeFn,
        row_align: int,
        reorder: str,
        max_bucket_rows,
        reorder_sample_edges: Optional[int],
        checkpoint_dir: Optional[str],
        sweep_dir: Optional[str],
        divide_chunk: Optional[int],
        sweep_checkpoint_every: Optional[int],
        on_part_done: Optional[PartHook],
        on_sweep_saved: Optional[SweepSavedHook],
        overlap: bool,
        pending_snap: Optional[SweepSnapshot],
        state_mgr=None,
        sweeps_mgr=None,
        part_parallel: Optional[int] = None,
        slice_decomposes: Optional[List[DecomposeFn]] = None,
        slice_specs: Optional[list] = None,
        fold_plan=None,
        watchdog=None,
        fault_plan=None,
    ):
        self.state = state
        self.remaining_graph = remaining_graph
        self.thresholds = thresholds
        self.strategy = strategy
        self.decompose_fn = decompose_fn
        self.row_align = row_align
        self.reorder = reorder
        self.max_bucket_rows = max_bucket_rows
        self.reorder_sample_edges = reorder_sample_edges
        self.checkpoint_dir = checkpoint_dir
        self.sweep_dir = sweep_dir
        self.divide_chunk = divide_chunk
        self.sweep_checkpoint_every = sweep_checkpoint_every
        self.on_part_done = on_part_done
        self.on_sweep_saved = on_sweep_saved
        self.overlap = overlap
        self.pending_snap = pending_snap
        self.state_mgr = state_mgr
        self.sweeps_mgr = sweeps_mgr

        # Part-parallel conquer: slice count, one DecomposeFn per mesh
        # slice (None = every slice thread shares ``decompose_fn``), the
        # pure SliceSpecs the scheduler prices against, and the GLOBAL
        # MeshPlan routing the E(v) boundary fold through the device
        # collectives (None = host fold).
        self.part_parallel = part_parallel
        self.slice_decomposes = slice_decomposes
        self.slice_specs = slice_specs
        self.fold_plan = fold_plan
        self.slice_busy_s = [0.0] * (part_parallel or 0)
        self.conquer_wall_s = 0.0
        self.boundary_exchange_bytes = 0
        self.speculation_discards = 0
        self._wave_index = 0

        # Fault tolerance: the wave watchdog config (None = fail-fast, the
        # historical semantics), the chaos-injection plan consulted at the
        # named sites, slices blacklisted so far (they stay dead for the
        # rest of the run — wave width shrinks S -> S-1 -> ... -> 1), and
        # the accumulated retry/blacklist/replan event accounting.
        self.watchdog = watchdog
        self.fault_plan = fault_plan
        self.blacklisted: set = set()
        self.retries = 0
        self.replans = 0
        self.degraded_waves = 0
        self.fault_events: List[dict] = []

        self.parts: List[PartReport] = state.reports
        self.preprocess_time_s = 0.0
        # The run's span recorder, handed to the worker threads.
        self.recorder = current()
        self.prefetch_hits = 0
        self.prefetch_misses = 0
        self._future: Optional[concurrent.futures.Future] = None
        self._executor: Optional[concurrent.futures.ThreadPoolExecutor] = None
        if overlap:
            self._executor = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix=PREFETCH_THREAD_PREFIX
            )

    def _visit_fault(self, site: str, **ctx) -> None:
        """Chaos hook: consult the fault plan at a named site (no-op
        without one). Faults at main-thread sites (``boundary_fold``,
        ``checkpoint_save``, ``prefetch``) are fail-fast — they kill the
        run like a real crash would, and recovery is the resume path;
        only ``slice_conquer`` faults (visited inside the wave executor)
        are retried/re-planned in-run."""
        if self.fault_plan is not None:
            self.fault_plan.visit(site, **ctx)

    # ---------------- divide stage ---------------- #
    def _fresh_stats(self) -> DivideStats:
        return DivideStats(chunk_slots=_resolve_chunk_slots(self.divide_chunk))

    def _plan_on(self, graph: Graph, ext: np.ndarray, cursor: int,
                 speculative: bool = False) -> Optional[PartPlan]:
        """Divide: plan the part at ``cursor`` on ``graph``/``ext``. Pure —
        runs on either the main thread (synchronous path) or the prefetch
        worker (``speculative=True``, on the predicted shrink)."""
        if cursor < len(self.thresholds):
            t = self.thresholds[cursor]
            dstats = self._fresh_stats()
            cand_mask, extract_time = timed_candidates(
                graph, ext, t, self.strategy,
                chunk_slots=self.divide_chunk, stats=dstats,
            )
            if not cand_mask.any():
                return PartPlan(
                    cursor=cursor, name=f"core>={t}", threshold=t,
                    part_g=None, part_local_ids=None, part_ext=None,
                    cand_mask=cand_mask, dstats=dstats,
                    extract_time_s=extract_time, speculative=speculative,
                )
            with span("kcore.divide.extract") as sp:
                part_g, part_local_ids = induced_subgraph(
                    graph, cand_mask, chunk_slots=self.divide_chunk,
                    stats=dstats,
                )
                part_ext = ext[cand_mask]
            extract_time += sp.seconds
            return PartPlan(
                cursor=cursor, name=f"core>={t}", threshold=t,
                part_g=part_g, part_local_ids=part_local_ids,
                part_ext=part_ext, cand_mask=cand_mask, dstats=dstats,
                extract_time_s=extract_time, speculative=speculative,
            )
        # Final (bottom) part: everything left.
        if graph.n_nodes == 0:
            return None
        return PartPlan(
            cursor=cursor, name="rest", threshold=None,
            part_g=graph, part_local_ids=None, part_ext=ext,
            cand_mask=None, dstats=self._fresh_stats(),
            extract_time_s=0.0, speculative=speculative,
        )

    def _build_plan(self, cursor: int) -> Optional[PartPlan]:
        """Synchronous divide on the CURRENT remaining graph."""
        return self._plan_on(
            self.remaining_graph, self.state.ext_remaining, cursor
        )

    def _bucketize(self, plan: PartPlan) -> None:
        """Reorder + bucketize the part — the device-layout half of the
        divide stage (prefetched plans arrive with ``bg`` already built)."""
        if plan.bg is not None or plan.part_g is None:
            return
        # Reorder the part, not the whole graph: each part is a fresh id
        # space, and locality only has to hold within the tiles actually
        # decomposed together. part_ext stays in part-local original order;
        # bucketize permutes it in and the engine un-permutes coreness out.
        with span("kcore.divide.bucketize") as sp:
            plan.bg = bucketize(
                reorder_graph(
                    plan.part_g, self.reorder,
                    sample_edges=self.reorder_sample_edges,
                ),
                ext=plan.part_ext, row_align=self.row_align,
                max_bucket_rows=self.max_bucket_rows,
            )
        plan.bucketize_time_s = sp.seconds

    # ---------------- prefetch stage ---------------- #
    def _submit_prefetch(self, plan: PartPlan) -> None:
        """Speculate past ``plan``'s conquer on the worker thread: shrink
        the remaining graph as if EVERY candidate finalizes (exact by
        construction for Exact-Divide, a bet for Rough) and build the next
        part's plan on the predicted shrink. The worker gets the current
        array references; the main thread only ever rebinds them."""
        if self._executor is None or plan.is_rest or plan.is_empty:
            return
        assert self._future is None, "a prefetch is already in flight"
        self._future = self._executor.submit(
            self._prefetch_task,
            self.remaining_graph, self.state.ext_remaining,
            plan.cand_mask, plan.cursor,
        )

    def _fold_external(self, graph: Graph, keep_local: np.ndarray,
                       upper_local: np.ndarray, stats: DivideStats) -> np.ndarray:
        """E(v) boundary fold — host pass, or device collectives when the
        pipeline holds a global mesh plan (part-parallel distributed mode).
        Bit-identical either way (differentially tested); the device path
        additionally accounts its psum bytes. Only ever called from the
        thread that owns ``stats`` — the byte counter is main-thread-only
        because the prefetch worker never runs with a fold plan (overlap
        and part_parallel are mutually exclusive)."""
        self._visit_fault("boundary_fold", n_nodes=int(graph.n_nodes))
        if self.fold_plan is not None:
            from repro.core.distributed import device_external_info

            delta, moved = device_external_info(
                graph, keep_local, upper_local, self.fold_plan,
                chunk_slots=self.divide_chunk, stats=stats,
            )
            self.boundary_exchange_bytes += moved
            return delta
        return external_info(
            graph, keep_local, upper_local,
            chunk_slots=self.divide_chunk, stats=stats,
        )

    def _speculative_shrink(self, graph: Graph, ext: np.ndarray,
                            cand_mask: np.ndarray, cursor: int) -> _Prefetch:
        """Shrink ``graph`` as if EVERY candidate of part ``cursor``
        finalizes — the shared speculation body of the overlap prefetch
        (depth 1, worker thread) and the part-parallel wave planner
        (depth ``part_parallel``, main thread)."""
        with span("kcore.divide.fold") as sp:
            stats = self._fresh_stats()
            keep_local = ~cand_mask
            ext_delta = self._fold_external(graph, keep_local, cand_mask,
                                            stats)
            shrink_graph, keep_ids = induced_subgraph(
                graph, keep_local, chunk_slots=self.divide_chunk, stats=stats
            )
            ext_next = ext[keep_local] + ext_delta
        return _Prefetch(
            base_cursor=cursor, shrink_graph=shrink_graph,
            shrink_keep_ids=keep_ids, ext_next=ext_next,
            shrink_stats=stats, shrink_time_s=sp.seconds,
        )

    def _prefetch_task(self, graph: Graph, ext: np.ndarray,
                       cand_mask: np.ndarray, cursor: int) -> _Prefetch:
        with bound(self.recorder):
            self._visit_fault("prefetch", cursor=cursor)
            pf = self._speculative_shrink(graph, ext, cand_mask, cursor)
            pf.plan = self._plan_on(
                pf.shrink_graph, pf.ext_next, cursor + 1, speculative=True
            )
            if pf.plan is not None:
                self._bucketize(pf.plan)
            return pf

    def _take_prefetch(self, cursor: int) -> Optional[_Prefetch]:
        """Join the in-flight prefetch (if any). Worker failures re-raise
        here — a broken divide pass is a real failure, not a missed bet."""
        if self._future is None:
            return None
        fut, self._future = self._future, None
        pf = fut.result()
        return pf if pf.base_cursor == cursor else None

    # ---------------- conquer stage ---------------- #
    def _conquer(self, plan: PartPlan, fn: Optional[DecomposeFn] = None,
                 lead: bool = True, account: bool = True, heartbeat=None):
        """Conquer one part. ``fn`` overrides the engine (a wave slice's
        decompose); ``lead=False`` (a wave's non-first parts) skips the
        pending-snapshot consult and the sweep-snapshot hook — only the
        part the boundary checkpoint actually points at may write
        snapshots, so a crashed wave leaves exactly the disk state a
        sequential run crashed in that part would. ``account=False``
        defers the preprocess-time accounting to the caller (the wave
        runner books it on the main thread — slice threads must not race
        on the counter). ``heartbeat`` (watchdog mode) is a zero-arg
        liveness callable composed into the engine's ``on_sweep`` hook —
        progress = sweep count, exactly what the watchdog times out on."""
        state = self.state
        t0 = time.perf_counter()
        init = None
        start_sweep = 0
        if lead and self.pending_snap is not None:
            snap = self.pending_snap
            if snap.matches(state, plan.cursor, plan.part_g.n_nodes,
                            plan.threshold):
                init = snap.coreness
                start_sweep = snap.sweep
            else:
                # Stale (e.g. a crash landed between a boundary save and
                # the sweeps purge): remove it so it cannot shadow this
                # run's snapshots on a later resume.
                self._purge_sweeps()
            # One shot either way: a snapshot can only belong to the first
            # part a resumed run executes; anything else is stale.
            self.pending_snap = None
        hook = None
        if lead and self.sweep_checkpoint_every is not None:
            every = max(1, int(self.sweep_checkpoint_every))
            last_saved = {"c": None if init is None else np.asarray(init)}

            def hook(it, coreness, _cursor=plan.cursor,
                     _threshold=plan.threshold, _n=plan.part_g.n_nodes,
                     _start=start_sweep, _last=last_saved):
                if it % every:
                    return
                c = np.asarray(coreness, dtype=np.int32)
                if _last["c"] is not None and np.array_equal(_last["c"], c):
                    return  # fixed point (or no progress): nothing to save
                save_s = SweepSnapshot(
                    coreness=c, parts_done=_cursor, sweep=_start + it,
                    n_part=_n, threshold=_threshold,
                    thresholds=state.thresholds,
                    fingerprint=state.fingerprint,
                ).save(
                    self.sweep_dir, manager=self.sweeps_mgr,
                    blocking=not self.overlap,
                )
                _last["c"] = c
                if self.on_sweep_saved is not None:
                    self.on_sweep_saved(_cursor, _start + it, save_s)

        if heartbeat is not None:
            inner = hook

            def hook(it, coreness, _inner=inner):
                heartbeat()
                if _inner is not None:
                    _inner(it, coreness)

        if account:
            self.preprocess_time_s += (
                (time.perf_counter() - t0) + plan.bucketize_time_s + plan.extract_time_s
            )
        fn = fn if fn is not None else self.decompose_fn
        if init is not None or hook is not None:
            res = fn(plan.bg, init_coreness=init, on_sweep=hook)
        else:
            res = fn(plan.bg)
        return res, bitmap_density(plan.bg), start_sweep

    # ---------------- merge + shrink ---------------- #
    def _report_for(self, plan: PartPlan, res, density: float,
                    start_sweep: int, finalized: int) -> PartReport:
        return PartReport(
            name=plan.name,
            threshold=plan.threshold,
            n_nodes=plan.part_g.n_nodes,
            n_edges=plan.part_g.n_edges,
            iterations=res.iterations,
            comm_amount=res.comm_amount,
            peak_bytes=res.peak_bytes,
            extract_time_s=plan.extract_time_s,
            decompose_time_s=res.wall_time_s,
            finalized=finalized,
            gathered_rows=res.gathered_rows,
            full_sweep_rows=res.full_sweep_rows,
            active_rows_per_iter=list(res.active_rows_per_iter),
            collective_bytes=res.collective_bytes,
            bitmap_density=density,
            resumed_at_sweep=start_sweep,
            prefetched=plan.speculative,
        )

    def _finalize_threshold(self, plan: PartPlan, res, density: float,
                            start_sweep: int):
        """Merge a threshold part's result into the global state and
        append its report (before the shrink — matching the report order
        the checkpoints have always serialized)."""
        state = self.state
        with span("kcore.merge"):
            # Finalize nodes that resolved at >= t (all of them for
            # Exact-Divide).
            final_local = res.coreness >= plan.threshold
            part_orig_ids = state.remaining_ids[plan.part_local_ids]
            newly = part_orig_ids[final_local]
            state.coreness[newly] = res.coreness[final_local]
            state.finalized[newly] = True
            report = self._report_for(
                plan, res, density, start_sweep, int(final_local.sum())
            )
            self.parts.append(report)
        return report, final_local

    def _shrink(self, plan: PartPlan, final_local: np.ndarray,
                report: PartReport) -> Optional[PartPlan]:
        """Fold the finalized nodes out of the remaining graph. Adopts the
        speculative shrink when the prediction held (byte-identical: the
        masks coincide and every divide pass is deterministic); otherwise
        discards it and recomputes synchronously, exactly as the
        sequential path. Returns the prefetched next plan on a hit."""
        pf = self._take_prefetch(plan.cursor)
        if pf is not None and bool(final_local.all()):
            self.prefetch_hits += 1
            self._adopt_shrink(plan, pf, report)
            return pf.plan
        if pf is not None:
            self.prefetch_misses += 1
        self._shrink_sync(plan, final_local, report)
        return None

    def _adopt_shrink(self, plan: PartPlan, pf: _Prefetch,
                      report: PartReport) -> None:
        """Adopt a validated speculative shrink (prediction held — the
        masks coincide, so this state is byte-identical to the sync fold)."""
        state = self.state
        plan.dstats.merge(pf.shrink_stats)
        state.ext_remaining = pf.ext_next
        state.remaining_ids = state.remaining_ids[pf.shrink_keep_ids]
        self.remaining_graph = pf.shrink_graph
        self.preprocess_time_s += pf.shrink_time_s
        report.divide_transient_bytes = plan.dstats.peak_transient_bytes

    def _shrink_sync(self, plan: PartPlan, final_local: np.ndarray,
                     report: PartReport) -> None:
        """The sequential fold: shrink the remaining graph by the part's
        ACTUALLY finalized nodes."""
        state = self.state
        with span("kcore.divide.fold") as sp:
            newly_mask_local = np.zeros(self.remaining_graph.n_nodes,
                                        dtype=bool)
            newly_mask_local[plan.part_local_ids[final_local]] = True
            keep_local = ~newly_mask_local
            ext_delta = self._fold_external(
                self.remaining_graph, keep_local, newly_mask_local,
                plan.dstats,
            )
            new_graph, keep_ids = induced_subgraph(
                self.remaining_graph, keep_local,
                chunk_slots=self.divide_chunk, stats=plan.dstats,
            )
            state.ext_remaining = state.ext_remaining[keep_local] + ext_delta
            state.remaining_ids = state.remaining_ids[keep_ids]
            self.remaining_graph = new_graph
        self.preprocess_time_s += sp.seconds
        report.divide_transient_bytes = plan.dstats.peak_transient_bytes

    def _merge_rest(self, plan: PartPlan, res, density: float,
                    start_sweep: int, annotate=None) -> None:
        state = self.state
        with span("kcore.merge"):
            state.coreness[state.remaining_ids] = res.coreness
            state.finalized[state.remaining_ids] = True
            report = self._report_for(
                plan, res, density, start_sweep, plan.part_g.n_nodes
            )
            if annotate is not None:
                annotate(report)  # wave/slice stamps, before the save
            self.parts.append(report)
            state.remaining_ids = np.zeros(0, dtype=np.int64)
            state.ext_remaining = np.zeros(0, dtype=np.int32)
            state.complete = True
        self._checkpoint_boundary(report)

    # ---------------- checkpoint stage ---------------- #
    def _purge_sweeps(self) -> None:
        if self.sweep_dir is None:
            return
        if self.sweeps_mgr is not None:
            # Waits out a pending async snapshot save first — the purge
            # can never shred a write in flight.
            self.sweeps_mgr.clear_steps()
        else:
            _clear_checkpoints(self.sweep_dir)

    def _checkpoint_boundary(self, report: Optional[PartReport]) -> None:
        """Save state at a part boundary, then fire the hook. Sweep
        snapshots of the just-finished part are purged after the boundary
        save (they are stale the moment the boundary exists; a crash
        between save and purge is caught by snapshot validation)."""
        if self.checkpoint_dir is not None:
            self._visit_fault("checkpoint_save",
                              parts_done=int(self.state.parts_done))
            on_done = None
            if report is not None:
                def on_done(_step, secs, _r=report):
                    _r.save_wall_s = secs
            with span("kcore.checkpoint"):
                blocked = self.state.save(
                    self.checkpoint_dir, manager=self.state_mgr,
                    blocking=not self.overlap, on_done=on_done,
                )
                self._purge_sweeps()
            if report is not None:
                report.save_time_s = blocked
        if self.on_part_done is not None and report is not None:
            self.on_part_done(len(self.parts) - 1, report)

    # ---------------- part-parallel waves ---------------- #
    def _wave_width(self) -> int:
        """Parts planned per wave: the configured slice count minus the
        blacklisted slices (elastic degradation — a degraded run plans
        narrower waves; at width 1 it IS the sequential loop)."""
        return max(1, (self.part_parallel or 1) - len(self.blacklisted))

    def _plan_wave(self, first_plan: PartPlan):
        """Plan up to ``part_parallel`` consecutive parts (minus any
        blacklisted slices) by chaining speculative shrinks: part ``i+1``
        is planned on the PREDICTED shrink of part ``i`` (every candidate
        finalizes — the PR 5 speculation discipline at depth
        ``part_parallel`` instead of 1). Returns ``(wave, shrinks)`` with
        ``shrinks[i]`` the speculative shrink applying after ``wave[i]``
        (``None`` for empty parts and for the un-speculated last entry).
        Main-thread, pure host work."""
        wave = [first_plan]
        shrinks: List[Optional[_Prefetch]] = [None]
        graph, ext = self.remaining_graph, self.state.ext_remaining
        while len(wave) < self._wave_width() and not wave[-1].is_rest:
            cur = wave[-1]
            if not cur.is_empty:
                pf = self._speculative_shrink(graph, ext, cur.cand_mask,
                                              cur.cursor)
                shrinks[-1] = pf
                graph, ext = pf.shrink_graph, pf.ext_next
            nxt = self._plan_on(graph, ext, cur.cursor + 1, speculative=True)
            if nxt is None:
                break  # predicted shrink emptied the graph — no rest part
            wave.append(nxt)
            shrinks.append(None)
        for p in wave:
            self._bucketize(p)
        return wave, shrinks

    def _run_wave(self, wave: List[PartPlan],
                  shrinks: List[Optional[_Prefetch]]) -> Optional[PartPlan]:
        """Conquer one wave across the mesh slices, then merge strictly in
        plan order. Returns the next wave's first plan (``None`` = done).

        The LPT schedule places each non-empty part on a slice by its
        modeled cost; every slice conquers its parts concurrently on its
        own worker thread; only the lead part (the one the last boundary
        checkpoint points at) consults/writes sweep snapshots. The merge
        loop then validates each speculation in plan order — on a hit the
        predicted shrink is adopted (byte-identical to the sequential
        fold), on a miss the sync fold runs and every later speculative
        conquer of the wave is discarded, exactly as the sequential loop
        would have recomputed them.

        With a watchdog configured the wave is fault-tolerant: failed
        parts retry on their slice with backoff; a slice that exhausts
        its retries or hangs past the timeout is blacklisted for the rest
        of the run and the wave tail re-plans over the survivors (parts
        are idempotent, so the result stays byte-identical). Telemetry
        (retries/blacklists/replans) folds into the run report."""
        from repro.core.partsched import (
            WaveTelemetry,
            assign_parts,
            conquer_wave,
            cost_for_plan,
        )

        state = self.state
        surviving = [
            sp for sp in self.slice_specs if sp.index not in self.blacklisted
        ]
        live = [p for p in wave if not p.is_empty]
        costs = [
            cost_for_plan(p.bg, p.cursor, surviving[0]) for p in live
        ]
        schedule = assign_parts(costs, surviving)
        # Divide-side accounting for the whole wave, booked on the main
        # thread before the slice threads start (_conquer(account=False)).
        self.preprocess_time_s += sum(
            p.bucketize_time_s + p.extract_time_s for p in wave
        )
        lead_cursor = min((p.cursor for p in live), default=None)
        by_cursor = {p.cursor: p for p in live}
        assign_of = {a.cursor: a for a in schedule.assignments}

        def _run_one(cursor: int, s: int, heartbeat=None):
            plan = by_cursor[cursor]
            fn = (
                self.slice_decomposes[s]
                if self.slice_decomposes is not None else None
            )
            with bound(self.recorder), span("kcore.part", **_PART_COUNTS):
                _count_part(plan)
                out = self._conquer(
                    plan, fn=fn, lead=(cursor == lead_cursor), account=False,
                    heartbeat=heartbeat,
                )
            # Only slice ``s``'s worker writes index ``s`` — no lock needed.
            self.slice_busy_s[s] += out[0].wall_time_s
            return out

        if self.watchdog is not None:
            run_part = _run_one
        else:
            # Fail-fast path: keep the historical two-arg call shape (no
            # heartbeat composed into on_sweep), so a custom decompose_fn
            # that accepts no kwargs stays usable without a watchdog.
            def run_part(cursor: int, s: int):
                return _run_one(cursor, s)

        tel = WaveTelemetry()
        t0 = time.perf_counter()
        try:
            results = conquer_wave(
                schedule, run_part, slices=surviving, watchdog=self.watchdog,
                fault_plan=self.fault_plan, telemetry=tel,
            )
        finally:
            self.conquer_wall_s += time.perf_counter() - t0
            self.retries += tel.retries
            self.replans += tel.replans
            if tel.blacklisted:
                self.degraded_waves += 1
                self.blacklisted.update(tel.blacklisted)
            self.fault_events.extend(tel.events)
        retries_of: Dict[int, int] = {}
        for e in tel.events:
            if e.get("event") == "retry":
                retries_of[e["cursor"]] = retries_of.get(e["cursor"], 0) + 1

        for i, plan in enumerate(wave):
            if plan.is_empty:
                state.parts_done = plan.cursor + 1
                self._checkpoint_boundary(None)
                continue
            res, density, start_sweep = results[plan.cursor]
            a = assign_of[plan.cursor]

            def stamp(r, _a=a):
                # slice_index is the PLANNED placement; a re-planned part's
                # actual executor is in the replan event log.
                r.slice_index = _a.slice_index
                r.wave = self._wave_index
                r.modeled_cost_bytes = _a.cost.total
                r.retries = retries_of.get(_a.cursor, 0)

            if plan.is_rest:
                self._merge_rest(plan, res, density, start_sweep,
                                 annotate=stamp)
                return None
            report, final_local = self._finalize_threshold(
                plan, res, density, start_sweep
            )
            stamp(report)
            pf = shrinks[i]
            if pf is not None and bool(final_local.all()):
                self.prefetch_hits += 1
                self._adopt_shrink(plan, pf, report)
                state.parts_done = plan.cursor + 1
                self._checkpoint_boundary(report)
                continue
            # Miss (or the wave's un-speculated tail): fold synchronously,
            # discard every later speculative conquer of this wave.
            if pf is not None:
                self.prefetch_misses += 1
                self.speculation_discards += sum(
                    1 for p in wave[i + 1:] if not p.is_empty
                )
            self._shrink_sync(plan, final_local, report)
            state.parts_done = plan.cursor + 1
            self._checkpoint_boundary(report)
            if pf is not None and i < len(wave) - 1:
                return self._build_plan(plan.cursor + 1)
        return self._build_plan(wave[-1].cursor + 1)

    def run_waves(self) -> None:
        state = self.state
        plan = self._build_plan(state.parts_done)
        while plan is not None:
            wave, shrinks = self._plan_wave(plan)
            plan = self._run_wave(wave, shrinks)
            self._wave_index += 1
        if not state.complete:
            # The shrink emptied the graph before the rest part.
            state.complete = True
            self._checkpoint_boundary(None)

    # ---------------- scheduler ---------------- #
    def run(self) -> None:
        if self.part_parallel is not None:
            self.run_waves()
            return
        state = self.state
        plan = None  # the next part's plan, when the prefetch built it
        # A part is left while a threshold is unconsumed or the remaining
        # graph still holds the rest part.
        while not state.complete and (
                state.parts_done < len(self.thresholds)
                or self.remaining_graph.n_nodes > 0):
            with span("kcore.part", **_PART_COUNTS):
                if plan is None:
                    plan = self._build_plan(state.parts_done)
                plan = self._run_part(plan)
        if not state.complete:
            # The shrink emptied the graph before the rest part.
            state.complete = True
            self._checkpoint_boundary(None)

    def _run_part(self, plan: PartPlan) -> Optional[PartPlan]:
        """Bucketize, conquer, merge, fold and checkpoint one part of the
        sequential loop. Returns the next part's plan when the prefetch
        built it."""
        state = self.state
        _count_part(plan)
        if plan.is_empty:
            # No candidates at this threshold: consume the cursor.
            state.parts_done = plan.cursor + 1
            self._checkpoint_boundary(None)
            return None
        self._bucketize(plan)
        self._submit_prefetch(plan)
        res, density, start_sweep = self._conquer(plan)
        if plan.is_rest:
            self._merge_rest(plan, res, density, start_sweep)
            return None
        report, final_local = self._finalize_threshold(
            plan, res, density, start_sweep
        )
        next_plan = self._shrink(plan, final_local, report)
        state.parts_done = plan.cursor + 1
        self._checkpoint_boundary(report)
        return next_plan

    def close(self, suppress_errors: bool = False) -> None:
        """Drain the prefetch worker and both checkpoint managers. Runs on
        EVERY exit path: after a crash-by-exception (the fault-injection
        tests) the pending async saves land before the exception leaves
        ``dc_kcore``, so the on-disk state at "crash" time is deterministic
        and no worker thread outlives the call."""
        if self._future is not None:
            fut, self._future = self._future, None
            exc = fut.exception()  # waits; consumes a worker failure
            if exc is not None and not suppress_errors:
                raise exc
        if self._executor is not None:
            self._executor.shutdown(wait=True)
            self._executor = None
        for mgr in (self.state_mgr, self.sweeps_mgr):
            if mgr is None:
                continue
            try:
                mgr.wait()
            except BaseException:
                if not suppress_errors:
                    raise


@_recorded
def dc_kcore(
    g: Graph,
    thresholds: Sequence[int] = (),
    strategy: str = "rough",
    decompose_fn: Optional[DecomposeFn] = None,
    row_align: int = 8,
    reorder: str = "identity",
    max_bucket_rows="auto",
    reorder_sample_edges: Optional[int] = None,
    checkpoint_dir: Optional[str] = None,
    resume: bool = False,
    on_part_done: Optional[PartHook] = None,
    divide_chunk: Optional[int] = None,
    sweep_checkpoint_every: Optional[int] = None,
    on_sweep_saved: Optional[SweepSavedHook] = None,
    overlap: bool = False,
    engine: str = "sorted",
    int16: bool = False,
    part_parallel: Optional[int] = None,
    part_parallel_plan=None,
    slice_capacity_bytes: Optional[int] = None,
    slice_timeout_s: Optional[float] = None,
    max_retries: Optional[int] = None,
    retry_backoff_s: float = 0.05,
    fault_plan=None,
    ckpt_retain: int = 2,
) -> tuple[np.ndarray, DCKCoreReport]:
    """Run DC-kCore. ``thresholds=()`` degenerates to the monolithic baseline
    (= the PSGraph competitor in the paper's tables).

    ``decompose_fn`` lets callers swap the conquer engine (single-device jit,
    Pallas-kernel, or the distributed shard_map engine) without touching the
    divide/merge logic. With ``sweep_checkpoint_every`` set it is invoked as
    ``decompose_fn(bg, init_coreness=..., on_sweep=...)``, so a custom engine
    must accept those kwargs (see :data:`DecomposeFn`); without the flag it
    is always called as plain ``decompose_fn(bg)``.

    ``engine`` selects the built-in conquer engine's sweep op
    (``"sorted"`` / ``"count"`` / ``"kernel"`` / ``"fused"`` — see
    :func:`repro.core.decompose.decompose`), and ``int16`` opts the fused
    engine into the halved-width estimate mode (overflow-guarded). Both
    apply only when ``decompose_fn`` is not given — a custom engine owns
    its own configuration, so combining them raises.

    ``overlap=True`` pipelines the stages: a single worker thread runs the
    next part's divide passes and bucketize (and the shrink of the current
    remaining graph) while the current part sweeps on the device, and
    checkpoint saves go through the manager's async thread instead of
    blocking the loop. The prefetch is *speculative* — it assumes every
    candidate of the conquering part finalizes — and is validated against
    the actual finalized set before being adopted, recomputed synchronously
    on a miss (Exact-Divide always hits by construction). Coreness is
    **byte-identical** with the flag on or off, resume included; only the
    wall clock and the host's share of it outside the conquer change
    (:attr:`DCKCoreReport.idle_fraction`, Fig 16).

    ``reorder`` (``"identity"`` / ``"bfs"`` / ``"rcm"``) applies a
    locality-aware node ordering to *each part* before bucketizing it: the
    part's tiles then see co-located neighbor ids, the bucket-adjacency
    bitmap gets sparser, and the static frontier filter starts paying off.
    Purely a layout decision — the permutation is carried on the
    ``BucketedGraph`` and the engines report coreness in part-local original
    ids, so divide/merge is untouched. ``reorder_sample_edges`` switches the
    ordering computation to the bounded edge-sample variant
    (:func:`~repro.graph.reorder.sampled_order`). ``max_bucket_rows`` is
    forwarded to :func:`~repro.graph.build.bucketize` (``"auto"`` = the
    degree-profile tile autotuner).

    ``divide_chunk`` bounds the divide step's transient host bytes: every
    extraction pass (candidates, induced subgraph, ext fold, shrink — and
    the resume-time remaining-graph rebuild) runs chunked over CSR row
    ranges of at most that many adjacency slots, bit-identical to the
    unchunked result at every chunk size (``None`` = the
    :data:`~repro.graph.build.DEFAULT_DIVIDE_CHUNK_SLOTS` budget — the
    divide transient is *always* bounded; the knob only sizes it). Each
    part's observed peak rides in ``PartReport.divide_transient_bytes``.

    ``checkpoint_dir`` enables per-part checkpointing: the
    :class:`PipelineState` is saved atomically after every part, and
    ``resume=True`` restores the latest complete checkpoint and re-enters at
    the first unfinished part — a killed run resumed this way produces
    coreness **byte-identical** to the uninterrupted run. ``on_part_done``
    (``hook(part_index, report)``) fires after each part's save (after the
    save *enqueue* in overlapped mode — a crash raised from the hook still
    drains the pending save before propagating, so the boundary is on disk
    either way) — the fault-injection tests raise from it to simulate a
    crash at the worst moment (state saved, next part not started).

    ``part_parallel=S`` conquers up to ``S`` consecutive parts CONCURRENTLY
    per wave: the wave planner chains speculative shrinks (part ``i+1``
    planned on part ``i``'s predicted shrink — the ``overlap`` speculation
    at depth ``S``), the partition scheduler
    (:mod:`repro.core.partsched`) places each part on a slice by its
    modeled collective+HBM cost, and the merge loop validates the
    predictions strictly in plan order, discarding the wave's tail on the
    first miss. Coreness, checkpoints, sweep snapshots and resume are
    **byte-identical** to the sequential path. Without
    ``part_parallel_plan`` the slices are worker threads sharing the
    configured engine (the test backend); with it (a
    :class:`~repro.core.distributed.MeshPlan`) the global mesh is split
    into ``S`` submeshes, each part sweeps on its slice through the
    shard_map engine, and the E(v) boundary folds run device-resident via
    collectives (``DCKCoreReport.boundary_exchange_bytes``).
    ``slice_capacity_bytes`` bounds each slice's modeled resident bytes
    (the scheduler refuses oversized parts). Mutually exclusive with
    ``overlap`` — the wave subsumes the depth-1 prefetch.

    ``sweep_checkpoint_every=k`` (requires ``checkpoint_dir``) additionally
    saves a :class:`SweepSnapshot` every ``k`` conquer sweeps through the
    same atomic path; ``resume=True`` (with the flag still set) then
    re-enters *mid-part* at the last completed sweep via the engines'
    ``init_coreness`` warm restart — still byte-identical, because the
    fixed point is exact from any snapshot. A stale or unreadable snapshot
    (finished part, other run, half-written ``.tmp``) is ignored and resume
    falls back to the part boundary. ``on_sweep_saved``
    (``hook(part_cursor, sweep, save_seconds)``) fires after each snapshot
    save — the mid-sweep fault-injection tests crash from it.

    ``slice_timeout_s`` / ``max_retries`` (require ``part_parallel``) turn
    the wave executor fault-TOLERANT instead of fail-fast: a failed part
    retries on its slice with exponential backoff (``retry_backoff_s``
    base) up to ``max_retries`` times; a slice whose sweep heartbeat
    stalls past ``slice_timeout_s`` — or that exhausts its retries — is
    blacklisted for the rest of the run and its unfinished parts re-plan
    over the surviving slices (S -> S-1 -> ... -> 1, width 1 ≡ the
    sequential loop). Parts are idempotent over immutable inputs, so a
    degraded run's coreness stays **byte-identical** to the fault-free
    sequential run; retries/blacklists/degraded waves land in the report.
    Without either knob the historical fail-fast semantics are unchanged
    (the first slice failure re-raises after the wave drains).

    ``fault_plan`` (a :class:`repro.runtime.FaultPlan`) injects chaos —
    crashes, hangs, slowdowns — into the named pipeline sites
    (``slice_conquer``, ``boundary_fold``, ``checkpoint_save``,
    ``prefetch``); the chaos tests, the CLI ``--fault`` flag and the
    bench harness share this one mechanism. ``ckpt_retain`` sizes both
    checkpoint managers' retention (default 2: the newest boundary plus
    one predecessor, so a corrupted latest step — detected by per-array
    CRC32, quarantined to ``step_*.corrupt`` — resumes from the previous
    retained step instead of restarting the part from scratch).

    Every run records its program spans (:mod:`repro.core.spans`: the job,
    each part, each divide pass, sweep and merge) on ``report.spans``,
    summed by :meth:`DCKCoreReport.stage_seconds`.
    """
    slice_decomposes = slice_specs = fold_plan = None
    if part_parallel is not None:
        if part_parallel < 1:
            raise ValueError(f"part_parallel must be >= 1, got {part_parallel}")
        if overlap:
            raise ValueError("part_parallel subsumes overlap (the wave IS "
                             "the speculation) — pass one or the other")
        if part_parallel_plan is not None:
            if decompose_fn is not None:
                raise ValueError("part_parallel_plan builds one distributed "
                                 "engine per mesh slice — decompose_fn would "
                                 "be silently ignored")
            if engine != "sorted" or int16:
                raise ValueError("part_parallel_plan selects the shard_map "
                                 "engine; engine=/int16= would be silently "
                                 "ignored")
            from repro.core.partsched import make_slice_decomposes, spec_of

            slice_plans, slice_decomposes = make_slice_decomposes(
                part_parallel_plan, part_parallel
            )
            slice_specs = [
                spec_of(p, i, slice_capacity_bytes)
                for i, p in enumerate(slice_plans)
            ]
            fold_plan = part_parallel_plan
        else:
            from repro.core.partsched import SliceSpec

            slice_specs = [
                SliceSpec(i, 1, 1, slice_capacity_bytes)
                for i in range(part_parallel)
            ]
    elif part_parallel_plan is not None:
        raise ValueError("part_parallel_plan requires part_parallel")
    watchdog = None
    if slice_timeout_s is not None or max_retries is not None:
        if part_parallel is None:
            raise ValueError("slice_timeout_s/max_retries configure the "
                             "part-parallel wave watchdog — they require "
                             "part_parallel")
        from repro.core.partsched import WatchdogConfig

        watchdog = WatchdogConfig(
            slice_timeout_s=slice_timeout_s,
            max_retries=2 if max_retries is None else int(max_retries),
            backoff_s=float(retry_backoff_s),
        )
    if ckpt_retain < 1:
        raise ValueError(f"ckpt_retain must be >= 1, got {ckpt_retain}")
    if engine == "fused":
        # Refuse before the first divide pass, not at the first sweep.
        from repro.kernels.fused import require_fused_platform

        require_fused_platform()
    if decompose_fn is None:
        decompose_fn = (  # noqa: E731
            lambda bg, **kw: decompose(bg, op=engine, int16=int16, **kw)
        )
    elif engine != "sorted" or int16:
        raise ValueError("engine=/int16= configure the built-in engine; "
                         "with decompose_fn they would be silently ignored "
                         "— configure the custom engine instead")
    if resume and checkpoint_dir is None:
        raise ValueError("resume=True requires checkpoint_dir")
    if sweep_checkpoint_every is not None and checkpoint_dir is None:
        raise ValueError("sweep_checkpoint_every requires checkpoint_dir")
    thresholds = sorted(set(int(t) for t in thresholds), reverse=True)
    t_start = time.perf_counter()

    n = g.n_nodes
    state: Optional[PipelineState] = None
    resumed_parts = 0
    sweep_dir = _sweep_dir(checkpoint_dir) if checkpoint_dir is not None else None
    pending_snap: Optional[SweepSnapshot] = None
    # Quarantine records from corrupt-checkpoint fallbacks during restore —
    # folded into the report's fault accounting.
    restore_events: List[dict] = []
    if resume:
        state = PipelineState.restore(checkpoint_dir, n, events=restore_events)
        if sweep_checkpoint_every is not None:
            # Mid-part resume point — consulted even when no part boundary
            # exists yet (a run killed during part 0 leaves only sweep
            # snapshots), and validated against the part it claims to
            # belong to at the moment that part runs.
            pending_snap = SweepSnapshot.restore(sweep_dir, events=restore_events)
    if state is None:
        if checkpoint_dir is not None and not resume:
            # Fresh run: purge stale steps (and sweep snapshots) from any
            # previous run in this dir, so a later resume can only see this
            # run's boundaries. A resume that found no boundary keeps the
            # dir as is — snapshot validation screens anything stale.
            _clear_checkpoints(checkpoint_dir)
            _clear_checkpoints(sweep_dir)
        state = PipelineState.fresh(g, thresholds)
        remaining_graph = g
    else:
        if state.fingerprint != graph_fingerprint(g):
            raise ValueError(
                f"checkpoint was written for a different graph "
                f"(fingerprint {state.fingerprint} != {graph_fingerprint(g)})"
            )
        if state.thresholds != thresholds:
            raise ValueError(
                f"checkpoint plans thresholds {state.thresholds}, "
                f"this run asked for {thresholds}"
            )
        resumed_parts = len(state.reports)
        if state.complete:
            report = DCKCoreReport(
                parts=state.reports,
                total_time_s=time.perf_counter() - t_start,
                preprocess_time_s=0.0,
                resumed_parts=resumed_parts,
                overlap=overlap,
                part_parallel=part_parallel or 0,
                quarantined_steps=len(restore_events),
                fault_events=list(restore_events),
            )
            return state.coreness.copy(), report
        # Rebuild the remaining graph from the original + finalized mask.
        # Induced-subgraph composition is byte-stable (monotone relabeling
        # of a sorted CSR), so this equals the incrementally shrunk graph.
        remaining_graph, keep_ids = induced_subgraph(
            g, ~state.finalized, chunk_slots=divide_chunk
        )
        assert np.array_equal(keep_ids, state.remaining_ids), (
            "checkpoint remaining-id map inconsistent with finalized mask"
        )

    state_mgr = sweeps_mgr = None
    if checkpoint_dir is not None:
        from repro.ckpt import CheckpointManager

        state_mgr = CheckpointManager(checkpoint_dir, retain=ckpt_retain)
        sweeps_mgr = CheckpointManager(sweep_dir, retain=ckpt_retain)

    pipeline = _PartPipeline(
        state=state,
        remaining_graph=remaining_graph,
        thresholds=thresholds,
        strategy=strategy,
        decompose_fn=decompose_fn,
        row_align=row_align,
        reorder=reorder,
        max_bucket_rows=max_bucket_rows,
        reorder_sample_edges=reorder_sample_edges,
        checkpoint_dir=checkpoint_dir,
        sweep_dir=sweep_dir,
        divide_chunk=divide_chunk,
        sweep_checkpoint_every=sweep_checkpoint_every,
        on_part_done=on_part_done,
        on_sweep_saved=on_sweep_saved,
        overlap=overlap,
        pending_snap=pending_snap,
        state_mgr=state_mgr,
        sweeps_mgr=sweeps_mgr,
        part_parallel=part_parallel,
        slice_decomposes=slice_decomposes,
        slice_specs=slice_specs,
        fold_plan=fold_plan,
        watchdog=watchdog,
        fault_plan=fault_plan,
    )
    try:
        pipeline.run()
    except BaseException:
        # Crash-by-exception (incl. the fault-injection hooks): drain the
        # worker and pending saves FIRST, so the disk state the "crashed"
        # run leaves behind is deterministic, then let the crash propagate.
        # Injected hangs are released first — a parked worker must wake
        # (and raise) for the drain to terminate promptly.
        if fault_plan is not None:
            fault_plan.release()
        pipeline.close(suppress_errors=True)
        raise
    if fault_plan is not None:
        fault_plan.release()
    pipeline.close()

    report = DCKCoreReport(
        parts=pipeline.parts,
        total_time_s=time.perf_counter() - t_start,
        preprocess_time_s=pipeline.preprocess_time_s,
        resumed_parts=resumed_parts,
        overlap=overlap,
        prefetch_hits=pipeline.prefetch_hits,
        prefetch_misses=pipeline.prefetch_misses,
        part_parallel=part_parallel or 0,
        conquer_wall_s=pipeline.conquer_wall_s,
        slice_busy_s=list(pipeline.slice_busy_s),
        speculation_discards=pipeline.speculation_discards,
        boundary_exchange_bytes=pipeline.boundary_exchange_bytes,
        retries=pipeline.retries,
        blacklisted_slices=sorted(pipeline.blacklisted),
        degraded_waves=pipeline.degraded_waves,
        quarantined_steps=len(restore_events),
        fault_events=list(restore_events) + list(pipeline.fault_events),
    )
    if not bool((state.coreness >= 0).all()):
        raise MergeIncompleteError(
            f"merge left {int((state.coreness < 0).sum())} of {n} nodes "
            f"unfinalized — every node must be resolved by exactly one part"
        )
    return state.coreness, report
