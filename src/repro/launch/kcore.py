"""DC-kCore launcher — the paper's workload as a CLI.

  python -m repro.launch.kcore --graph rmat:18:16 --thresholds 16,64
  python -m repro.launch.kcore --graph file:/data/com-friendster.txt \
      --budget-gb 2 --strategy rough --edge-chunk 1048576 --check
  python -m repro.launch.kcore --graph rmat:14:12 --reorder rcm --check
  python -m repro.launch.kcore --graph rmat:14:12 --thresholds 16 \
      --checkpoint-dir /tmp/kcore-ck --resume

Graphs: ``rmat:<scale>:<edge_factor>``, ``ba:<n>:<m>``, ``er:<n>:<deg>``,
``file:<path>`` (SNAP edge list), ``npz:<path>``.

``--edge-chunk N`` routes ingest through the streaming path: ``file:``
graphs are read in N-edge chunks and built via the spill-to-disk external
dedup (synthetic graphs are re-streamed through the same builder), and the
CLI reports the tracked peak transient host bytes next to the in-memory
loader's baseline. ``--divide-chunk N`` sizes the chunked divide passes
(adjacency slots of transient per extraction chunk; the divide step is
always chunk-bounded — this only overrides the default budget), with each
part's observed peak in the report table. ``--checkpoint-dir`` saves the
pipeline state after every part (atomic, ``.tmp``-then-rename);
``--sweep-checkpoint-every K`` additionally snapshots the conquer state
every K sweeps, so ``--resume`` re-enters a killed run *mid-part* at the
last completed sweep (falling back to the part boundary when no valid
snapshot exists). ``--overlap`` turns on the staged pipeline — the next
part's divide runs on a worker thread and checkpoint saves go async while
the current part sweeps; coreness is byte-identical either way. The
summary prints one line of seconds per stage, read from the run's program
spans (:mod:`repro.core.spans`), and the executables each part built.
``--reorder {identity,bfs,rcm}`` applies
a locality-aware node ordering to each part before tiling
(``--reorder-sample N`` computes it from an N-slot edge sample);
``--max-bucket-rows`` overrides the tile autotuner with a uniform row cap
(``auto`` = degree-profile autotuner, ``none`` = one tile per degree
class). ``--engine {sorted,count,kernel,fused}`` selects the conquer
sweep engine — ``fused`` is the single-kernel Pallas sweep (gather +
h-index + dirty push fused per row tile; CPU backend only, since Mosaic
refuses it for the TPU) — and
``--int16`` opts the fused engine into the halved-width estimate mode
(falls back to int32 automatically when any starting estimate reaches
2^15; coreness is bit-identical in every case). With ``--part-parallel``,
slices are priced against the real memory budget: slice capacity defaults
to the ``--budget-gb`` value (override with ``--slice-capacity-gb``), and a
part whose modeled resident bytes no slice admits triggers a re-divide
with smaller parts (``plan_thresholds`` at a halved budget) instead of
aborting the pipeline. ``--slice-timeout`` / ``--max-retries`` arm the
part-parallel fault-tolerance layer: a crashed part retries on its slice
with backoff, and a slice that hangs past the timeout (or exhausts its
retries) is blacklisted with its unfinished parts re-planned over the
survivors — the run completes degraded, byte-identical to sequential.
``--ckpt-retain`` keeps the N newest boundary/sweep checkpoints (default
2, so a corrupted latest step can fall back to its predecessor).
``--fault site:kind[:at[:count[:delay]]]`` injects failures for chaos
testing (sites: slice_conquer, boundary_fold, checkpoint_save, prefetch,
serve_update; kinds: crash, hang, slow); ``--fault-log FILE`` writes the
run's fault/recovery event trail as JSON.
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.core.dckcore import dc_kcore
from repro.core.divide import plan_thresholds
from repro.core.spans import recording, stage_seconds
from repro.core.partsched import SliceCapacityError
from repro.graph import barabasi_albert, erdos_renyi, rmat
from repro.graph.io import (
    csr_from_edge_chunks,
    graph_edge_chunks,
    load_edgelist,
    load_npz,
    stream_edgelist,
)
from repro.graph.oracle import peel_coreness


def load_graph(spec: str, seed: int, edge_chunk: int | None = None):
    """Build the graph for ``spec``; with ``edge_chunk`` set, run ingest
    through the streaming builder and return its :class:`IngestStats`."""
    kind, _, rest = spec.partition(":")
    if kind == "file":
        if edge_chunk is not None:
            return stream_edgelist(rest, chunk_edges=edge_chunk)
        return load_edgelist(rest), None
    if kind == "rmat":
        scale, ef = (rest.split(":") + ["16"])[:2]
        g = rmat(int(scale), int(ef), seed=seed)
    elif kind == "ba":
        n, m = rest.split(":")
        g = barabasi_albert(int(n), int(m), seed=seed)
    elif kind == "er":
        n, d = rest.split(":")
        g = erdos_renyi(int(n), float(d), seed=seed)
    elif kind == "npz":
        g = load_npz(rest)
    else:
        raise ValueError(f"unknown graph spec {spec}")
    if edge_chunk is not None:
        # Re-stream the in-memory graph through the chunked builder so the
        # streaming path (and its resident-bytes accounting) is exercised
        # for synthetic specs too.
        g, stats = csr_from_edge_chunks(
            graph_edge_chunks(g, edge_chunk), n_nodes=g.n_nodes,
            chunk_edges=edge_chunk,
        )
        return g, stats
    return g, None


def run_with_capacity_replan(
    g,
    thresholds,
    *,
    replan_budget_bytes=None,
    max_replans=3,
    dc=dc_kcore,
    **dc_kwargs,
):
    """Run ``dc_kcore``; on :class:`SliceCapacityError`, re-divide and retry.

    The wave scheduler refuses a part whose modeled resident bytes exceed
    every slice's capacity. When that happens mid-run the right response is
    not to abort: re-plan the thresholds with a smaller per-part budget
    (halved each attempt, with a proportionally larger part allowance) so
    the oversized part is split, and start over from scratch. The shrink
    starts from whichever of ``replan_budget_bytes`` and the wave's
    ``slice_capacity_bytes`` is smaller — capacity is the constraint that
    tripped, and halving a budget orders of magnitude above it would burn
    every retry without changing the plan. ``resume`` is forced off on
    retries because the aborted attempt's
    checkpoints describe a different partition. Gives up and re-raises
    after ``max_replans`` re-divides, or immediately when no
    ``replan_budget_bytes`` is known to shrink from.

    Returns ``(core, report, thresholds, n_replans)`` with the thresholds
    that actually completed.
    """
    attempt = 0
    while True:
        try:
            core, report = dc(g, thresholds=thresholds, **dc_kwargs)
            return core, report, thresholds, attempt
        except SliceCapacityError as exc:
            attempt += 1
            if replan_budget_bytes is None or attempt > max_replans:
                raise
            base = int(replan_budget_bytes)
            cap = dc_kwargs.get("slice_capacity_bytes")
            if cap is not None:
                base = min(base, int(cap))
            shrunk = max(1, base >> attempt)
            thresholds = plan_thresholds(
                g.degrees, shrunk, max_parts=8 * (1 << attempt)
            )
            print(f"slice capacity exceeded ({exc}); re-divided for "
                  f"{shrunk / 2**30:.3f} GB/part -> thresholds {thresholds} "
                  f"(retry {attempt}/{max_replans})")
            dc_kwargs["resume"] = False


def stage_line(report, plan_s: float = 0.0) -> str:
    """One line of host seconds per stage of a run, from its spans: the
    job, the planner (timed by the caller), the divide passes, the
    conquer's set-up and read-out, the sweeps with the tiles, rows and
    padded slots they ran and the rows that changed, their wait on the
    device and their host self time per sweep, the merge and the checkpoint
    saves."""
    st = report.stage_seconds()

    def total(name):
        return st[name].total_s if name in st else 0.0

    sweeps = [r for r in report.spans if r.name == "kcore.sweep"]
    n = len(sweeps)

    def swept(key):
        return sum(r.counts.get(key, 0) for r in sweeps)

    wait_ms = 1e3 * total("kcore.sweep.wait") / max(1, n)
    host_ms = 1e3 * st["kcore.sweep"].self_s / n if n else 0.0
    return (f"stages (s) of a {total('kcore.job'):.3f}s job: "
            f"plan {plan_s:.3f}, "
            f"candidates {total('kcore.divide.candidates'):.3f}, "
            f"extract {total('kcore.divide.extract'):.3f}, "
            f"fold {total('kcore.divide.fold'):.3f}, "
            f"bucketize {total('kcore.divide.bucketize'):.3f}, "
            f"conquer set-up {total('kcore.conquer.setup'):.3f} "
            f"read-out {total('kcore.conquer.readout'):.3f}, "
            f"{n} sweeps {total('kcore.sweep'):.3f} over "
            f"{swept('active_tiles'):,} tiles, {swept('active_rows'):,} rows, "
            f"{swept('swept_slots') / 1e6:.1f} Mslots, "
            f"{swept('changed_rows'):,} changed rows "
            f"(wait {wait_ms:.2f} ms + host {host_ms:.2f} ms a sweep), "
            f"merge {total('kcore.merge'):.3f}, "
            f"checkpoint {total('kcore.checkpoint'):.3f}")


def compiles_line(report) -> str:
    """Executables built (compiled or loaded from the compile cache) while
    each part ran, in the order the parts started."""
    parts = []
    for r in report.spans:
        if r.name != "kcore.part":
            continue
        c = r.counts
        name = f"core>={c['threshold']}" if "threshold" in c else "rest"
        parts.append(f"{name} (n={c.get('n_nodes', 0):,} "
                     f"m={c.get('n_edges', 0):,}) {c.get('compiles', 0)} in "
                     f"{c.get('compile_ms', 0.0):.0f} ms")
    return "compiles per part: " + ("; ".join(parts) or "-")


def parse_max_bucket_rows(v: str):
    """argparse type for --max-bucket-rows: "auto" | "none" -> None | int."""
    if v == "auto":
        return "auto"
    if v == "none":
        return None
    try:
        return int(v)
    except ValueError:
        raise argparse.ArgumentTypeError(f"expected 'auto', 'none' or an int, got {v!r}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="rmat:14:16")
    ap.add_argument("--thresholds", default="", help="comma list; empty = monolithic")
    ap.add_argument("--budget-gb", type=float, default=None,
                    help="auto-plan thresholds for this per-part budget")
    ap.add_argument("--strategy", choices=["rough", "exact"], default="rough")
    ap.add_argument("--reorder", choices=["identity", "bfs", "rcm"], default="identity",
                    help="locality-aware node ordering applied per part")
    ap.add_argument("--reorder-sample", type=int, default=None, metavar="SLOTS",
                    help="compute the ordering from an edge sample of this "
                         "many slots (out-of-core variant) instead of the "
                         "full CSR traversal")
    ap.add_argument("--engine", choices=["sorted", "count", "kernel", "fused"],
                    default="sorted",
                    help="conquer sweep engine (fused = single-kernel "
                         "Pallas sweep)")
    ap.add_argument("--int16", action="store_true",
                    help="fused engine only: int16 estimate vector for 2x "
                         "effective bandwidth (overflow-guarded int32 "
                         "fallback; bit-identical coreness)")
    ap.add_argument("--max-bucket-rows", type=parse_max_bucket_rows, default="auto",
                    help='tile row cap: "auto" (degree-profile autotuner), '
                         '"none" (one tile per degree class) or an int')
    ap.add_argument("--edge-chunk", type=int, default=None, metavar="EDGES",
                    help="stream ingest in chunks of this many edges "
                         "(bounded-transient spill-to-disk CSR build)")
    ap.add_argument("--divide-chunk", type=int, default=None, metavar="SLOTS",
                    help="chunk budget (adjacency slots) of the divide "
                         "passes; default = the built-in bounded budget")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="save pipeline state here after every part")
    ap.add_argument("--sweep-checkpoint-every", type=int, default=None,
                    metavar="K",
                    help="also snapshot the conquer state every K sweeps "
                         "(mid-part resume; requires --checkpoint-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from --checkpoint-dir at the first "
                         "unfinished part (or mid-part, at the last "
                         "completed sweep snapshot)")
    ap.add_argument("--overlap", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="pipeline the stages: prefetch the next part's "
                         "divide on a worker thread and make checkpoint "
                         "saves async while the current part sweeps "
                         "(byte-identical coreness either way)")
    ap.add_argument("--part-parallel", type=int, default=None, metavar="S",
                    help="conquer up to S parts concurrently per wave "
                         "(speculative shrink chain, validated in plan "
                         "order; byte-identical coreness). Without "
                         "--devices the slices are worker threads sharing "
                         "--engine")
    ap.add_argument("--slice-capacity-gb", type=float, default=None,
                    metavar="GB",
                    help="cap each part-parallel slice's modeled resident "
                         "bytes (default: the --budget-gb value, so slices "
                         "are priced against the same budget the divide "
                         "planned for; requires --part-parallel)")
    ap.add_argument("--slice-timeout", type=float, default=None, metavar="S",
                    help="declare a part-parallel slice dead when its "
                         "sweep heartbeat stalls this many seconds "
                         "(blacklist + re-plan over the survivors; "
                         "requires --part-parallel)")
    ap.add_argument("--max-retries", type=int, default=None, metavar="N",
                    help="retry a crashed part on its slice up to N times "
                         "with exponential backoff before blacklisting "
                         "the slice (requires --part-parallel)")
    ap.add_argument("--ckpt-retain", type=int, default=2, metavar="N",
                    help="keep the N newest boundary/sweep checkpoint "
                         "steps (default 2: a corrupted latest step falls "
                         "back to its predecessor on --resume)")
    ap.add_argument("--fault", action="append", default=[], metavar="SPEC",
                    help="inject a failure: site:kind[:at[:count[:delay]]] "
                         "(repeatable; chaos testing)")
    ap.add_argument("--fault-log", default=None, metavar="FILE",
                    help="write the fault/recovery event trail as JSON")
    ap.add_argument("--devices", type=int, default=None, metavar="N",
                    help="run the shard_map engine over a data x model mesh "
                         "of the first N devices, split into --part-parallel "
                         "slices, with device-resident E(v) boundary "
                         "exchange (requires --part-parallel; N must be "
                         "divisible by S). Under JAX_PLATFORMS=cpu the N "
                         "devices are virtual host devices; elsewhere they "
                         "are the real chips, and fewer than N is an error")
    ap.add_argument("--check", action="store_true", help="verify vs BZ peeling")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.resume and args.checkpoint_dir is None:
        ap.error("--resume requires --checkpoint-dir")
    if args.sweep_checkpoint_every is not None and args.checkpoint_dir is None:
        ap.error("--sweep-checkpoint-every requires --checkpoint-dir")
    if args.int16 and args.engine != "fused":
        ap.error("--int16 requires --engine fused")
    if args.devices is not None and args.part_parallel is None:
        ap.error("--devices requires --part-parallel")
    if args.part_parallel is not None and args.overlap:
        ap.error("--part-parallel subsumes --overlap (the wave IS the "
                 "speculation) — pass one or the other")
    if args.devices is not None and args.engine != "sorted":
        ap.error("--devices selects the shard_map engine; drop --engine")
    if args.slice_capacity_gb is not None and args.part_parallel is None:
        ap.error("--slice-capacity-gb requires --part-parallel")
    if (args.slice_timeout is not None or args.max_retries is not None) \
            and args.part_parallel is None:
        ap.error("--slice-timeout/--max-retries configure the part-parallel "
                 "watchdog; they require --part-parallel")
    if args.ckpt_retain < 1:
        ap.error("--ckpt-retain must be >= 1")

    fault_plan = None
    if args.fault:
        from repro.runtime import FaultPlan

        try:
            fault_plan = FaultPlan.parse(args.fault)
        except ValueError as e:
            ap.error(str(e))

    part_parallel_plan = None
    if args.devices is not None:
        from repro.launch.mesh import (
            force_host_device_count,
            make_mesh_plan_for_devices,
        )

        if os.environ.get("JAX_PLATFORMS") == "cpu":
            # CPU runs emulate the mesh. The flag edit must precede the
            # first backend query; every import so far touches only
            # numpy/argparse, so the backend is still cold.
            force_host_device_count(args.devices)
        # Slot-shard over "model" when the "data" axis still divides into
        # --part-parallel slices afterwards; otherwise keep the mesh flat.
        mp = 2 if args.devices % (2 * args.part_parallel) == 0 else 1
        try:
            part_parallel_plan = make_mesh_plan_for_devices(
                args.devices, model_parallel=mp
            )
        except ValueError as e:
            ap.error(str(e))

    t0 = time.perf_counter()
    g, ingest = load_graph(args.graph, args.seed, edge_chunk=args.edge_chunk)
    ingest_s = time.perf_counter() - t0
    print(f"graph: n={g.n_nodes:,} m={g.n_edges:,} max_deg={int(g.degrees.max())}")
    if ingest is not None:
        print(f"ingest (streamed, {ingest_s:.2f}s): chunk={ingest.chunk_edges:,} edges, "
              f"{ingest.n_chunks} chunks, {ingest.n_bins} dedup bins, "
              f"spill={ingest.spill_bytes/2**20:.1f} MiB; "
              f"peak transient {ingest.peak_transient_bytes/2**20:.2f} MiB "
              f"vs in-memory baseline {ingest.baseline_transient_bytes/2**20:.2f} MiB "
              f"(output CSR {ingest.output_bytes/2**20:.2f} MiB)")

    budget_bytes = (
        int(args.budget_gb * 2**30) if args.budget_gb is not None else None
    )
    plan_s = 0.0
    if budget_bytes is not None:
        with recording() as planning:
            thresholds = plan_thresholds(g.degrees, budget_bytes)
        plan_s = stage_seconds(planning.records)["kcore.plan"].total_s
        print(f"planned thresholds for {args.budget_gb} GB/part: {thresholds}")
    else:
        thresholds = [int(t) for t in args.thresholds.split(",") if t]

    # Price the part-parallel slices against the real budget: an oversized
    # part then fails LPT assignment at planning time (SliceCapacityError,
    # caught below as a re-divide) instead of OOMing mid-wave.
    slice_capacity_bytes = None
    if args.part_parallel is not None:
        if args.slice_capacity_gb is not None:
            slice_capacity_bytes = int(args.slice_capacity_gb * 2**30)
        elif budget_bytes is not None:
            slice_capacity_bytes = budget_bytes

    core, report, thresholds, n_replans = run_with_capacity_replan(
        g, thresholds,
        replan_budget_bytes=budget_bytes,
        strategy=args.strategy,
        reorder=args.reorder,
        reorder_sample_edges=args.reorder_sample,
        max_bucket_rows=args.max_bucket_rows,
        checkpoint_dir=args.checkpoint_dir,
        resume=args.resume,
        divide_chunk=args.divide_chunk,
        sweep_checkpoint_every=args.sweep_checkpoint_every,
        overlap=args.overlap,
        engine=args.engine, int16=args.int16,
        part_parallel=args.part_parallel,
        part_parallel_plan=part_parallel_plan,
        slice_capacity_bytes=slice_capacity_bytes,
        slice_timeout_s=args.slice_timeout,
        max_retries=args.max_retries,
        fault_plan=fault_plan,
        ckpt_retain=args.ckpt_retain)
    if n_replans:
        print(f"capacity re-divides: {n_replans} (final thresholds "
              f"{thresholds})")
    print(f"\nDC-kCore done in {report.total_time_s:.2f}s "
          f"(preprocess {report.preprocess_time_s:.2f}s, engine={args.engine}"
          f"{'+int16' if args.int16 else ''}, reorder={args.reorder}, "
          f"overlap={'on' if report.overlap else 'off'})")
    print(stage_line(report, plan_s))
    print(compiles_line(report))
    if report.overlap:
        print(f"prefetch: {report.prefetch_hits} hit(s), "
              f"{report.prefetch_misses} miss(es) recomputed")
    if report.part_parallel:
        util = "/".join(f"{u:.2f}" for u in report.slice_utilization)
        print(f"part-parallel: {report.part_parallel} slice(s), wave wall "
              f"{report.conquer_wall_s:.2f}s, slice utilization [{util}], "
              f"{report.prefetch_hits} speculation hit(s), "
              f"{report.prefetch_misses} miss(es), "
              f"{report.speculation_discards} conquer(s) discarded, "
              f"boundary-exchange bytes = {report.boundary_exchange_bytes:,}")
    if (report.retries or report.blacklisted_slices or report.degraded_waves
            or report.quarantined_steps):
        bl = ",".join(str(s) for s in report.blacklisted_slices) or "-"
        print(f"fault tolerance: {report.retries} part retr"
              f"{'y' if report.retries == 1 else 'ies'}, "
              f"blacklisted slices [{bl}], "
              f"{report.degraded_waves} degraded wave(s), "
              f"{report.quarantined_steps} quarantined checkpoint step(s)")
    if args.fault_log:
        events = list(report.fault_events)
        if fault_plan is not None:
            events += [e for e in fault_plan.events if e not in events]
        with open(args.fault_log, "w") as f:
            json.dump({"events": events}, f, indent=2, default=str)
        print(f"fault-event log: {len(events)} event(s) -> {args.fault_log}")
    if report.resumed_parts:
        print(f"resumed: {report.resumed_parts} part(s) restored from "
              f"{args.checkpoint_dir}, not re-run")
    mid = [p for p in report.parts if p.resumed_at_sweep]
    for p in mid:
        print(f"resumed mid-part: {p.name} warm-restarted at sweep "
              f"{p.resumed_at_sweep} from a sweep snapshot")
    print(f"k_max = {int(core.max())}, total comm = {report.total_comm:,} updates, "
          f"peak part bytes = {report.peak_bytes/2**20:.1f} MiB")
    print(f"sweep work (frontier): {report.total_gathered_rows:,} gathered rows "
          f"vs {report.total_full_sweep_rows:,} full-sweep rows; "
          f"measured collective bytes = {report.total_collective_bytes:,}")
    if args.checkpoint_dir:
        # save_s = time the pipeline was BLOCKED on saving; save_wall_s =
        # what the completed writes actually cost (hidden behind sweeps
        # when --overlap makes the saves async).
        print(f"checkpoint saves: blocked {report.total_save_time_s:.3f}s, "
              f"completed writes {report.total_save_wall_s:.3f}s "
              f"({args.checkpoint_dir})")
    for p in report.parts:
        print(f"  part {p.name:>10}: n={p.n_nodes:>9,} m={p.n_edges:>11,} "
              f"iters={p.iterations:>3} comm={p.comm_amount:>10,} "
              f"work={p.gathered_rows:>10,}/{p.full_sweep_rows:<10,} "
              f"adj_density={p.bitmap_density:.3f} coll_bytes={p.collective_bytes:,} "
              f"divide_peak={p.divide_transient_bytes/2**20:.2f}MiB "
              f"save_s={p.save_time_s:.3f} save_wall_s={p.save_wall_s:.3f} "
              f"finalized={p.finalized:,}"
              + (f" slice={p.slice_index} wave={p.wave} "
                 f"modeled={p.modeled_cost_bytes:,}B"
                 if p.slice_index >= 0 else "")
              + (" [prefetched]" if p.prefetched else ""))
    if args.check:
        t0 = time.perf_counter()
        oracle = peel_coreness(g)
        ok = bool((core == oracle).all())
        print(f"oracle check ({time.perf_counter()-t0:.1f}s): {'CONSISTENT' if ok else 'MISMATCH'}")
        if not ok:
            raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
