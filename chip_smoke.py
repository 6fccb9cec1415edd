#!/usr/bin/env python3
"""Smoke run of DC-kCore on a TPU: the main path, once, at a real size.

    python3 chip_smoke.py                  # one chip: phases a, b, c
    python3 chip_smoke.py --four-chips     # four chips: phases d, e only
    python3 chip_smoke.py --cpu-rehearsal --scale 12 --serve-scale 10

Every phase checks its coreness element for element against one
Batagelj-Zaversnik peeling result (``graph/oracle.py``) on the same graph:

  (a) monolithic ``dc_kcore`` with the default sorted engine, on a Graph500
      R-MAT (a=.57, b=.19, c=.19, edge factor 16) at ``--scale`` (22);
  (b) divided ``dc_kcore`` with ``engine="kernel"`` (the Pallas h-index
      kernel, compiled by Mosaic) under a per-part budget that splits the
      graph into at least 2 parts;
  (c) the ``kcore_serve`` CLI, in this process: it boots on an R-MAT at
      ``--serve-scale`` (18), drains sealed edit-log batches of inserts and
      deletes while answering queries, and its final coreness is checked
      against the oracle on the final graph;
  (d) ``--four-chips``: ``decompose_distributed(use_kernel=True)`` on a 2x2
      data x model mesh of the four chips;
  (e) ``--four-chips``: ``dc_kcore(part_parallel=2)`` (Exact-Divide) over
      that mesh split into 2 slices.

The oracle peels on a host thread while the device phases run. Each phase
prints n, m, its parts, its wall time, its set-up time (divide, bucketize
and compilation: a set-up time, not a speed), the process's peak device
bytes and the oracle verdict. The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The script exits non-zero, without that line, when a phase fails or JAX
finds no TPU; ``--cpu-rehearsal`` runs on the CPU backend instead and
reports it as such. Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SERVE_BATCHES = 3
SWAPS_PER_BATCH = 64


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phases (d, e)")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU backend (with --four-chips: four "
                         "virtual host devices); never reports a TPU")
    ap.add_argument("--scale", type=int, default=22,
                    help="R-MAT scale of phases a, b, d, e (2^scale nodes)")
    ap.add_argument("--serve-scale", type=int, default=18,
                    help="R-MAT scale the phase-c server boots on")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


class CompileClock:
    """Seconds of XLA backend compilation (Mosaic kernels included), summed
    over threads, read from JAX's own monitoring events."""

    def __init__(self, jax):
        self._lock = threading.Lock()
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.total += duration


class Oracle:
    """``peel_coreness`` of one graph on a daemon host thread, so the device
    phases overlap it and a failed phase exits without waiting for it."""

    def __init__(self, g):
        self._done = threading.Event()
        self._out = self._exc = None
        self._t0 = time.perf_counter()
        threading.Thread(target=self._run, args=(g,), daemon=True,
                         name="chip-smoke-oracle").start()

    def _run(self, g):
        from repro.graph.oracle import peel_coreness

        try:
            self._out = peel_coreness(g)
        except Exception as exc:  # re-raised by result() in the main thread
            self._exc = exc
        finally:
            print(f"oracle: peel_coreness took "
                  f"{time.perf_counter() - self._t0:.3f}s on a host thread",
                  flush=True)
            self._done.set()

    def result(self):
        self._done.wait()
        if self._exc is not None:
            raise self._exc
        return self._out


class Smoke:
    def __init__(self, jax, device):
        self.jax = jax
        self.device = device
        self.clock = CompileClock(jax)
        self.ok = True

    def peak_bytes(self):
        stats = self.device.memory_stats()
        return stats.get("peak_bytes_in_use") if stats else None

    def record(self, phase, *, n, m, parts, wall_s, setup_s, compile_s,
               match, extra=""):
        self.ok &= match
        print(f"[{phase}] n={n:,} m={m:,} parts={parts} wall_s={wall_s:.3f} "
              f"setup_s={setup_s:.3f} (divide + bucketize + compile; a "
              f"set-up time, not a speed) compile_s={compile_s:.3f} "
              f"peak_bytes_in_use={self.peak_bytes()} "
              f"oracle={'MATCH' if match else 'MISMATCH'}"
              + (f" {extra}" if extra else ""), flush=True)

    def timed(self, fn):
        """``(result, wall_s, compile_s)`` of ``fn()``."""
        c0, t0 = self.clock.total, time.perf_counter()
        out = fn()
        return out, time.perf_counter() - t0, self.clock.total - c0


def divide_budget(g):
    """A per-part budget of 60% of the planner's whole-graph estimate, so
    ``plan_thresholds`` must split the graph."""
    return int(0.6 * int(g.degrees.sum()) * 8)


def phase_monolithic(smoke, g, oracle):
    from repro.core.dckcore import dc_kcore

    (core, rep), wall, comp = smoke.timed(lambda: dc_kcore(g))
    smoke.record("a monolithic sorted", n=g.n_nodes, m=g.n_edges,
                 parts=len(rep.parts), wall_s=wall,
                 setup_s=rep.preprocess_time_s + comp, compile_s=comp,
                 match=bool((core == oracle.result()).all()),
                 extra=f"iterations={rep.total_iterations} "
                       f"sweep_s={rep.total_decompose_time_s:.3f}")


def phase_kernel(smoke, g, oracle):
    import jax
    import jax.numpy as jnp

    from repro.core.dckcore import dc_kcore
    from repro.core.divide import plan_thresholds
    from repro.kernels.hindex import hindex_op

    budget = divide_budget(g)
    thresholds = plan_thresholds(g.degrees, budget)
    (core, rep), wall, comp = smoke.timed(
        lambda: dc_kcore(g, thresholds, engine="kernel"))
    # The engine's kernel must be Mosaic's, not the interpreter's.
    rows = jax.ShapeDtypeStruct((1024,), jnp.int32)
    tile = jax.ShapeDtypeStruct((1024, 128), jnp.int32)
    hlo = hindex_op.lower(tile, rows, rows, cand=128).as_text()
    lowered = "tpu_custom_call" in hlo
    want_lowered = smoke.device.platform != "cpu"
    if lowered != want_lowered:
        print(f"[b] hindex_op lowering: tpu_custom_call={lowered}, expected "
              f"{want_lowered} on {smoke.device.platform}", flush=True)
    smoke.record("b divided kernel", n=g.n_nodes, m=g.n_edges,
                 parts=len(rep.parts), wall_s=wall,
                 setup_s=rep.preprocess_time_s + comp, compile_s=comp,
                 match=bool((core == oracle.result()).all())
                 and lowered == want_lowered and len(rep.parts) >= 2,
                 extra=f"budget_gb={budget / 2**30:.3f} "
                       f"thresholds={thresholds} "
                       f"hindex_tpu_custom_call={lowered} "
                       f"sweep_s={rep.total_decompose_time_s:.3f}")


def swap_batches(g, rng, n_batches, swaps):
    """Degree-preserving double-edge swaps: delete (a,b),(c,d), insert
    (a,d),(c,b). Every batch both inserts and deletes, and no degree moves,
    so each batch re-sweeps the same tile shapes as the boot run.

    Returns the batches as ``(ins_u, ins_v, del_u, del_v)`` and the edge
    keys ``u * n + v`` (u < v) of the final graph, built here by plain set
    arithmetic, independent of the server's CSR splicing."""
    import numpy as np

    n = g.n_nodes
    src = np.repeat(np.arange(n, dtype=np.int64), g.degrees)
    dst = g.indices.astype(np.int64)
    keys = np.unique(np.minimum(src, dst) * n + np.maximum(src, dst))
    keys = keys[keys // n != keys % n]
    batches = []
    for _ in range(n_batches):
        live = set(keys.tolist())
        touched = set()
        ins, dels = [], []
        while len(ins) < 2 * swaps:
            (a, b), (c, d) = divmod(int(rng.choice(keys)), n), \
                divmod(int(rng.choice(keys)), n)
            e1, e2 = min(a, d) * n + max(a, d), min(c, b) * n + max(c, b)
            ends = {a, b, c, d}
            if len(ends) < 4 or ends & touched or e1 in live or e2 in live:
                continue
            touched |= ends
            dels += [(a, b), (c, d)]
            ins += [(a, d), (c, b)]
        ins_a, del_a = np.array(ins, np.int64), np.array(dels, np.int64)
        batches.append((ins_a[:, 0], ins_a[:, 1], del_a[:, 0], del_a[:, 1]))
        ins_k = np.minimum(ins_a[:, 0], ins_a[:, 1]) * n + np.maximum(
            ins_a[:, 0], ins_a[:, 1])
        del_k = np.minimum(del_a[:, 0], del_a[:, 1]) * n + np.maximum(
            del_a[:, 0], del_a[:, 1])
        keys = np.union1d(np.setdiff1d(keys, del_k), ins_k)
    return batches, keys


def phase_serve(smoke, args):
    import numpy as np

    from repro.core.snapshot_pub import SnapshotPublisher
    from repro.graph.editlog import EditLog
    from repro.graph.generators import rmat
    from repro.graph.oracle import peel_coreness
    from repro.graph.structs import Graph
    from repro.launch import kcore_serve

    spec = f"rmat:{args.serve_scale}:16"
    g0 = rmat(args.serve_scale, 16, seed=args.seed)
    batches, final_keys = swap_batches(
        g0, np.random.default_rng(args.seed), SERVE_BATCHES, SWAPS_PER_BATCH)
    n = g0.n_nodes
    ref = Graph.from_edges(final_keys // n, final_keys % n, n_nodes=n)
    pub = SnapshotPublisher()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_editlog_") as d:
        with EditLog(d) as log:
            for iu, iv, du, dv in batches:
                log.append(iu, iv)
                log.append(du, dv, delete=True)
                log.seal_batch()
            metrics, wall, comp = smoke.timed(lambda: kcore_serve.main(
                ["--graph", spec, "--seed", str(args.seed),
                 "--edit-log", log.workdir,
                 "--max-batches", str(SERVE_BATCHES), "--json"],
                publisher=pub))
    snap = pub.snapshot
    same_graph = (np.array_equal(snap.graph.indptr, ref.indptr)
                  and np.array_equal(snap.graph.indices, ref.indices))
    match = (same_graph and metrics["batches_drained"] == SERVE_BATCHES
             and snap.verify()
             and np.array_equal(snap.coreness, peel_coreness(ref)))
    smoke.record("c serve", n=ref.n_nodes, m=ref.n_edges, parts=1,
                 wall_s=wall, setup_s=comp, compile_s=comp, match=match,
                 extra=f"graph={spec} batches={metrics['batches_drained']} "
                       f"edits={2 * 2 * SWAPS_PER_BATCH}/batch "
                       f"modes={metrics['update_modes']} "
                       f"queries={metrics['n_queries']} "
                       f"same_final_graph={same_graph}")


def phase_distributed(smoke, g, oracle, plan):
    from repro.core.distributed import decompose_distributed
    from repro.graph.build import bucketize

    t0 = time.perf_counter()
    bg = bucketize(g)
    bucketize_s = time.perf_counter() - t0
    res, wall, comp = smoke.timed(
        lambda: decompose_distributed(bg, plan, use_kernel=True))
    smoke.record("d shard_map use_kernel 2x2", n=g.n_nodes, m=g.n_edges,
                 parts=1, wall_s=wall + bucketize_s,
                 setup_s=bucketize_s + comp, compile_s=comp,
                 match=bool((res.coreness == oracle.result()).all()),
                 extra=f"iterations={res.iterations} "
                       f"collective_bytes={res.collective_bytes}")


def phase_part_parallel(smoke, g, oracle, plan):
    from repro.core.dckcore import dc_kcore
    from repro.core.divide import plan_thresholds

    thresholds = plan_thresholds(g.degrees, divide_budget(g))
    # Exact-Divide's speculative shrinks always hit, so a wave's parts
    # commit on both slices instead of being re-run on slice 0.
    (core, rep), wall, comp = smoke.timed(lambda: dc_kcore(
        g, thresholds, strategy="exact", part_parallel=2,
        part_parallel_plan=plan))
    slices = sorted({p.slice_index for p in rep.parts})
    smoke.record("e part_parallel=2 over 2 slices", n=g.n_nodes,
                 m=g.n_edges, parts=len(rep.parts), wall_s=wall,
                 setup_s=rep.preprocess_time_s + comp, compile_s=comp,
                 match=bool((core == oracle.result()).all())
                 and slices == [0, 1],
                 extra=f"thresholds={thresholds} slices={slices} "
                       f"boundary_exchange_bytes="
                       f"{rep.boundary_exchange_bytes}")


def main(argv=None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)
    n_chips = 4 if args.four_chips else 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.four_chips:
            from repro.launch.mesh import force_host_device_count

            force_host_device_count(n_chips)
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    want = "cpu" if args.cpu_rehearsal else "tpu"
    if platform != want:
        print(f"chip_smoke: JAX finds no {want.upper()} (platform "
              f"{platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < n_chips:
        print(f"chip_smoke: needs {n_chips} devices, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 1
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"device: {json.dumps(device)}")
    print(f"compile cache: {cache_dir}", flush=True)

    from repro.graph.generators import rmat

    smoke = Smoke(jax, devices[0])
    t0 = time.perf_counter()
    g = rmat(args.scale, 16, seed=args.seed)
    print(f"graph rmat:{args.scale}:16 seed={args.seed}: n={g.n_nodes:,} "
          f"m={g.n_edges:,} max_deg={int(g.degrees.max())}, generated in "
          f"{time.perf_counter() - t0:.3f}s (set-up)", flush=True)
    oracle = Oracle(g)
    if args.four_chips:
        from repro.launch.mesh import make_mesh_plan_for_devices

        plan = make_mesh_plan_for_devices(4, model_parallel=2)
        print(f"mesh: {dict(plan.mesh.shape)} over devices "
              f"{[d.id for d in plan.mesh.devices.flat]}", flush=True)
        phase_distributed(smoke, g, oracle, plan)
        phase_part_parallel(smoke, g, oracle, plan)
    else:
        phase_monolithic(smoke, g, oracle)
        phase_kernel(smoke, g, oracle)
        phase_serve(smoke, args)
    if not smoke.ok:
        print("chip_smoke: a phase disagreed with the oracle", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
