#!/usr/bin/env python3
"""The sound program and the control, side by side, on many seeds of a cell.

    python3 bench/control.py --workload kron-divided --seeds 1,2,3 \
        --control-seeds 1,2,3

For each seed it builds the cell's graph, runs one job of the program as the
cell configures it and, on the control seeds, one job of the control, and
compares both with the plain reference. It prints one JSON line per seed and
a summary: the largest count of wrong vertices any sound job gave (the lower
reading of ``wrong_nodes``) and the smallest any control job gave (the upper
one). The benchmark's own runs never run the control.

The control breaks the configuration's guarantee, exact coreness, in the
way that would tempt a faster program: it stops each part's h-index fixed
point early. It is the program's own engine with its ``max_iter`` path
switched on, capped two sweeps short of the count the part needs, so only
the last sweep that still changes an estimate is skipped. On a mix with a
mesh plan the engine is each slice's ``decompose_distributed``, which
``dc_kcore`` builds itself; on any other mix it is ``decompose``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import graphs, job as jobs_mod, reference, run as bench_run  # noqa: E402


def two_short(fn):
    """The engine ``fn`` capped two sweeps short of its fixed point."""

    def early(bg, **kw):
        full = fn(bg, **kw)
        return fn(bg, max_iter=max(1, full.iterations - 2), **kw)

    return early


def control_dc(g, thresholds=(), **kw):
    """``dc_kcore`` whose engine stops two sweeps short of the fixed point."""
    from repro.core import partsched
    from repro.core.dckcore import dc_kcore
    from repro.core.decompose import decompose

    if kw.get("part_parallel_plan") is None:
        engine, int16 = kw.pop("engine"), kw.pop("int16")
        return dc_kcore(g, thresholds, **kw, decompose_fn=two_short(
            lambda bg, **dkw: decompose(bg, op=engine, int16=int16, **dkw)))
    # dc_kcore builds one distributed engine per mesh slice and refuses a
    # decompose_fn beside a plan, so the cap goes on the engines it builds.
    real = partsched.make_slice_decomposes

    def slice_decomposes(plan, n_slices, **ekw):
        plans, fns = real(plan, n_slices, **ekw)
        return plans, [two_short(f) for f in fns]

    partsched.make_slice_decomposes = slice_decomposes
    try:
        return dc_kcore(g, thresholds, **kw)
    finally:
        partsched.make_slice_decomposes = real


def parse_seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None, *, require_accelerator: bool = True,
         overrides=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=parse_seeds, required=True)
    ap.add_argument("--control-seeds", type=parse_seeds, default=[])
    args = ap.parse_args(sys.argv[1:] if argv is None else argv)
    _spec, cell, config, traffic = bench_run.load_cell(args.workload)
    config = {**config, **(overrides or {})}
    if os.path.join(ROOT, "src") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from repro.graph.structs import Graph

    if bench_run.accelerator(jax, int(cell["chips"]), require_accelerator,
                             jobs_mod.devices_of(traffic)) is None:
        return 2
    if require_accelerator:
        bench_run.use_compile_cache(jax)
    sound, control = [], []
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        csr = graphs.make_graph(config, seed)
        g = Graph(indptr=csr.indptr, indices=csr.indices, n_nodes=csr.n)
        budget = jobs_mod.budget_bytes(traffic, csr.degrees)
        kwargs = jobs_mod.dc_kwargs(traffic, budget)
        ref = reference.coreness(csr.indptr, csr.indices)
        line = {"seed": seed, "n": csr.n, "m": csr.m}
        if seed in args.seeds:
            job = jobs_mod.run_job(jax, g, budget, kwargs)
            line["sound_wrong"] = bench_run.wrong_nodes(job.core, ref)
            line["sound_wall_s"] = job.wall_s
            line["sweeps"] = job.report.total_iterations
            sound.append(line["sound_wrong"])
        if seed in args.control_seeds:
            t = time.perf_counter()
            job = jobs_mod.run_job(jax, g, budget, kwargs, dc=control_dc)
            line["control_wrong"] = bench_run.wrong_nodes(job.core, ref)
            line["control_wall_s"] = time.perf_counter() - t
            control.append(line["control_wrong"])
        print(json.dumps(line), flush=True)
    print(json.dumps({
        "workload": args.workload,
        "sound_seeds": len(sound), "lower_wrong_nodes": max(sound, default=None),
        "control_seeds": len(control),
        "upper_wrong_nodes": min(control, default=None),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
