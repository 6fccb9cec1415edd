import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Dry-run of the DISTRIBUTED K-CORE sweep at the paper's true scales.

The paper's graphs (com-friendster 1.8B, WX-15B, WX-136B edges) cannot be
materialized here, but the shard_map sweep can be lowered and compiled from
ShapeDtypeStruct stand-ins exactly like the LM dry-run: bucket shapes come
from a power-law degree model calibrated to (n, m). This reproduces the
paper's central scalability claim on the TPU mesh:

  * WX-136B **monolithic** (the PSGraph baseline): node ids exceed int32 and
    the replicated coreness + ext vectors alone need ~18 GiB/chip -> does
    NOT fit the 16 GiB v5e budget. (Paper: "PSGraph fails WX-136B".)
  * WX-136B **divided** (Rough-Divide at t=250, the paper's threshold): the
    top part is small; the rest part fits int32 ids and — with the int16
    coreness wire — the 16 GiB budget. (Paper: DC-kCore completes WX-136B.)

Usage:
    python -m repro.launch.kcore_dryrun [--wire int16] [--cand 2048]
"""
import argparse
import dataclasses
import json
import math
import time

import numpy as np

ARTIFACT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))),
    "benchmarks", "artifacts", "kcore",
)

# (name, n_nodes, n_edges, divide_threshold, k_max from the paper)
WORKLOADS = {
    "com-friendster": (65_608_366, 1_806_067_135, 80, 304),
    "WX-15B": (646_408_482, 15_179_911_593, 100, 401),
    "WX-136B": (2_226_845_928, 136_588_315_957, 250, 1_179),
}


def powerlaw_bucket_rows(n: int, m: int, max_width: int = 1 << 20):
    """Rows per power-of-two degree bucket for a power-law degree model
    calibrated so the mean degree matches 2m/n. Hub nodes above max_width
    are assumed degree-split (standard virtual-node trick; documented)."""
    mu = 2 * m / n
    # discrete P(d) ~ d^-alpha on [1, max_width]; solve alpha for mean mu.
    ds = np.arange(1, max_width + 1, dtype=np.float64)

    def mean_for(alpha):
        w = ds ** (-alpha)
        return float((ds * w).sum() / w.sum())

    lo, hi = 1.05, 3.5
    for _ in range(60):
        mid = (lo + hi) / 2
        if mean_for(mid) > mu:
            lo = mid
        else:
            hi = mid
    alpha = (lo + hi) / 2
    w = ds ** (-alpha)
    p = w / w.sum()
    buckets = []
    width = 8
    lo_d = 1
    while lo_d <= max_width:
        hi_d = min(width, max_width)
        frac = p[lo_d - 1 : hi_d].sum()
        rows = int(n * frac)
        if rows > 0:
            buckets.append((width, rows))
        lo_d = width + 1
        width *= 2
    return alpha, buckets


def degseq_hindex(buckets) -> int:
    """h-index of the modeled degree sequence (candidate window bound)."""
    best = 0
    for h in [8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384, 32768]:
        cnt = sum(rows for width, rows in buckets if width >= h)
        if cnt >= h:
            best = h
    return best


def build_specs_for(n: int, buckets, plan, wire_dtype, id_dtype):
    import jax
    import jax.numpy as jnp

    ns, ms = plan.n_node_shards, plan.n_slot_shards
    bucket_specs = []
    for width, rows in buckets:
        rows_p = max(ns, int(math.ceil(rows / ns)) * ns)
        width_p = max(ms * 8, int(math.ceil(width / ms)) * ms)
        bucket_specs.append(
            (
                jax.ShapeDtypeStruct((rows_p,), jnp.int32),
                jax.ShapeDtypeStruct((rows_p, width_p), id_dtype),
            )
        )
    c = jax.ShapeDtypeStruct((n + 1,), wire_dtype)
    ext = jax.ShapeDtypeStruct((n + 1,), jnp.int32)
    # Frontier plumbing: the mask models a full sweep (all buckets active)
    # at compile time; node_tile is the replicated int16 node -> bucket map
    # (bucket counts are tiny; 2 bytes/node, same class as the int16 wire).
    active = jax.ShapeDtypeStruct((len(bucket_specs),), jnp.bool_)
    node_tile = jax.ShapeDtypeStruct((n + 1,), jnp.int16)
    return c, ext, active, node_tile, bucket_specs


def run_case(name, n, m, cand, wire, multi_pod=True, tag="", n_iters=30):
    import jax
    import jax.numpy as jnp

    from repro.core.distributed import (
        MeshPlan,
        make_sweep_fn,
        planned_collective_schedule,
    )
    from repro.launch.mesh import make_production_mesh
    from repro.roofline import hw
    from repro.roofline.analysis import parse_collectives, roofline_terms

    mesh = make_production_mesh(multi_pod=multi_pod)
    node_axes = ("pod", "data") if multi_pod else ("data",)
    plan = MeshPlan(mesh=mesh, node_axes=node_axes, slot_axes=("model",))
    alpha, buckets = powerlaw_bucket_rows(n, m)
    wire_dtype = jnp.int16 if wire == "int16" else jnp.int32
    id_dtype = jnp.int32 if n < 2**31 else jnp.int64

    # Feasibility: replicated state + sharded tiles per device.
    id_bytes = 4 if id_dtype == jnp.int32 else 8
    wire_bytes = 2 if wire == "int16" else 4
    slots = sum(r * max(8, w) for w, r in buckets)
    tiles_dev = slots * id_bytes / mesh.size
    # coreness (wire) + ext (int16) + frontier node->bucket map (int16)
    state_dev = (n + 1) * (wire_bytes + 2 + 2)
    total_dev = tiles_dev + state_dev + 512 * 2**20
    fits = total_dev < hw.HBM_BYTES
    rec = {
        "case": f"{name}{tag}",
        "n": n,
        "m": m,
        "alpha": round(alpha, 3),
        "mesh": "2x16x16" if multi_pod else "16x16",
        "cand": cand,
        "wire": wire,
        "id_dtype": str(id_dtype.__name__),
        "memory_model": {
            "tiles_dev": tiles_dev,
            "state_dev": state_dev,
            "total_dev": total_dev,
        },
        "fits_16gb": bool(fits),
    }
    # Modeled collective traffic: a dry run never sweeps, so the table
    # derives per-iteration ICI bytes from the planned frontier schedule
    # over the modeled bucket shapes — same per-bucket ring formula as the
    # live engine's measured counter (see planned_collective_schedule; the
    # pinning test holds the two together). Reported even for infeasible
    # layouts: the formula only needs shapes.
    sched = planned_collective_schedule(
        [r for _w, r in buckets], plan, cand,
        wire_bytes=wire_bytes, n_iters=n_iters,
    )
    rec["modeled_collectives"] = {
        "n_iters": n_iters,
        "first_sweep_bytes": sched[0],
        "total_bytes": sum(sched),
        "per_iter_bytes": sched,
    }
    if n + 1 >= 2**31:
        # int64 ids double the tile bytes AND overflow JAX's int32 scatter
        # paths — the monolithic 2.2B-node layout is infeasible outright;
        # the divide step is what brings every part under 2^31 ids.
        rec["fits_16gb"] = False
        rec["skipped_compile"] = "node ids exceed int32 (monolithic 2.2B-node layout)"
        _dump(rec)
        return rec
    if not fits:
        rec["skipped_compile"] = "exceeds per-device HBM — infeasible layout"
        _dump(rec)
        return rec

    c, ext, active, node_tile, bucket_specs = build_specs_for(
        n, buckets, plan, wire_dtype, id_dtype
    )
    sweep = make_sweep_fn(plan, cand, wire_dtype)(len(bucket_specs))
    t0 = time.perf_counter()
    with mesh:
        lowered = sweep.lower(c, ext, active, node_tile, bucket_specs)
        compiled = lowered.compile()
    rec["compile_s"] = round(time.perf_counter() - t0, 1)
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    colls = parse_collectives(compiled.as_text())
    rl = roofline_terms(
        float(cost.get("flops", 0.0)),
        float(cost.get("bytes accessed", 0.0)),
        colls.total_wire,
        mesh.size,
    )
    rec["xla_temp_bytes"] = mem.temp_size_in_bytes
    rec["collectives"] = {"wire_bytes": colls.wire_bytes, "count": colls.count}
    rec["roofline"] = rl.as_dict()
    _dump(rec)
    return rec


def _dump(rec):
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    path = os.path.join(ARTIFACT_DIR, f"{rec['case']}__{rec['mesh']}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    rl = rec.get("roofline")
    extra = (
        f"compute={rl['compute_s']:.4g}s memory={rl['memory_s']:.4g}s "
        f"collective={rl['collective_s']:.4g}s [{rl['bottleneck']}]"
        if rl
        else rec.get("skipped_compile", "")
    )
    mc = rec.get("modeled_collectives")
    coll = (
        f"coll/iter0={mc['first_sweep_bytes']/2**30:.3f}GiB "
        f"coll_total={mc['total_bytes']/2**30:.2f}GiB "
        if mc else ""
    )
    print(
        f"{rec['case']:34s} mesh={rec['mesh']} fits16g={rec['fits_16gb']} "
        f"dev_mem={rec['memory_model']['total_dev']/2**30:.1f}GiB {coll}{extra}",
        flush=True,
    )


def run_split3(name, n, m, t, kmax, wire, tag=""):
    """Recursive Rough-Divide into 3 parts (paper §5.6): the TPU id/memory
    budget forces more parts for WX-136B than the paper's CPU cluster used.
    Part sizes are modeled from the degree buckets (in-part adjacency is
    conservatively the full bucket width)."""
    _alpha, buckets = powerlaw_bucket_rows(n, m)
    top = [(w, r) for w, r in buckets if w >= 2 * t]
    mid = [(w, r) for w, r in buckets if 8 < w < 2 * t]
    bot = [(w, r) for w, r in buckets if w <= 8]
    for label, part, cand in [
        (f"top(t={t})", top, min(2 * kmax, 4096)),
        (f"mid(8<d<{t})", mid, t),
        ("bottom(d<=8)", bot, 8),
    ]:
        pn = sum(r for _w, r in part)
        pm = sum(r * w for w, r in part) // 2
        run_case(f"{name}-3p-{label}", max(pn, 1 << 20), max(pm, 1 << 22), cand,
                 wire, multi_pod=True, tag=tag)


def run_slices(name, n, m, t, kmax, wire, n_slices, tag=""):
    """Part-parallel schedule table: price the 3-part split's parts with
    the production scheduler (``part_cost`` + ``assign_parts``) on the
    single-pod 16x16 mesh divided into ``n_slices`` slices along "data".
    Pure planning-layer math — no devices are touched, so this prints the
    same placement the live part-parallel engine would compute."""
    from repro.core.partsched import SliceSpec, assign_parts, part_cost

    node_shards, slot_shards = 16, 16
    if node_shards % n_slices != 0:
        raise SystemExit(f"--slices must divide the {node_shards}-way node axis")
    specs = [
        SliceSpec(index=i, n_node_shards=node_shards // n_slices,
                  n_slot_shards=slot_shards)
        for i in range(n_slices)
    ]
    wire_bytes = 2 if wire == "int16" else 4
    _alpha, buckets = powerlaw_bucket_rows(n, m)
    splits = [
        (f"top(t={t})", [(w, r) for w, r in buckets if w >= 2 * t],
         min(2 * kmax, 4096)),
        (f"mid(8<d<{t})", [(w, r) for w, r in buckets if 8 < w < 2 * t], t),
        ("bottom(d<=8)", [(w, r) for w, r in buckets if w <= 8], 8),
    ]
    costs, labels = [], {}
    for cursor, (label, part, cand) in enumerate(splits):
        shapes = [(r, w) for w, r in part]
        pn = max(sum(r for _w, r in part), 1)
        c = part_cost(shapes, cand, pn, specs[0], wire_bytes=wire_bytes)
        costs.append(dataclasses.replace(c, cursor=cursor))
        labels[cursor] = label
    sched = assign_parts(costs, specs)
    loads = sched.slice_loads()
    peak = max(loads) or 1
    print(f"\n{name}{tag}: 3-part split on 16x16 / {n_slices} slices "
          f"({specs[0].n_node_shards}x{specs[0].n_slot_shards} each, wire={wire})")
    for a in sched.assignments:
        c = a.cost
        print(f"  part {a.cursor} {labels[a.cursor]:16s} -> slice {a.slice_index}  "
              f"coll={c.collective_bytes/2**30:8.2f}GiB  "
              f"hbm/dev={c.hbm_bytes/2**30:8.2f}GiB  "
              f"resident/dev={c.part_bytes/2**30:6.2f}GiB")
    for i, load in enumerate(loads):
        bar = "#" * int(40 * load / peak)
        print(f"  slice {i}: modeled {load/2**30:10.2f}GiB  "
              f"util={load/peak:5.1%}  {bar}")
    rec = {
        "case": f"{name}{tag}-slices{n_slices}",
        "mesh": "16x16",
        "n_slices": n_slices,
        "wire": wire,
        "decisions": [{**d, "label": labels[d["cursor"]]}
                      for d in sched.decisions()],
        "slice_loads": loads,
        "slice_utilization": [load / peak for load in loads],
    }
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    with open(os.path.join(ARTIFACT_DIR, f"{rec['case']}__16x16.json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--wire", choices=["int32", "int16"], default="int32")
    ap.add_argument("--cand", type=int, default=None, help="candidate window")
    ap.add_argument("--tag", default="")
    ap.add_argument("--case", default=None)
    ap.add_argument("--split3", action="store_true")
    ap.add_argument("--mono-only", action="store_true")
    ap.add_argument("--slices", type=int, default=None,
                    help="print the part-parallel schedule table for the "
                         "3-part split across N mesh slices (planning only)")
    args = ap.parse_args()

    for name, (n, m, t, kmax) in WORKLOADS.items():
        if args.case and args.case != name:
            continue
        if args.slices:
            run_slices(name, n, m, t, kmax, args.wire, args.slices, tag=args.tag)
            continue
        if args.split3:
            run_split3(name, n, m, t, kmax, args.wire, tag=args.tag)
            continue
        _alpha, buckets = powerlaw_bucket_rows(n, m)
        cand = args.cand or degseq_hindex(buckets)
        # Monolithic (PSGraph baseline).
        run_case(name, n, m, cand, args.wire, multi_pod=True, tag=args.tag + "-mono")
        if args.mono_only:
            continue
        # Rough-Divide at the paper's threshold: top part (deg >= t) and the
        # rest (modeled sizes: nodes with modeled degree >= t go to the top).
        top_n = sum(r for w, r in buckets if w >= t)
        top_m = sum(r * min(w, 4 * t) for w, r in buckets if w >= t) // 2
        rest_n, rest_m = n - top_n, m - top_m
        run_case(f"{name}-top(t={t})", max(top_n, 1 << 20), max(top_m, 1 << 22),
                 min(cand, kmax * 2), args.wire, multi_pod=True, tag=args.tag)
        run_case(f"{name}-rest(t={t})", rest_n, rest_m, min(cand, t),
                 args.wire, multi_pod=True, tag=args.tag)


if __name__ == "__main__":
    main()
