import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape) cell, on the single-pod 16x16 mesh
AND the 2x16x16 multi-pod mesh:

    with mesh:
        lowered  = jax.jit(step, in_shardings=..., out_shardings=...).lower(**input_specs(arch))
        compiled = lowered.compile()
        print(compiled.memory_analysis())   # proves it fits 16 GB/chip
        print(compiled.cost_analysis())     # FLOPs/bytes for the roofline

plus collective-byte extraction from the post-SPMD HLO. One JSON artifact
per cell lands in ``benchmarks/artifacts/dryrun/`` — the roofline tables in
EXPERIMENTS.md and ``benchmarks/bench_dryrun.py`` read from there.

Usage:
    python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    python -m repro.launch.dryrun --all                  # single-pod pass
    python -m repro.launch.dryrun --all --multi-pod      # 512-chip pass
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import SHAPES, cells
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs, rules_for
from repro.launch.steps import step_fn_for
from repro.models.model import build_specs
from repro.models.module import count_params
from repro.roofline import hw
from repro.roofline import flops_model
from repro.roofline.analysis import (
    active_params,
    model_flops,
    parse_collectives,
    roofline_terms,
)
from repro.sharding.policy import active_mesh, dp_size

MICRO_PER_DEVICE = 2  # target per-device microbatch rows for train cells
BIG_MODEL_PARAMS = 50e9  # above this, microbatch 1 row/device (stash budget)

ARTIFACT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))),
    "benchmarks", "artifacts", "dryrun",
)


def run_cell(arch: str, shape_name: str, multi_pod: bool, rules=None,
             artifact_dir: str = ARTIFACT_DIR, tag: str = "",
             accum_override: int = None, grad_constrain: bool = False,
             accum_dtype=None) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    shape = SHAPES[shape_name]
    t0 = time.perf_counter()
    specs, cfg, log = input_specs(arch, shape_name, mesh, rules=rules)
    the_rules = rules or rules_for(cfg, shape_name)
    n_params = count_params(build_specs(cfg))
    accum = 1
    if shape.kind == "train":
        per_dev = max(1, shape.global_batch // dp_size(mesh, the_rules))
        micro = 1 if n_params > BIG_MODEL_PARAMS else MICRO_PER_DEVICE
        accum = max(1, per_dev // micro)
        if accum_override:
            accum = accum_override
    grad_shardings = None
    if grad_constrain:
        grad_shardings = jax.tree.map(lambda s: s.sharding, specs["params"])
    fn, order = step_fn_for(
        cfg, shape.kind, accum_steps=accum, grad_shardings=grad_shardings,
        accum_dtype=accum_dtype,
    )
    kwargs = {k: specs[k] for k in order}

    with mesh, active_mesh(mesh, the_rules):
        lowered = jax.jit(fn).lower(**kwargs)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    print(mem)
    print({k: v for k, v in cost.items() if k in ("flops", "bytes accessed")})
    hlo = compiled.as_text()
    colls = parse_collectives(hlo)

    # HLO cost_analysis counts scan bodies once (loop-blind); the roofline
    # compute/memory terms come from the analytic model instead, which
    # tests validate against unrolled HLO. Collectives are loop-corrected
    # by parse_collectives.
    hlo_flops_dev = float(cost.get("flops", 0.0))
    hlo_bytes_dev = float(cost.get("bytes accessed", 0.0))
    n_active = active_params(cfg)
    mflops = model_flops(cfg, shape, n_params, n_active)
    analytic = flops_model.cost(
        cfg, shape, n_params, n_chips, remat=(shape.kind == "train")
    )
    flops_dev = analytic.flops_total / n_chips
    bytes_dev = analytic.hbm_bytes_per_device
    rl = roofline_terms(flops_dev, bytes_dev, colls.total_wire, n_chips, mflops)

    per_dev_hbm = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    )
    mem_model = flops_model.device_memory_model(
        cfg, shape, n_params, n_chips, dp_size(mesh, the_rules), accum
    )
    record = {
        "arch": arch,
        "shape": shape_name,
        "kind": shape.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "params": n_params,
        "active_params": n_active,
        "accum_steps": accum,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "hlo_flops_per_device_loopblind": hlo_flops_dev,
        "hlo_bytes_per_device_loopblind": hlo_bytes_dev,
        "analytic_detail": analytic.detail,
        "memory_analysis": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "cpu_backend_peak_bytes": per_dev_hbm,
        },
        # TPU-faithful analytic budget (CPU temp includes scatter-expander /
        # convert-hoist artifacts absent on the target; see flops_model).
        "memory_model": mem_model,
        "fits_16gb": bool(mem_model["total"] < hw.HBM_BYTES),
        "collectives": {
            "count": colls.count,
            "raw_bytes": colls.op_bytes,
            "wire_bytes": colls.wire_bytes,
            "total_wire_bytes": colls.total_wire,
        },
        "roofline": rl.as_dict(),
        "replicated_fallbacks": [
            {"axes": list(map(str, a)), "dim": d, "size": s, "axis_size": m}
            for (a, d, s, m) in log.replicated
        ],
    }
    os.makedirs(artifact_dir, exist_ok=True)
    fname = f"{arch}__{shape_name}__{record['mesh']}{tag}.json"
    with open(os.path.join(artifact_dir, fname), "w") as f:
        json.dump(record, f, indent=1)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--artifact-dir", default=ARTIFACT_DIR)
    ap.add_argument("--tag", default="", help="artifact filename suffix (perf variants)")
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--grad-constrain", action="store_true")
    ap.add_argument("--accum-dtype", choices=["f32", "bf16"], default=None)
    ap.add_argument("--rules", choices=["default", "serve"], default="default")
    args = ap.parse_args()

    import jax.numpy as jnp
    accum_dtype = {None: None, "f32": jnp.float32, "bf16": jnp.bfloat16}[args.accum_dtype]
    rules_override = None
    if args.rules == "serve":
        from repro.sharding.policy import SERVE_RULES
        rules_override = dict(SERVE_RULES)

    todo = []
    if args.all:
        todo = cells()
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        todo = [(args.arch, args.shape)]
    meshes = [args.multi_pod] if not args.both_meshes else [False, True]

    failures = []
    for arch, shape_name in todo:
        for mp in meshes:
            label = f"{arch} x {shape_name} x {'2x16x16' if mp else '16x16'}"
            print(f"=== {label} ===", flush=True)
            try:
                rec = run_cell(
                    arch, shape_name, mp, rules=rules_override,
                    artifact_dir=args.artifact_dir, tag=args.tag,
                    accum_override=args.accum,
                    grad_constrain=args.grad_constrain,
                    accum_dtype=accum_dtype,
                )
                rl = rec["roofline"]
                print(
                    f"  ok: compute={rl['compute_s']:.4g}s memory={rl['memory_s']:.4g}s "
                    f"collective={rl['collective_s']:.4g}s bottleneck={rl['bottleneck']} "
                    f"(lower {rec['lower_s']}s, compile {rec['compile_s']}s)",
                    flush=True,
                )
            except Exception as e:  # noqa: BLE001
                failures.append((label, repr(e)))
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for label, err in failures:
            print(" ", label, err)
        raise SystemExit(1)
    print("\nall dry-run cells compiled OK")


if __name__ == "__main__":
    main()
