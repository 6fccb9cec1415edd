#!/usr/bin/env python3
"""Chip benchmark of DC-kCore: time to exact coreness, and the memory it takes.

    python3 bench/run.py --workload kron-divided --seed 7 --seconds 10 --trace 0

The workload is a cell of ``BENCHMARK.json``: a configuration (a graph,
``bench/configs/<name>.json``) under a traffic mix (how each job runs,
``bench/traffic/<name>.json``). Each metric the cell reports is read by
``bench/metrics/<name>.py``. Everything is found by name, so a cell, a
configuration, a mix or a metric is added by adding files and entries.

Set-up builds the graph from ``--seed`` (``bench/graphs.py``) and runs one
whole job as warm-up, which compiles every program the cell's jobs use.
The window then runs whole jobs (``bench/job.py``) back to back until
``--seconds`` have passed, and finishes the job in flight. With
``--trace 1`` the window runs under the profiler and the run reports the
cell's per-layer metrics instead of its end-to-end ones. After the window
every job's coreness, the warm-up's included, is compared vertex by vertex
with the plain reference (``bench/reference.py``) on the same graph.

A mix that names ``devices`` runs its jobs over the first ``devices`` of
the cell's chips (``bench/job.py``); device busy time and the shares priced
from it are taken over all the cell's chips, an idle one included.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with a trace), and
last ``checks``, each compared number with its limit. The run exits non-zero
without that line when JAX finds no TPU or fewer chips than the cell asks
for, or when the mix names more devices than the cell has chips. JAX's
compile cache is kept in ``.jax_cache`` at the checkout's root.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import graphs, job as jobs_mod, peaks as peaks_mod  # noqa: E402
from bench import reference, xplane  # noqa: E402
from bench.compiles import CompileClock  # noqa: E402

BENCH = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
CACHE_BYTES = 4 * 2**30
TRACE_DIR = os.path.join(ROOT, "artifacts", "bench_trace")
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    setup_s: float
    window_s: float                 # host clock, whole window
    jobs: list                      # bench.job.Job, the window's
    n: int
    m: int
    chips: int                      # the cell's chips
    peak_bytes: Optional[int]       # on the fullest chip
    peaks: Optional[dict]           # bench/peaks.json row; None off the chip
    trace: Optional[xplane.Summary]
    window_built: int               # compiles + cache loads in the window


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap.parse_args(argv)


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str):
    """``(spec, cell, config, traffic)`` of the cell ``name``."""
    spec = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = _json(os.path.join(ROOT, conf["file"]))
    traffic = _json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    return spec, cell, config, traffic


def metrics_of(spec: dict, cell_name: str, trace: bool):
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in spec[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def accelerator(jax, chips: int, require_tpu: bool = True,
                mix_devices: int = 1):
    """The cell's devices, or None (with the reason on stderr). A mix that
    runs on more devices than the cell has chips is refused too."""
    if mix_devices > chips:
        log(f"bench: the mix runs on {mix_devices} devices, the cell has "
            f"{chips} chips")
        return None
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        log(f"bench: JAX finds no TPU (platform {devices[0].platform!r})")
        return None
    if len(devices) < chips:
        log(f"bench: the cell needs {chips} chips, JAX finds {len(devices)}")
        return None
    return devices[:chips]


def use_compile_cache(jax) -> None:
    """Keep every compiled program, however quick, in the checkout's cache
    directory, handed to the program through ``JAX_COMPILATION_CACHE_DIR``.
    The cache may hold CACHE_BYTES: one seed's sweep programs take 50-150
    MB, and a smaller cap evicts them before the same seed runs again."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", CACHE_BYTES)


def peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def wrong_nodes(core, ref) -> int:
    if core.shape != ref.shape:
        return int(ref.size)
    return int((core != ref).sum())


def main(argv=None, *, require_accelerator: bool = True,
         overrides: Optional[dict] = None) -> int:
    """Run one cell. Tests pass ``require_accelerator=False`` to drive a run
    on the CPU backend at a size ``overrides`` shrinks; such a run keeps no
    compile cache and reports no peaks."""
    args = parse_args(sys.argv[1:] if argv is None else argv)
    spec, cell, config, traffic = load_cell(args.workload)
    config = {**config, **(overrides or {})}
    chips = int(cell["chips"])
    if os.path.join(ROOT, "src") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from repro.graph.structs import Graph

    devices = accelerator(jax, chips, require_accelerator,
                          jobs_mod.devices_of(traffic))
    if devices is None:
        return 2
    peaks = None
    if require_accelerator:
        peaks = peaks_mod.lookup(devices[0].device_kind)
        use_compile_cache(jax)
    clock = CompileClock(jax)

    t = time.perf_counter()
    csr = graphs.make_graph(config, args.seed)
    g = Graph(indptr=csr.indptr, indices=csr.indices, n_nodes=csr.n)
    budget = jobs_mod.budget_bytes(traffic, csr.degrees)
    kwargs = jobs_mod.dc_kwargs(traffic, budget)
    log(f"graph {config['name']} seed={args.seed}: n={csr.n} m={csr.m} "
        f"max_deg={int(csr.degrees.max())} budget_bytes={budget} "
        f"({time.perf_counter() - t:.3f}s)")
    warm = jobs_mod.run_job(jax, g, budget, kwargs)
    log(f"warm-up job: {warm.wall_s:.3f}s thresholds={warm.thresholds} "
        f"parts={len(warm.report.parts)} built={clock.built} "
        f"({clock.build_s:.3f}s) compiled={clock.compiled} "
        f"cache_loads={clock.cache_loads}")
    if warm.report.part_parallel:
        log(f"part-parallel: {warm.report.part_parallel} slices, part "
            f"slices {[p.slice_index for p in warm.report.parts]}, waves "
            f"{[p.wave for p in warm.report.parts]}, re-divides "
            f"{warm.replans}, boundary-exchange bytes "
            f"{warm.report.boundary_exchange_bytes}")

    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    built0 = clock.built
    window = []
    setup_s = time.perf_counter() - T0
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        w0 = time.perf_counter()
        while True:
            window.append(jobs_mod.run_job(jax, g, budget, kwargs))
            if time.perf_counter() - w0 >= args.seconds:
                break
        window_s = time.perf_counter() - w0
    window_built = clock.built - built0
    summary = None
    if args.trace:
        jax.profiler.stop_trace()
        t = time.perf_counter()
        summary = xplane.summarize(TRACE_DIR, devices[0].platform,
                                   WINDOW_SPAN, len(devices))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        log(f"trace: {summary.n_ops} device ops, busy {summary.busy_s:.3f}s "
            f"of {summary.window_s:.3f}s, read in "
            f"{time.perf_counter() - t:.3f}s")
    peak = peak_bytes(devices)
    log(f"window: {len(window)} jobs in {window_s:.3f}s, built "
        f"{window_built}; per job [wall, divide, sweep] seconds and sweeps: "
        + " ".join(f"[{j.wall_s:.3f} {j.plan_s + j.report.preprocess_time_s:.3f}"
                   f" {j.report.total_decompose_time_s:.3f}"
                   f" {j.report.total_iterations}]" for j in window))

    run = Run(setup_s=setup_s, window_s=window_s, jobs=window, n=csr.n,
              m=csr.m, chips=len(devices), peak_bytes=peak, peaks=peaks,
              trace=summary, window_built=window_built)
    metrics = {}
    for m in metrics_of(spec, cell["name"], bool(args.trace)):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    cores = [j.core for j in [warm] + window]
    del warm, window, run, g
    gc.collect()
    t = time.perf_counter()
    ref = reference.coreness(csr.indptr, csr.indices)
    wrong = [wrong_nodes(c, ref) for c in cores]
    log(f"reference: {time.perf_counter() - t:.3f}s, k_max={int(ref.max())}, "
        f"wrong nodes per job (warm-up first) {wrong}")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    checks = {"wrong_nodes": {"value": max(wrong), "limit": 0}}
    result = {
        "correct": max(wrong) == 0,
        "attempted": len(cores) - 1,
        "failed": sum(1 for w in wrong[1:] if w),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
