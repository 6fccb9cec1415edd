"""One job of a cell: what ``python -m repro.launch.kcore --graph <g>
--budget-gb <b>`` does once the graph is in host memory.

It plans the thresholds with ``plan_thresholds(g.degrees, budget_bytes)``
(none for a whole-graph mix), then calls the CLI's own
``run_with_capacity_replan`` with the CLI's defaults, overridden only by the
keys the traffic mix names. The job ends with the coreness array on the
host. Each stage sits in a ``bench.*`` span, so that a trace can name what
the host was doing while the device idled.

A mix that names ``part_parallel`` conquers that many parts at once, as
``--part-parallel S`` does; one that also names ``devices`` runs them on
the shard_map engine over a mesh of the first ``devices`` devices, as
``--devices N`` does (``dc_kwargs``).
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

# ``python -m repro.launch.kcore``'s defaults for the arguments it passes to
# ``run_with_capacity_replan``. A traffic mix may override those named in
# TRAFFIC_KEYS.
CLI_DEFAULTS = dict(
    strategy="rough",
    reorder="identity",
    reorder_sample_edges=None,
    max_bucket_rows="auto",
    checkpoint_dir=None,
    resume=False,
    divide_chunk=None,
    sweep_checkpoint_every=None,
    overlap=False,
    engine="sorted",
    int16=False,
    part_parallel=None,
    part_parallel_plan=None,
    slice_capacity_bytes=None,
    slice_timeout_s=None,
    max_retries=None,
    fault_plan=None,
    ckpt_retain=2,
)
TRAFFIC_KEYS = ("budget_fraction", "strategy", "reorder", "engine",
                "max_bucket_rows", "part_parallel", "devices", "why")


@dataclasses.dataclass
class Job:
    core: np.ndarray
    report: object        # repro.core.dckcore.DCKCoreReport
    thresholds: list
    plan_s: float         # plan_thresholds, host clock
    wall_s: float         # the whole job, host clock
    replans: int          # capacity re-divides run_with_capacity_replan made


def budget_bytes(traffic: dict, degrees: np.ndarray):
    """The mix's per-part budget: a fraction of the planner's whole-graph
    estimate (8 bytes per adjacency slot), or None for one part."""
    frac = traffic.get("budget_fraction")
    if frac is None:
        return None
    return int(float(frac) * int(degrees.sum()) * 8)


def devices_of(traffic: dict) -> int:
    """Devices the mix's jobs run on: its ``devices``, else one."""
    return int(traffic.get("devices") or 1)


def dc_kwargs(traffic: dict, budget=None) -> dict:
    """The job's ``run_with_capacity_replan`` keywords: the CLI's defaults,
    the mix's keys, and for a part-parallel mix the CLI's slice capacity
    (the per-part ``budget``) and, where it names ``devices``, its mesh
    plan. A plan needs JAX's devices, so call this once JAX is up."""
    unknown = set(traffic) - set(TRAFFIC_KEYS)
    if unknown:
        raise ValueError(f"unknown traffic keys {sorted(unknown)}")
    kw = dict(CLI_DEFAULTS)
    kw.update({k: traffic[k] for k in TRAFFIC_KEYS
               if k in traffic and k in CLI_DEFAULTS})
    if kw["part_parallel"] is not None:
        kw["slice_capacity_bytes"] = budget
    if traffic.get("devices") is not None:
        from repro.launch.mesh import make_mesh_plan_for_devices

        if kw["part_parallel"] is None:
            raise ValueError("a mix that names devices names part_parallel")
        # As the CLI does: shard neighbor slots over a "model" axis of 2
        # where the "data" axis still splits into the slices, else stay flat.
        n, slices = int(traffic["devices"]), int(kw["part_parallel"])
        kw["part_parallel_plan"] = make_mesh_plan_for_devices(
            n, model_parallel=2 if n % (2 * slices) == 0 else 1)
    return kw


def run_job(jax, g, budget, kwargs: dict, dc=None) -> Job:
    """One job on the program graph ``g``. ``dc`` replaces ``dc_kcore``
    (the control does so); the default is the program's own."""
    from repro.core.divide import plan_thresholds
    from repro.launch.kcore import run_with_capacity_replan

    extra = {} if dc is None else {"dc": dc}
    with jax.profiler.TraceAnnotation("bench.job"):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.plan_thresholds"):
            thresholds = (plan_thresholds(g.degrees, budget)
                          if budget is not None else [])
        plan_s = time.perf_counter() - t0
        with jax.profiler.TraceAnnotation("bench.dc_kcore"):
            core, report, thresholds, replans = run_with_capacity_replan(
                g, thresholds, replan_budget_bytes=budget, **extra, **kwargs)
        wall_s = time.perf_counter() - t0
    return Job(core=np.asarray(core), report=report,
               thresholds=list(thresholds), plan_s=plan_s, wall_s=wall_s,
               replans=replans)
