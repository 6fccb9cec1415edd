"""One job of a cell: what ``python -m repro.launch.kcore --graph <g>
--budget-gb <b>`` does once the graph is in host memory.

It plans the thresholds with ``plan_thresholds(g.degrees, budget_bytes)``
(none for a whole-graph mix), then calls the CLI's own
``run_with_capacity_replan`` with the CLI's defaults, overridden only by the
keys the traffic mix names. The job ends with the coreness array on the
host. Each stage sits in a ``bench.*`` span, so that a trace can name what
the host was doing while the device idled.
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
                "max_bucket_rows", "why")


@dataclasses.dataclass
class Job:
    core: np.ndarray
    report: object        # repro.core.dckcore.DCKCoreReport
    thresholds: list
    plan_s: float         # plan_thresholds, host clock
    wall_s: float         # the whole job, host clock


def budget_bytes(traffic: dict, degrees: np.ndarray):
    """The mix's per-part budget: a fraction of the planner's whole-graph
    estimate (8 bytes per adjacency slot), or None for one part."""
    frac = traffic.get("budget_fraction")
    if frac is None:
        return None
    return int(float(frac) * int(degrees.sum()) * 8)


def dc_kwargs(traffic: dict) -> dict:
    unknown = set(traffic) - set(TRAFFIC_KEYS)
    if unknown:
        raise ValueError(f"unknown traffic keys {sorted(unknown)}")
    kw = dict(CLI_DEFAULTS)
    kw.update({k: traffic[k] for k in TRAFFIC_KEYS
               if k in traffic and k in CLI_DEFAULTS})
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
            core, report, thresholds, _replans = run_with_capacity_replan(
                g, thresholds, replan_budget_bytes=budget, **extra, **kwargs)
        wall_s = time.perf_counter() - t0
    return Job(core=np.asarray(core), report=report,
               thresholds=list(thresholds), plan_s=plan_s, wall_s=wall_s)
