"""Child process of ``test_bench_part_parallel.py``: whole runs of a
part-parallel mix on four virtual CPU devices, in one interpreter so that
JAX starts once.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python bench/tests/part_parallel_child.py <checkout> <cell> <one-chip cell>

``<checkout>`` holds ``BENCHMARK.json`` and ``bench/`` with the two cells
added as data files only. The last line of stdout is one JSON object, keyed
by scenario: the CLI's and the job's run keywords, each run's result line
(or exit code and stdout where it gives none), the slices the warm-up job
used, and the control's summary.
"""
from __future__ import annotations

import contextlib
import io
import json
import sys

ROOT, CELL, ONE_CHIP = sys.argv[1:4]
sys.path.insert(0, ROOT)

TINY = {"scale": 10}
CLI_BUDGET = 2**20  # bytes: --budget-gb 2**-10


class _Stop(Exception):
    pass


def cli_kwargs():
    """What ``python -m repro.launch.kcore --part-parallel 2 --devices 4``
    passes to ``run_with_capacity_replan``. It must run before anything
    starts JAX's backend: the CLI sets the CPU device count itself."""
    from repro.launch import kcore

    seen = {}

    def capture(g, thresholds, **kw):
        seen.update(kw)
        raise _Stop

    argv, sys.argv = sys.argv, ["kcore", "--graph", "rmat:8:4",
                                "--budget-gb", str(CLI_BUDGET / 2**30),
                                "--part-parallel", "2", "--devices", "4"]
    real, kcore.run_with_capacity_replan = \
        kcore.run_with_capacity_replan, capture
    try:
        with contextlib.redirect_stdout(io.StringIO()):
            kcore.main()
    except _Stop:
        pass
    finally:
        sys.argv = argv
        kcore.run_with_capacity_replan = real
    return seen


def describe(kw):
    """Run keywords as JSON: the mesh plan by its layout and devices."""
    out = {k: v for k, v in kw.items()
           if k not in ("part_parallel_plan", "replan_budget_bytes")}
    plan = kw.get("part_parallel_plan")
    if plan is not None:
        from repro.core.partsched import slice_mesh_plans

        out["part_parallel_plan"] = {
            "shape": dict(plan.mesh.shape),
            "devices": [d.id for d in plan.mesh.devices.flat],
            "node_axes": list(plan.node_axes),
            "slot_axes": list(plan.slot_axes),
            "slices": [[d.id for d in p.mesh.devices.flat]
                       for p in slice_mesh_plans(plan, kw["part_parallel"])],
        }
    return out


@contextlib.contextmanager
def patched(module, name, value):
    real = getattr(module, name)
    setattr(module, name, value)
    try:
        yield real
    finally:
        setattr(module, name, real)


# Faults of the distributed engine's timed path, each a context manager.
def state_unchanged():
    import jax.numpy as jnp
    from repro.core import distributed

    def make_sweep_fn(*_a, **_kw):
        def build(n_buckets):
            def sweep(c, ext_pad, active, node_tile, buckets):
                return (c, jnp.zeros((n_buckets,), jnp.int32),
                        jnp.zeros((n_buckets,), bool))
            return sweep
        return build

    return patched(distributed, "make_sweep_fn", make_sweep_fn)


def half_left_out():
    import jax.numpy as jnp
    from repro.core import distributed

    real = distributed.make_sweep_fn

    def make_sweep_fn(*a, **kw):
        inner = real(*a, **kw)

        def build(n_buckets):
            sweep = inner(n_buckets)
            keep = jnp.arange(n_buckets) < n_buckets // 2
            return lambda c, e, active, t, b: sweep(c, e, active & keep, t, b)
        return build

    return patched(distributed, "make_sweep_fn", make_sweep_fn)


def exchange_left_out():
    import numpy as np
    from repro.core import distributed

    real = distributed.device_external_info

    def device_external_info(*a, **kw):
        delta, moved = real(*a, **kw)
        return np.zeros_like(delta), moved

    return patched(distributed, "device_external_info", device_external_info)


def answer_altered():
    from repro.core import distributed

    real = distributed.decompose_distributed

    def decompose_distributed(bg, plan, **kw):
        res = real(bg, plan, **kw)
        res.coreness = res.coreness.copy()
        res.coreness[-1] += 1
        return res

    return patched(distributed, "decompose_distributed", decompose_distributed)


FAULTS = (state_unchanged, half_left_out, exchange_left_out, answer_altered)


def bench_run(run, argv):
    """``(exit code, last stdout line as JSON or None, stdout)``."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(argv, require_accelerator=False, overrides=TINY)
    text = buf.getvalue()
    lines = text.strip().splitlines()
    try:
        last = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        last = None
    return rc, last, text


def args(cell, trace=0, seed=2**31 + 3):
    return ["--workload", cell, "--seed", str(seed), "--seconds", "0.2",
            "--trace", str(trace)]


def main():
    out = {"cli": describe(cli_kwargs())}
    import jax

    from bench import control, job as jobs_mod, run

    out["n_devices"] = len(jax.devices())
    out["job"] = describe(jobs_mod.dc_kwargs(
        {"part_parallel": 2, "devices": 4}, CLI_BUDGET))

    warm_slices = []
    real_run_job = jobs_mod.run_job

    def run_job(*a, **kw):
        job = real_run_job(*a, **kw)
        warm_slices.append(sorted({p.slice_index for p in job.report.parts}))
        return job

    with patched(jobs_mod, "run_job", run_job):
        for trace in (0, 1):
            rc, line, _ = bench_run(run, args(CELL, trace))
            out[f"sound_t{trace}"] = {"rc": rc, "line": line,
                                      "slices": warm_slices[0]}
            warm_slices.clear()
    for fault in FAULTS:
        with fault():
            rc, line, _ = bench_run(run, args(CELL))
        out[fault.__name__] = {"rc": rc, "line": line}
    rc, line, text = bench_run(run, args(ONE_CHIP))
    out["one_chip"] = {"rc": rc, "stdout": text}
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = control.main(["--workload", CELL, "--seeds", "4,5",
                           "--control-seeds", "4,5"],
                          require_accelerator=False, overrides=TINY)
    out["control"] = {"rc": rc, "summary": json.loads(
        buf.getvalue().strip().splitlines()[-1])}
    print(json.dumps(out, default=str), flush=True)


if __name__ == "__main__":
    main()
