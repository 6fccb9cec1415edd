"""Fault-tolerant elastic conquer: chaos differential suite.

Four layers, mirroring the recovery machinery:

* **FaultPlan** (pure, in-process): spec parsing, visit windows, the
  bounded-hang contract (a parked thread always terminates).
* **conquer_wave watchdog** (deterministic, controlled ``run_part``):
  fail-fast semantics preserved, retry with backoff, crash-exhaustion
  blacklist + re-plan over survivors, hang detection, all-slices-dead.
* **dc_kcore chaos differential**: faults injected at every
  ``slice_conquer`` visit — the part-parallel run completes (possibly
  degraded to fewer slices), byte-identical to the fault-free sequential
  baseline, with every retry/blacklist accounted in the report.
* **Checkpoint integrity**: per-leaf CRC32, typed corruption errors,
  quarantine (``step_N.corrupt``) + fallback to the previous retained
  step, and the dc_kcore resume path over a corrupted latest step.

The elastic 8->4 remesh check (formerly tests/test_elastic.py) folds in
here: degraded restore onto a smaller mesh is the same elasticity story,
now exercised through ``restore_pytree_with_fallback``.
"""
import json
import os
import threading
import time

import numpy as np
import pytest

from distributed_helpers import run_with_devices

from repro.ckpt import (
    DEFAULT_RETAIN,
    CheckpointCorruptError,
    CheckpointManager,
    latest_step,
    quarantine_step,
    restore_pytree,
    restore_pytree_with_fallback,
    save_pytree,
)
from repro.core.dckcore import dc_kcore
from repro.core.partsched import (
    PartCost,
    SliceCapacityError,
    SliceSpec,
    WatchdogConfig,
    WaveTelemetry,
    assign_parts,
    conquer_wave,
)
from repro.graph.generators import rmat
from repro.runtime import FAULT_SITES, FaultPlan, FaultSpec, InjectedFailure


# --------------------------------------------------------------------- #
# FaultPlan: specs, visit windows, bounded hangs.
# --------------------------------------------------------------------- #
def test_fault_spec_parse_forms():
    s = FaultSpec.parse("slice_conquer:crash")
    assert (s.site, s.kind, s.at, s.count) == ("slice_conquer", "crash", 0, 1)
    s = FaultSpec.parse("checkpoint_save:hang:3:2:0.5")
    assert (s.kind, s.at, s.count, s.delay_s) == ("hang", 3, 2, 0.5)
    assert FaultSpec.parse("prefetch:slow:1").at == 1


@pytest.mark.parametrize("bad", [
    "slice_conquer",                    # no kind
    "nope:crash",                       # unknown site
    "slice_conquer:explode",            # unknown kind
    "slice_conquer:crash:0:1:2:3",      # too many fields
])
def test_fault_spec_parse_rejects(bad):
    with pytest.raises(ValueError):
        FaultSpec.parse(bad)


def test_fault_plan_visit_window_and_events():
    plan = FaultPlan([FaultSpec("prefetch", "crash", at=1, count=2)])
    plan.visit("prefetch", cursor=0)  # visit 0: before the window
    for k in (1, 2):
        with pytest.raises(InjectedFailure):
            plan.visit("prefetch", cursor=k)
    plan.visit("prefetch", cursor=3)  # visit 3: past the window
    assert plan.visits("prefetch") == 4
    assert [e["visit"] for e in plan.events] == [1, 2]
    assert all(e["event"] == "inject" and e["kind"] == "crash"
               for e in plan.events)


def test_fault_plan_unknown_site_never_fires():
    plan = FaultPlan([FaultSpec("slice_conquer", "crash")])
    plan.visit("boundary_fold")  # armed elsewhere: plain pass-through
    assert plan.events == []


def test_fault_plan_hang_is_bounded_and_releasable():
    plan = FaultPlan([FaultSpec("serve_update", "hang", delay_s=30.0)])
    t0 = time.perf_counter()
    release = threading.Timer(0.05, plan.release)
    release.start()
    try:
        with pytest.raises(InjectedFailure):
            plan.visit("serve_update")
    finally:
        release.cancel()
    assert time.perf_counter() - t0 < 5.0  # woke on release, not delay_s
    # A tiny delay bounds the park even without a release.
    plan2 = FaultPlan([FaultSpec("serve_update", "hang", delay_s=0.01)])
    with pytest.raises(InjectedFailure):
        plan2.visit("serve_update")


# --------------------------------------------------------------------- #
# conquer_wave watchdog: deterministic controlled-run_part harness.
# --------------------------------------------------------------------- #
def _schedule(n_parts, n_slices):
    costs = [PartCost(cursor=c, collective_bytes=100, hbm_bytes=0,
                      part_bytes=1) for c in range(n_parts)]
    slices = [SliceSpec(index=s, n_node_shards=1, n_slot_shards=1)
              for s in range(n_slices)]
    return assign_parts(costs, slices), slices


def test_conquer_wave_fail_fast_raises_earliest_cursor():
    schedule, slices = _schedule(4, 2)

    def run_part(cursor, s):
        if cursor in (1, 2):
            raise RuntimeError(f"boom {cursor}")
        return cursor * 10

    with pytest.raises(RuntimeError, match="boom 1"):
        conquer_wave(schedule, run_part, slices=slices)


def test_conquer_wave_retry_commits_identical_result():
    schedule, slices = _schedule(4, 2)
    fails = {1: 2}  # cursor 1 fails twice, then succeeds
    tel = WaveTelemetry()

    def run_part(cursor, s):
        if fails.get(cursor, 0) > 0:
            fails[cursor] -= 1
            raise RuntimeError("transient")
        return cursor * 10

    results = conquer_wave(
        schedule, run_part, slices=slices,
        watchdog=WatchdogConfig(max_retries=2, backoff_s=0.001),
        telemetry=tel,
    )
    assert results == {c: c * 10 for c in range(4)}
    assert tel.retries == 2 and tel.blacklisted == [] and tel.replans == 0


def test_conquer_wave_exhausted_retries_blacklist_and_replan():
    schedule, slices = _schedule(6, 2)
    victim = schedule.parts_for(0)[0]
    tel = WaveTelemetry()

    def run_part(cursor, s):
        if cursor == victim and s == 0:
            raise RuntimeError("slice 0 is broken")
        return cursor * 10

    results = conquer_wave(
        schedule, run_part, slices=slices,
        watchdog=WatchdogConfig(max_retries=1, backoff_s=0.001),
        telemetry=tel,
    )
    # Every part completed — the victim re-planned onto the survivor.
    assert results == {c: c * 10 for c in range(6)}
    assert tel.blacklisted == [0] and tel.replans == 1 and tel.degraded
    kinds = [e["event"] for e in tel.events]
    assert kinds.count("retry") == 1 and "blacklist" in kinds \
        and "replan" in kinds


def test_conquer_wave_hang_is_declared_dead_and_replanned():
    schedule, slices = _schedule(4, 2)
    victim = schedule.parts_for(1)[0]
    unhang = threading.Event()
    tel = WaveTelemetry()

    def run_part(cursor, s, heartbeat=None):
        if cursor == victim and s == 1:
            unhang.wait(timeout=10)
            raise RuntimeError("woke from hang")
        heartbeat()
        return cursor * 10

    try:
        results = conquer_wave(
            schedule, run_part, slices=slices,
            watchdog=WatchdogConfig(slice_timeout_s=0.2, poll_s=0.02,
                                    max_retries=0, drain_timeout_s=5.0),
            telemetry=tel,
        )
    finally:
        unhang.set()
    assert results == {c: c * 10 for c in range(4)}
    assert tel.blacklisted == [1]
    assert any(e["event"] == "blacklist" and e["reason"] == "hang"
               for e in tel.events)


def test_conquer_wave_all_slices_dead_raises():
    schedule, slices = _schedule(3, 2)

    def run_part(cursor, s):
        raise RuntimeError("every slice is broken")

    with pytest.raises(RuntimeError, match="every slice is broken"):
        conquer_wave(
            schedule, run_part, slices=slices,
            watchdog=WatchdogConfig(max_retries=0, backoff_s=0.001),
        )


def test_conquer_wave_replan_capacity_exhaustion_raises():
    # The survivor cannot admit the victim's part: re-plan fails and the
    # wave raises the declare-dead error instead of spinning.
    costs = [PartCost(cursor=0, collective_bytes=100, hbm_bytes=0,
                      part_bytes=100)]
    slices = [SliceSpec(index=0, n_node_shards=1, n_slot_shards=1,
                        capacity_bytes=200),
              SliceSpec(index=1, n_node_shards=1, n_slot_shards=1,
                        capacity_bytes=10)]
    schedule = assign_parts(costs, slices)

    def run_part(cursor, s):
        raise RuntimeError("slice 0 is broken")

    with pytest.raises(SliceCapacityError):
        conquer_wave(
            schedule, run_part, slices=slices,
            watchdog=WatchdogConfig(max_retries=0, backoff_s=0.001),
        )


# --------------------------------------------------------------------- #
# dc_kcore chaos differential: byte-identity under injected faults.
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def chaos_graph():
    g = rmat(10, 8, seed=11)
    base, _ = dc_kcore(g, thresholds=(4, 10))
    return g, base


def test_dckcore_crash_at_every_conquer_visit(chaos_graph):
    """A single injected crash at the k-th slice_conquer visit, for every
    k the fault-free run performs: the run completes byte-identical with
    exactly that one retry accounted."""
    g, base = chaos_graph
    probe = FaultPlan()  # counts visits without arming anything
    core, _ = dc_kcore(g, thresholds=(4, 10), part_parallel=2,
                       max_retries=2, fault_plan=probe)
    np.testing.assert_array_equal(core, base)
    n_visits = probe.visits("slice_conquer")
    assert n_visits >= 3  # one per part at minimum
    for k in range(n_visits):
        plan = FaultPlan([FaultSpec("slice_conquer", "crash", at=k)])
        core, report = dc_kcore(g, thresholds=(4, 10), part_parallel=2,
                                max_retries=2, fault_plan=plan)
        np.testing.assert_array_equal(core, base)
        fired = len(plan.events)
        assert fired == 1, (k, plan.events)
        assert report.retries == 1
        retry_events = [e for e in report.fault_events
                        if e["event"] == "retry"]
        assert len(retry_events) == 1
        # Per-part attribution: at most the one retry (a retried attempt
        # later discarded by a speculation miss re-runs clean next wave).
        assert sum(p.retries for p in report.parts) <= 1


def test_dckcore_hang_blacklists_and_degrades(chaos_graph):
    """An injected hang trips the watchdog: the slice is blacklisted, its
    parts re-plan onto the survivor (2 -> 1 ≡ sequential), and the run
    completes byte-identical, reported as degraded."""
    g, base = chaos_graph
    # The timeout must be << the hang delay but leave a legitimate sweep
    # (or a cold compile, which also stalls the heartbeat) well clear.
    plan = FaultPlan([FaultSpec("slice_conquer", "hang", at=0, delay_s=60.0)])
    core, report = dc_kcore(g, thresholds=(4, 10), part_parallel=2,
                            slice_timeout_s=2.0, max_retries=0,
                            fault_plan=plan)
    np.testing.assert_array_equal(core, base)
    assert len(report.blacklisted_slices) == 1
    assert report.degraded_waves >= 1
    assert any(e["event"] == "blacklist" and e["reason"] == "hang"
               for e in report.fault_events)
    # The blacklist sticks for the rest of the run: every later wave is
    # effectively sequential, and no conquer worker outlives the run
    # (the autouse thread-leak gate enforces the second half).


def test_dckcore_mainthread_sites_fail_fast(chaos_graph, tmp_path):
    """boundary_fold / checkpoint_save faults are main-thread: they kill
    the run (recovery = checkpointed resume, not in-run retry) — even
    with the watchdog armed."""
    g, _ = chaos_graph
    plan = FaultPlan([FaultSpec("boundary_fold", "crash")])
    with pytest.raises(InjectedFailure):
        dc_kcore(g, thresholds=(4, 10), part_parallel=2, max_retries=2,
                 fault_plan=plan)
    plan = FaultPlan([FaultSpec("checkpoint_save", "crash")])
    with pytest.raises(InjectedFailure):
        dc_kcore(g, thresholds=(4, 10), part_parallel=2, max_retries=2,
                 checkpoint_dir=str(tmp_path / "ck"), fault_plan=plan)


def test_dckcore_crash_then_resume_after_degraded_run(chaos_graph, tmp_path):
    """Degrade the run (a slice crash past its retry budget blacklists
    it), then kill it at a boundary checkpoint save; resume with no
    faults is byte-identical to sequential, with the saved parts
    restored — degraded-mode checkpoints carry no mode dependence."""
    g, base = chaos_graph
    ck = str(tmp_path / "ck")
    plan = FaultPlan([FaultSpec("slice_conquer", "crash", at=0),
                      FaultSpec("checkpoint_save", "crash", at=1)])
    with pytest.raises(InjectedFailure):
        dc_kcore(g, thresholds=(4, 10), part_parallel=2, checkpoint_dir=ck,
                 max_retries=0, fault_plan=plan)
    # Both faults fired: the conquer crash (-> blacklist at retries=0)
    # and the boundary-save kill.
    assert sorted(e["site"] for e in plan.events) == \
        ["checkpoint_save", "slice_conquer"]
    core, report = dc_kcore(g, thresholds=(4, 10), part_parallel=2,
                            checkpoint_dir=ck, resume=True)
    np.testing.assert_array_equal(core, base)
    assert report.resumed_parts >= 1


def test_dckcore_watchdog_requires_part_parallel(chaos_graph):
    g, _ = chaos_graph
    with pytest.raises(ValueError, match="part_parallel"):
        dc_kcore(g, thresholds=(4,), slice_timeout_s=1.0)
    with pytest.raises(ValueError, match="ckpt_retain"):
        dc_kcore(g, thresholds=(4,), ckpt_retain=0)


# --------------------------------------------------------------------- #
# Checkpoint integrity: CRC, quarantine, fallback.
# --------------------------------------------------------------------- #
def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"w": rng.standard_normal((8, 4)).astype(np.float32),
            "steps": np.arange(5, dtype=np.int32)}


def _corrupt_leaf(ckdir, step):
    sd = os.path.join(ckdir, f"step_{step:08d}")
    leaf = next(f for f in sorted(os.listdir(sd)) if f.endswith(".npy"))
    p = os.path.join(sd, leaf)
    raw = bytearray(open(p, "rb").read())
    raw[-4] ^= 0xFF  # flip data bits past the npy header
    open(p, "wb").write(bytes(raw))


def test_crc_roundtrip_and_corruption_detected(tmp_path):
    d = str(tmp_path)
    save_pytree(d, _tree(), step=1)
    tree, step, _ = restore_pytree(d, _tree())  # intact: CRC passes
    assert step == 1
    _corrupt_leaf(d, 1)
    with pytest.raises(CheckpointCorruptError, match="CRC mismatch"):
        restore_pytree(d, _tree())


def test_corrupt_manifest_is_typed(tmp_path):
    d = str(tmp_path)
    save_pytree(d, _tree(), step=1)
    mf = os.path.join(d, "step_00000001", "manifest.json")
    open(mf, "w").write("{not json")
    with pytest.raises(CheckpointCorruptError):
        restore_pytree(d, _tree())


def test_pre_crc_manifest_still_loads(tmp_path):
    d = str(tmp_path)
    save_pytree(d, _tree(), step=1)
    mf = os.path.join(d, "step_00000001", "manifest.json")
    manifest = json.load(open(mf))
    del manifest["crc32"]  # a checkpoint written before CRC stamping
    json.dump(manifest, open(mf, "w"))
    _, step, _ = restore_pytree(d, _tree())
    assert step == 1


def test_fallback_quarantines_and_restores_previous(tmp_path):
    d = str(tmp_path)
    save_pytree(d, _tree(seed=1), step=1)
    save_pytree(d, _tree(seed=2), step=2)
    _corrupt_leaf(d, 2)
    seen = []
    tree, step, _ = restore_pytree_with_fallback(
        d, _tree(), on_corrupt=lambda s, e: seen.append(s))
    assert step == 1 and seen == [2]
    np.testing.assert_array_equal(tree["w"], _tree(seed=1)["w"])
    # Step 2 is quarantined for postmortem and invisible to latest_step.
    assert os.path.isdir(os.path.join(d, "step_00000002.corrupt"))
    assert latest_step(d) == 1


def test_fallback_raises_when_nothing_intact(tmp_path):
    d = str(tmp_path)
    save_pytree(d, _tree(), step=1)
    _corrupt_leaf(d, 1)
    with pytest.raises(FileNotFoundError, match="no intact"):
        restore_pytree_with_fallback(d, _tree())
    assert latest_step(d) is None


def test_quarantine_step_replaces_stale_quarantine(tmp_path):
    d = str(tmp_path)
    save_pytree(d, _tree(seed=1), step=1)
    q = quarantine_step(d, 1)
    assert q.endswith(".corrupt") and os.path.isdir(q)
    save_pytree(d, _tree(seed=2), step=1)
    quarantine_step(d, 1)  # second quarantine of the same step: replaced
    assert latest_step(d) is None


def test_dckcore_resume_falls_back_over_corrupt_boundary(tmp_path):
    """Corrupt the latest boundary checkpoint: resume quarantines it and
    restarts from the previous retained step — byte-identical."""
    g = rmat(10, 8, seed=11)
    base, _ = dc_kcore(g, thresholds=(4, 10))
    ck = str(tmp_path / "ck")
    dc_kcore(g, thresholds=(4, 10), checkpoint_dir=ck)
    steps = sorted(d for d in os.listdir(ck) if d.startswith("step_"))
    assert len(steps) == 2  # retain=2 default
    _corrupt_leaf(ck, int(steps[-1].split("_")[1]))
    core, report = dc_kcore(g, thresholds=(4, 10), checkpoint_dir=ck,
                            resume=True)
    np.testing.assert_array_equal(core, base)
    assert report.quarantined_steps == 1
    assert any(e["event"] == "quarantine" for e in report.fault_events)
    assert any(d.endswith(".corrupt") for d in os.listdir(ck))


def test_dckcore_resume_every_step_corrupt_restarts_fresh(tmp_path):
    g = rmat(10, 8, seed=11)
    base, _ = dc_kcore(g, thresholds=(4, 10))
    ck = str(tmp_path / "ck")
    dc_kcore(g, thresholds=(4, 10), checkpoint_dir=ck)
    for d in list(os.listdir(ck)):
        if d.startswith("step_"):
            _corrupt_leaf(ck, int(d.split("_")[1]))
    core, report = dc_kcore(g, thresholds=(4, 10), checkpoint_dir=ck,
                            resume=True)
    np.testing.assert_array_equal(core, base)
    assert report.quarantined_steps == 2
    assert report.resumed_parts == 0  # nothing intact: fresh run


# --------------------------------------------------------------------- #
# CheckpointManager: retention knob + async error surfacing.
# --------------------------------------------------------------------- #
def test_manager_retain_default_and_keep_alias(tmp_path):
    m = CheckpointManager(str(tmp_path))
    assert m.retain == DEFAULT_RETAIN == 2
    m2 = CheckpointManager(str(tmp_path), keep=5)
    assert m2.retain == 5 and m2.keep == 5
    m3 = CheckpointManager(str(tmp_path), retain=1)
    assert m3.keep == 1


def test_manager_async_error_surfaces_on_next_save(tmp_path, monkeypatch):
    import repro.ckpt.checkpoint as ckmod

    m = CheckpointManager(str(tmp_path), retain=2)
    real = ckmod.save_pytree

    def boom(*a, **k):
        raise OSError("disk on fire")

    monkeypatch.setattr(ckmod, "save_pytree", boom)
    m.save(_tree(), step=1, blocking=False)
    m._pending.join()  # let the worker fail without draining the error
    monkeypatch.setattr(ckmod, "save_pytree", real)
    with pytest.raises(OSError, match="disk on fire"):
        m.save(_tree(), step=2, blocking=False)
    m.wait()


def test_manager_on_done_error_surfaces_on_clear_steps(tmp_path):
    m = CheckpointManager(str(tmp_path), retain=2)

    def bad_hook(step, secs):
        raise ValueError("hook exploded")

    m.save(_tree(), step=1, blocking=False, on_done=bad_hook)
    m._pending.join()
    with pytest.raises(ValueError, match="hook exploded"):
        m.clear_steps()
    m.wait()


def test_clear_steps_purges_quarantined_and_tmp(tmp_path):
    d = str(tmp_path)
    m = CheckpointManager(d, retain=3)
    m.save(_tree(), step=1, blocking=True)
    m.save(_tree(), step=2, blocking=True)
    quarantine_step(d, 2)
    os.makedirs(os.path.join(d, "step_00000009.tmp"))
    m.clear_steps()
    left = [x for x in os.listdir(d) if x.startswith("step_")]
    assert left == []


# --------------------------------------------------------------------- #
# Capacity re-plan exhaustion (launcher-level retry loop).
# --------------------------------------------------------------------- #
def test_capacity_replan_exhaustion_reraises(tmp_path):
    from repro.launch.kcore import run_with_capacity_replan

    g = rmat(8, 4, seed=3)
    ck = str(tmp_path / "ck")
    calls = []
    exc = SliceCapacityError("part 0 fits no slice")

    def dc_stub(graph, thresholds, **kw):
        calls.append((tuple(thresholds), kw.get("resume")))
        raise exc

    with pytest.raises(SliceCapacityError) as ei:
        run_with_capacity_replan(
            g, [4], replan_budget_bytes=1 << 20, max_replans=3,
            dc=dc_stub, checkpoint_dir=ck, resume=True)
    assert ei.value is exc                  # the original error, not a wrap
    assert len(calls) == 1 + 3              # first try + max_replans
    assert calls[0][1] is True              # resume honored on the first try
    assert all(r is False for _, r in calls[1:])  # forced off on retries
    assert not os.path.exists(ck)           # no checkpoint litter


# --------------------------------------------------------------------- #
# Elastic remesh (folded in from tests/test_elastic.py): a checkpoint
# saved on an 8-device mesh restores re-sharded onto 4, through the
# integrity-checking fallback path.
# --------------------------------------------------------------------- #
_ELASTIC_SAVE = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.ckpt import save_pytree
from jax.sharding import AxisType
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
w = jax.device_put(jnp.arange(64*32, dtype=jnp.float32).reshape(64, 32),
                   NamedSharding(mesh, P("data", "model")))
b = jax.device_put(jnp.ones((32,), jnp.float32), NamedSharding(mesh, P("model")))
save_pytree("%DIR%", {"w": w, "b": b}, step=3, extra={"mesh": "4x2"})
print("SAVED")
"""

_ELASTIC_RESTORE = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.ckpt import restore_pytree_with_fallback
from jax.sharding import AxisType
assert len(jax.devices()) == 4
mesh = jax.make_mesh((2, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
template = {"w": np.zeros((64, 32), np.float32), "b": np.zeros((32,), np.float32)}
shardings = {"w": NamedSharding(mesh, P("data", "model")),
             "b": NamedSharding(mesh, P("model"))}
tree, step, extra = restore_pytree_with_fallback("%DIR%", template,
                                                 shardings=shardings)
assert step == 3 and extra["mesh"] == "4x2"
np.testing.assert_array_equal(np.asarray(tree["w"]),
                              np.arange(64*32, dtype=np.float32).reshape(64, 32))
assert tree["w"].sharding.mesh.shape["data"] == 2  # re-sharded onto new mesh
print("RESTORED")
"""


def test_elastic_remesh_8_to_4(tmp_path):
    d = str(tmp_path / "ck")
    out = run_with_devices(_ELASTIC_SAVE.replace("%DIR%", d), n_devices=8)
    assert "SAVED" in out
    out = run_with_devices(_ELASTIC_RESTORE.replace("%DIR%", d), n_devices=4)
    assert "RESTORED" in out
