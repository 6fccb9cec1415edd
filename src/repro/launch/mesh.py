"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any device query). Every mesh
is built with ``AxisType.Auto`` axes, the behaviour the sharding policy
assumes (``jax.make_mesh`` defaults to ``Explicit``).

Single pod: 16x16 = 256 v5e chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis carries only data parallelism (gradient all-reduce), keeping
cross-pod (DCN-class) traffic minimal.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh_for_devices(n: int, model_parallel: int = 1, axis_names=("data", "model")):
    """An ``(n / model_parallel) x model_parallel`` mesh over the first
    ``n`` of ``jax.devices()``: the real chips on an accelerator, virtual
    host devices under :func:`force_host_device_count`. Raises when the
    backend has fewer than ``n`` devices."""
    if n % model_parallel != 0:
        raise ValueError(f"{n} devices do not split into model_parallel="
                         f"{model_parallel}")
    devices = jax.devices()
    if len(devices) < n:
        raise ValueError(
            f"asked for a {n}-device mesh, but the {devices[0].platform} "
            f"backend has {len(devices)} device(s)"
        )
    return jax.make_mesh(
        (n // model_parallel, model_parallel), axis_names,
        axis_types=(AxisType.Auto,) * len(axis_names), devices=devices[:n],
    )


def make_mesh_plan_for_devices(n: int, model_parallel: int = 1):
    """A :class:`~repro.core.distributed.MeshPlan` over ``n`` local devices:
    rows sharded over ``"data"``, neighbor slots over ``"model"`` — the
    layout the part-parallel scheduler slices along its first node axis."""
    from repro.core.distributed import MeshPlan

    return MeshPlan(
        mesh=make_mesh_for_devices(n, model_parallel),
        node_axes=("data",),
        slot_axes=("model",),
    )


def _backends_initialized() -> bool:
    """Has jax already instantiated a backend (device queries ran)?

    Reaches into ``jax._src.xla_bridge`` (no public probe exists); defaults
    to ``False`` if the internal layout shifts — the worst case is a clear
    late-flag failure instead of an early one.
    """
    try:
        from jax._src import xla_bridge

        return bool(xla_bridge._backends)
    except (ImportError, AttributeError):
        return False


def force_host_device_count(n: int) -> None:
    """Make the CPU host expose ``n`` virtual devices (the test/emulation
    backend for multi-device runs) by rewriting ``XLA_FLAGS``.

    Only CPU runs use it: on an accelerator the mesh is built from the
    real devices. Must run BEFORE jax instantiates a backend — the flag is
    read once at backend init, so a late call would silently do nothing;
    this raises instead. Any previous
    ``--xla_force_host_platform_device_count`` token is dropped so repeated
    calls don't accumulate contradictory flags.
    """
    import os

    if _backends_initialized():
        raise RuntimeError(
            "force_host_device_count must be called before jax initializes "
            "its backends (the flag is read once at backend init)"
        )
    kept = [
        t for t in os.environ.get("XLA_FLAGS", "").split()
        if not t.startswith("--xla_force_host_platform_device_count")
    ]
    kept.append(f"--xla_force_host_platform_device_count={int(n)}")
    os.environ["XLA_FLAGS"] = " ".join(kept)


def init_multiprocess(
    coordinator_address: str,
    num_processes: int,
    process_id: int,
    local_device_ids=None,
) -> None:
    """Join this process to a multi-process jax mesh (one host per mesh
    slice in the part-parallel deployment story); after it returns,
    ``jax.devices()`` spans every process and the global MeshPlan can be
    built as usual. Not for one host's chips: one process drives them all."""
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=int(num_processes),
        process_id=int(process_id),
        local_device_ids=(
            None if local_device_ids is None else list(local_device_ids)
        ),
    )
