"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
``v5e:2x2`` topology that is described, not attached. Nothing runs, so
these tests say nothing about results or speed; they catch what the
interpreter cannot — a kernel Mosaic refuses, a block that does not fit,
a compile that blows up with the bucket width.

Only one process at a time may load the TPU library, and it keeps it until
it exits. So the topology is described inside a module fixture (never at
import time), and every test that needs it lives in this one file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.decompose import _sweep
from repro.core.distributed import MeshPlan, make_sweep_fn
from repro.core.hindex import hindex_of_sequence
from repro.graph.build import bucketize
from repro.graph.generators import rmat
from repro.kernels.counts import partial_counts_op
from repro.kernels.fused import fused_sweep_op
from repro.kernels.fused.ops import MOSAIC_REFUSAL
from repro.kernels.hindex import hindex_op

WIDTHS = [8, 128, 1024]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # Executables for a described chip cannot be read back from the
        # persistent cache without the chip; keep them out of it.
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Make the kernels' platform probe answer "tpu" while a whole engine
    program is traced here, so it picks the Mosaic lowering as on a chip."""
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    jax.clear_caches()


def _spec(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("op", ["hindex", "counts"])
@pytest.mark.parametrize("width", WIDTHS)
def test_kernel_compiles_for_v5e(one_chip, op, width):
    rows = 4096
    x = _spec((rows, width), one_chip)
    v = _spec((rows,), one_chip)
    if op == "hindex":
        fn = jax.jit(lambda a, e, c: hindex_op(a, e, c, cand=128,
                                               interpret=False))
        compiled = fn.lower(x, v, v).compile()
    else:
        fn = jax.jit(lambda a, e: partial_counts_op(a, e, cand=128,
                                                    interpret=False))
        compiled = fn.lower(x, v).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("op", ["hindex", "counts"])
def test_hub_width_compiles_for_v5e(one_chip, op):
    """A hub bucket (2^18 slots, as R-MAT scale 22 has) with a wide
    candidate window: the kernels walk slot chunks on the grid and
    candidate chunks in a loop, so this compiles like a narrow one."""
    rows, width, cand = 8, 1 << 18, 2000
    x = _spec((rows, width), one_chip)
    v = _spec((rows,), one_chip)
    if op == "hindex":
        compiled = jax.jit(lambda a, e, c: hindex_op(
            a, e, c, cand=cand, interpret=False)).lower(x, v, v).compile()
    else:
        compiled = jax.jit(lambda a, e: partial_counts_op(
            a, e, cand=cand, interpret=False)).lower(x, v).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_kernel_is_refused_for_v5e(one_chip):
    """The refusal ``require_fused_platform`` quotes is still Mosaic's."""
    n, rows, width = 4096, 1024, 128
    fn = jax.jit(lambda c, e, i, nb: fused_sweep_op(
        c, e, i, nb, cand=128, interpret=False))
    args = (_spec((n + 1,), one_chip), _spec((n + 1,), one_chip),
            _spec((rows,), one_chip), _spec((rows, width), one_chip))
    with pytest.raises(NotImplementedError,
                       match=MOSAIC_REFUSAL.split(": ", 1)[1]):
        fn.lower(*args).compile()


def _buckets_and_cand(scale):
    g = rmat(scale, 8, seed=3)
    bg = bucketize(g)
    cand = max(1, hindex_of_sequence(bg.degrees.astype(np.int64) + bg.ext))
    return g, bg, cand


def _sweep_specs(bg, one_chip):
    return [(_spec(b.node_ids.shape, one_chip),
             _spec(b.neigh.shape, one_chip),
             _spec(mask.shape, one_chip, jnp.uint32))
            for b, mask in zip(bg.buckets, bg.row_tile_masks())]


def test_kernel_engine_sweep_compiles_for_v5e(one_chip, on_tpu):
    """The ``engine="kernel"`` sweep over one real tile set: every bucket's
    h-index is a Mosaic kernel, none is interpreted."""
    g, bg, cand = _buckets_and_cand(9)
    n = g.n_nodes
    buckets = _sweep_specs(bg, one_chip)
    compiled = _sweep.lower(
        _spec((n + 1,), one_chip), _spec((n + 1,), one_chip), buckets,
        _spec((len(buckets),), one_chip, jnp.bool_), op="kernel", cand=cand,
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= len(bg.buckets)


def test_sorted_sweep_scatters_no_slots_for_v5e(one_chip):
    """The sorted engine's sweep with the frontier on, as the chip's
    compiler emits it: every scatter writes the int32 estimate vector (one
    row write per tile); the dirty bits take no scatter over slots."""
    import re

    g, bg, cand = _buckets_and_cand(9)
    n = g.n_nodes
    text = _sweep.lower(
        _spec((n + 1,), one_chip), _spec((n + 1,), one_chip),
        _sweep_specs(bg, one_chip),
        _spec((len(bg.buckets),), one_chip, jnp.bool_), op="sorted",
        cand=cand,
    ).compile().as_text()
    scatters = re.findall(r"= (\S+) scatter\(", text)
    assert scatters
    assert all(s.startswith(f"s32[{n + 1}]") for s in scatters), scatters


def test_sharded_kernel_sweep_compiles_for_2x2(topo, on_tpu):
    """The shard_map sweep with ``use_kernel=True`` on a 2x2 data x model
    mesh of the described chips: counts kernels plus their collectives."""
    g, bg, cand = _buckets_and_cand(9)
    n = g.n_nodes
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    plan = MeshPlan(mesh=mesh, node_axes=("data",), slot_axes=("model",))
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("data"))
    tiles = NamedSharding(mesh, P("data", "model"))
    buckets = [
        (_spec((-(-b.n_rows // 2) * 2,), rows),
         _spec((-(-b.n_rows // 2) * 2, -(-b.width // 2) * 2), tiles))
        for b in bg.buckets
    ]
    sweep = make_sweep_fn(plan, cand, use_kernel=True)(len(buckets))
    compiled = sweep.lower(
        _spec((n + 1,), rep), _spec((n + 1,), rep),
        _spec((len(buckets),), rep, jnp.bool_),
        _spec((n + 1,), rep, jnp.int16), buckets,
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= len(bg.buckets)
    assert "all-reduce" in text
