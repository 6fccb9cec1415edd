"""Dry-run machinery on a small virtual mesh (subprocess; fast CI proxy for
the 512-chip sweep — the full sweep is `python -m repro.launch.dryrun --all
--both-meshes` and its artifacts live in benchmarks/artifacts/dryrun)."""
import pytest

from distributed_helpers import run_with_devices

_CODE = r"""
import jax, json
from jax.sharding import AxisType
from repro.launch.specs import input_specs, rules_for
from repro.launch.steps import step_fn_for
from repro.sharding.policy import active_mesh
from repro.configs import SHAPES
from repro.roofline.analysis import parse_collectives

mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
arch, shape_name = "%ARCH%", "%SHAPE%"
specs, cfg, log = input_specs(arch, shape_name, mesh)
kind = SHAPES[shape_name].kind
fn, order = step_fn_for(cfg, kind, accum_steps=2 if kind == "train" else 1)
kwargs = {k: specs[k] for k in order}
with mesh, active_mesh(mesh):
    lowered = jax.jit(fn).lower(**kwargs)
    compiled = lowered.compile()
mem = compiled.memory_analysis()
cost = compiled.cost_analysis()
colls = parse_collectives(compiled.as_text())
assert cost["flops"] > 0
assert mem.temp_size_in_bytes >= 0
print("OK", arch, shape_name, int(cost["flops"]), colls.total_wire)
"""


@pytest.mark.parametrize(
    "arch,shape",
    [
        ("granite-3-2b", "train_4k"),
        ("qwen2-moe-a2.7b", "prefill_32k"),
        ("mamba2-130m", "decode_32k"),
        ("whisper-small", "decode_32k"),
    ],
)
def test_dryrun_cell_small_mesh(arch, shape):
    out = run_with_devices(
        _CODE.replace("%ARCH%", arch).replace("%SHAPE%", shape), n_devices=8,
        timeout=900,
    )
    assert "OK" in out
