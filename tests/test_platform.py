"""What the code decides from the platform: Pallas interpret mode, the
fused kernel's refusal off the CPU, real-device meshes, and where the
persistent compilation cache lives."""
import os

import jax
import pytest

from repro.core.dckcore import dc_kcore
from repro.core.decompose import decompose
from repro.graph.build import bucketize
from repro.graph.generators import rmat
from repro.kernels import resolve_interpret
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh_for_devices


@pytest.fixture
def tpu_backend(monkeypatch):
    """Make the platform probe answer "tpu" (the process stays on CPU)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_interpret_only_on_cpu(monkeypatch):
    assert resolve_interpret(None) is True  # this suite runs on the CPU
    assert resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    assert resolve_interpret(True) is True  # explicit override wins


@pytest.mark.parametrize("int16", [False, True])
def test_fused_engine_refused_off_cpu(tpu_backend, int16):
    bg = bucketize(rmat(8, 4, seed=1))
    with pytest.raises(NotImplementedError, match="Only 2D gather"):
        decompose(bg, op="fused", int16=int16)


def test_fused_engine_refused_before_divide(tpu_backend, monkeypatch):
    import repro.core.dckcore as dck

    def no_divide(*a, **k):
        raise AssertionError("the divide ran before the refusal")

    monkeypatch.setattr(dck, "_PartPipeline", no_divide)
    with pytest.raises(NotImplementedError, match="tpu backend"):
        dc_kcore(rmat(8, 4, seed=1), thresholds=[4], engine="fused")


def test_mesh_needs_enough_devices():
    n = len(jax.devices())
    mesh = make_mesh_for_devices(n)
    assert mesh.devices.size == n
    with pytest.raises(ValueError, match=f"has {n} device"):
        make_mesh_for_devices(n + 1)


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_dir(tmp_path, monkeypatch, env_set):
    before = jax.config.jax_compilation_cache_dir
    if env_set:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = compile_cache.DEFAULT_CACHE_DIR
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        # Fixed: no temporary name, pid or clock in the path.
        assert compile_cache.enable_compile_cache() == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if not env_set:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert want == os.path.join(repo, ".jax_cache")
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    assert jax.config.jax_compilation_cache_dir == before
