"""Layer-level unit tests: SSD vs sequential oracle, MoE dispatch vs dense
reference, chunked attention vs full attention, sliding windows."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig, SSMConfig
from repro.models.attention import attention, attention_specs
from repro.models.moe import moe, moe_specs
from repro.models.module import init_params
from repro.models.ssm import ssd_chunked, ssd_sequential_ref


# --------------------------------------------------------------------- #
# SSD
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("chunk", [4, 8, 16, 64])
@pytest.mark.parametrize("seq", [16, 33, 64])
def test_ssd_chunked_matches_sequential(chunk, seq):
    rng = jax.random.PRNGKey(chunk * 100 + seq)
    b, h, p, n = 2, 3, 8, 4
    k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
    x = jax.random.normal(k1, (b, seq, h, p), jnp.float32)
    B = jax.random.normal(k2, (b, seq, n), jnp.float32)
    C = jax.random.normal(k3, (b, seq, n), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k4, (b, seq, h), jnp.float32))
    A = -jnp.exp(jax.random.normal(k5, (h,), jnp.float32) * 0.5)
    y_chunk, _ = ssd_chunked(x, B, C, dt, A, chunk=chunk)
    y_ref = ssd_sequential_ref(x, B, C, dt, A)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_ref), atol=2e-4, rtol=2e-4)


def test_ssd_state_carry_consistency():
    """Final state from chunked == final state from one-step recurrence."""
    rng = jax.random.PRNGKey(0)
    b, s, h, p, n = 1, 24, 2, 4, 4
    ks = jax.random.split(rng, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    B = jax.random.normal(ks[1], (b, s, n))
    C = jax.random.normal(ks[2], (b, s, n))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[4], (h,)) * 0.5)
    _, h_fin = ssd_chunked(x, B, C, dt, A, chunk=8)
    hs = jnp.zeros((b, h, n, p), jnp.float32)
    for t in range(s):
        a = jnp.exp(dt[:, t] * A[None, :])
        hs = a[:, :, None, None] * hs + jnp.einsum(
            "bn,bhp,bh->bhnp", B[:, t], x[:, t], dt[:, t]
        )
    np.testing.assert_allclose(np.asarray(h_fin), np.asarray(hs), atol=2e-4, rtol=2e-4)


# --------------------------------------------------------------------- #
# MoE
# --------------------------------------------------------------------- #
def _moe_cfg(n_experts=8, top_k=2, cf=8.0):
    return ModelConfig(
        name="t", family="moe", n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
        head_dim=16, d_ff=0, vocab_size=64, dtype=jnp.float32,
        moe=MoEConfig(n_experts=n_experts, top_k=top_k, d_expert=16,
                      capacity_factor=cf),
    )


def _moe_dense_ref(params, x, cfg):
    """Loop-over-experts dense reference (no capacity dropping)."""
    m = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    logits = xf @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, m.top_k)
    top_w = top_p / top_p.sum(-1, keepdims=True)
    out = jnp.zeros_like(xf)
    for slot in range(m.top_k):
        for e in range(m.n_experts):
            sel = top_e[:, slot] == e
            h = jax.nn.silu(xf @ params["wi_gate"][e]) * (xf @ params["wi_up"][e])
            y = h @ params["wo"][e]
            out = out + jnp.where(sel[:, None], top_w[:, slot:slot + 1] * y, 0.0)
    return out.reshape(b, s, d)


def test_moe_matches_dense_reference_with_big_capacity():
    cfg = _moe_cfg(cf=16.0)  # capacity large enough: nothing dropped
    params = init_params(moe_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model), jnp.float32)
    got, aux = moe(params, x, cfg)
    want = _moe_dense_ref(params, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)
    assert float(aux) > 0.5  # switch aux loss ~1 for near-uniform routing


def test_moe_capacity_drops_tokens():
    cfg = _moe_cfg(cf=0.25)  # tiny capacity: most tokens dropped
    params = init_params(moe_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model), jnp.float32)
    got, _ = moe(params, x, cfg)
    assert bool(jnp.isfinite(got).all())
    # Dropped tokens produce zero output rows; at cf=0.25 some must be zero.
    row_norm = jnp.abs(got).sum(-1).reshape(-1)
    assert float((row_norm == 0).mean()) > 0.1


def test_moe_shared_experts():
    cfg = ModelConfig(
        name="t", family="moe", n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
        head_dim=16, d_ff=0, vocab_size=64, dtype=jnp.float32,
        moe=MoEConfig(n_experts=4, top_k=2, d_expert=16, n_shared=2, d_shared=32),
    )
    params = init_params(moe_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 32), jnp.float32)
    out, _ = moe(params, x, cfg)
    assert out.shape == x.shape and bool(jnp.isfinite(out).all())


# --------------------------------------------------------------------- #
# Attention
# --------------------------------------------------------------------- #
def _attn_cfg(**kw):
    base = dict(
        name="t", family="dense", n_layers=1, d_model=64, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=64, dtype=jnp.float32,
        attn_chunk=16,
    )
    base.update(kw)
    return ModelConfig(**base)


def _run_attn(cfg, window=None, seq=64):
    params = init_params(attention_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, seq, cfg.d_model), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (2, seq))
    out, _ = attention(params, x, cfg, positions=pos, causal=True, window=window)
    return out


def test_chunked_attention_matches_full():
    cfg_full = _attn_cfg(attention_impl="full")
    cfg_chunk = _attn_cfg(attention_impl="chunked")
    a = _run_attn(cfg_full)
    b = _run_attn(cfg_chunk)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


def test_chunked_sliding_window_matches_full():
    a = _run_attn(_attn_cfg(attention_impl="full"), window=8)
    b = _run_attn(_attn_cfg(attention_impl="chunked"), window=8)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


def test_sliding_window_limits_context():
    """Token far beyond the window must not influence the output."""
    cfg = _attn_cfg(attention_impl="full")
    params = init_params(attention_specs(cfg), jax.random.PRNGKey(0))
    seq, w = 32, 4
    x = jax.random.normal(jax.random.PRNGKey(1), (1, seq, cfg.d_model), jnp.float32)
    pos = jnp.arange(seq, dtype=jnp.int32)[None]
    out1, _ = attention(params, x, cfg, positions=pos, causal=True, window=w)
    x2 = x.at[0, 0].set(x[0, 0] + 100.0)  # outside window of last token
    out2, _ = attention(params, x2, cfg, positions=pos, causal=True, window=w)
    np.testing.assert_allclose(
        np.asarray(out1[0, -1]), np.asarray(out2[0, -1]), atol=1e-5
    )
    assert float(jnp.abs(out1[0, 0] - out2[0, 0]).max()) > 1e-3  # but locally it did


def test_moe_grouped_dispatch_matches_dense():
    """g>1 dispatch groups (the sharded path) == dense reference when the
    capacity is large enough that nothing drops."""
    from repro.sharding import policy as sp

    cfg = _moe_cfg(cf=16.0)
    params = init_params(moe_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model), jnp.float32)
    want = _moe_dense_ref(params, x, cfg)
    got1, _ = moe(params, x, cfg)  # g=1 (no active mesh)
    # Force g=4 grouping under a real (trivial, 1-device) mesh so the
    # logical constraints resolve.
    from jax.sharding import AxisType

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    saved = (sp._ACTIVE_AXES, sp._ACTIVE_RULES)
    try:
        sp._ACTIVE_AXES = {"data": 4}
        sp._ACTIVE_RULES = {"batch": ("data",), "experts": ("data",)}
        with mesh:
            got4, _ = jax.jit(lambda p, xx: moe(p, xx, cfg))(params, x)
    finally:
        sp._ACTIVE_AXES, sp._ACTIVE_RULES = saved
    np.testing.assert_allclose(np.asarray(got1), np.asarray(want), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(got4), np.asarray(want), atol=1e-4, rtol=1e-4)
