"""Decoder blocks and the scanned heterogeneous layer stack.

Every assigned architecture is a periodic pattern of block kinds
(attention / SSM / dense-MLP / MoE / cross-attention / local / global).
The stack groups layers into one *pattern period* (gemma3: 6, jamba: 8,
llama-vision: 5, homogeneous archs: 1), stacks parameters per period slot
over groups, and runs ``lax.scan`` over groups — HLO size stays O(period),
independent of depth (62- and 72-layer models compile like 6- and 8-layer
ones). Layers beyond ``n_groups * period`` form an unrolled tail
(gemma3: 62 = 10x6 + 2).

Caches thread through the scan as per-slot stacked pytrees
(``[n_groups, ...]`` leaves), so prefill/decode share the same structure.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import layernorm, layernorm_specs, rmsnorm, rmsnorm_specs
from repro.models.mlp import gelu_mlp, gelu_mlp_specs, swiglu, swiglu_specs
from repro.models.moe import moe, moe_specs
from repro.models.module import ParamSpec, stack_specs


# --------------------------------------------------------------------- #
# Layer kinds
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class LayerKind:
    attn: bool
    ssm: bool
    moe: bool
    cross: bool
    window: Optional[int]
    theta: float
    causal: bool = True


def layer_kind(cfg, idx: int, causal: bool = True, allow_cross: bool = True) -> LayerKind:
    is_attn = cfg.is_attn_layer(idx)
    window = None
    theta = cfg.rope_theta
    if is_attn and cfg.sliding_window is not None:
        if cfg.is_global_layer(idx):
            theta = cfg.rope_global_theta or cfg.rope_theta
        else:
            window = cfg.sliding_window
    return LayerKind(
        attn=is_attn,
        ssm=not is_attn,
        moe=cfg.is_moe_layer(idx),
        cross=allow_cross and cfg.is_cross_layer(idx),
        window=window,
        theta=theta,
        causal=causal,
    )


def pattern_period(cfg) -> int:
    period = 1
    for cycle in (cfg.global_every, cfg.attn_every, cfg.cross_attn_every,
                  cfg.moe.every_k_layers if cfg.moe else None):
        if cycle:
            period = math.lcm(period, cycle)
    return period


# --------------------------------------------------------------------- #
# Norm dispatch
# --------------------------------------------------------------------- #
def _norm_specs(cfg):
    return layernorm_specs(cfg.d_model) if cfg.norm_type == "layer" else rmsnorm_specs(cfg.d_model)


def _norm(params, x, cfg):
    fn = layernorm if cfg.norm_type == "layer" else rmsnorm
    return fn(params, x, cfg.norm_eps)


# --------------------------------------------------------------------- #
# One block
# --------------------------------------------------------------------- #
def block_specs(cfg, kind: LayerKind) -> dict:
    specs: Dict[str, Any] = {}
    if kind.cross:
        specs["cross_norm"] = _norm_specs(cfg)
        specs["cross"] = attn_lib.attention_specs(cfg, cross=True)
        specs["cross_gate"] = ParamSpec((), (), init="zeros")
    specs["pre_norm"] = _norm_specs(cfg)
    if kind.attn:
        specs["attn"] = attn_lib.attention_specs(cfg)
    else:
        specs["ssm"] = ssm_lib.ssm_specs(cfg)
    if cfg.post_norms:
        specs["post_norm"] = _norm_specs(cfg)
    if kind.moe:
        specs["mlp_norm"] = _norm_specs(cfg)
        specs["moe"] = moe_specs(cfg)
    elif cfg.d_ff > 0:
        specs["mlp_norm"] = _norm_specs(cfg)
        if cfg.mlp_type == "gelu":
            specs["mlp"] = gelu_mlp_specs(cfg.d_model, cfg.d_ff, cfg.param_dtype)
        else:
            specs["mlp"] = swiglu_specs(cfg.d_model, cfg.d_ff, cfg.param_dtype)
    return specs


def _mlp_part(params, x, cfg, kind: LayerKind):
    if "mlp_norm" not in params:  # pure-SSM blocks (mamba2) have no FFN
        return x, jnp.float32(0)
    h = _norm(params["mlp_norm"], x, cfg)
    if kind.moe:
        out, aux = moe(params["moe"], h, cfg)
    elif cfg.mlp_type == "gelu":
        out, aux = gelu_mlp(params["mlp"], h, cfg), jnp.float32(0)
    else:
        out, aux = swiglu(params["mlp"], h, cfg), jnp.float32(0)
    return x + out, aux


def block_apply(params, x, cfg, kind: LayerKind, ctx, collect_cache: bool = False):
    """Full-sequence block. ctx: positions [B,S], cross_src, cross_positions.

    Returns (x, aux, cache_or_None)."""
    cache = {}
    if kind.cross:
        h = _norm(params["cross_norm"], x, cfg)
        c_out, (ck, cv) = attn_lib.attention(
            params["cross"], h, cfg,
            positions=ctx["positions"], causal=False,
            kv_src=ctx["cross_src"], kv_positions=ctx.get("cross_positions"),
        )
        x = x + jnp.tanh(params["cross_gate"]).astype(x.dtype) * c_out
        if collect_cache:
            n_src = ck.shape[1]
            src_pos = jnp.broadcast_to(
                jnp.arange(n_src, dtype=jnp.int32), (ck.shape[0], n_src)
            )
            cache["cross_kv"] = {"k": ck, "v": cv, "slot_pos": src_pos}
    h = _norm(params["pre_norm"], x, cfg)
    if kind.attn:
        a_out, (k, v) = attn_lib.attention(
            params["attn"], h, cfg,
            positions=ctx["positions"], causal=kind.causal,
            window=kind.window, theta=kind.theta,
        )
        if collect_cache:
            lc = attn_lib.init_cache_layer(cfg, x.shape[0], ctx["max_len"], kind.window)
            cache["attn"] = attn_lib.cache_write(lc, k, v, ctx["positions"])
    else:
        a_out, ssm_cache = ssm_lib.ssm_block(
            params["ssm"], h, cfg, return_cache=collect_cache
        )
        if collect_cache:
            cache["ssm"] = ssm_cache
    if cfg.post_norms:
        a_out = _norm(params["post_norm"], a_out, cfg)
    x = x + a_out
    x, aux = _mlp_part(params, x, cfg, kind)
    return x, aux, (cache if collect_cache else None)


def block_decode(params, x, cache, cfg, kind: LayerKind, ctx):
    """One-token block step. ctx: position [B]. Returns (x, new_cache)."""
    new_cache = dict(cache)
    if kind.cross:
        h = _norm(params["cross_norm"], x, cfg)
        c_out, _ = attn_lib.attention_decode(
            params["cross"], h, cache["cross_kv"], cfg,
            position=ctx["position"], cross=True,
        )
        x = x + jnp.tanh(params["cross_gate"]).astype(x.dtype) * c_out
    h = _norm(params["pre_norm"], x, cfg)
    if kind.attn:
        a_out, new_cache["attn"] = attn_lib.attention_decode(
            params["attn"], h, cache["attn"], cfg,
            position=ctx["position"], window=kind.window, theta=kind.theta,
        )
    else:
        a_out, new_cache["ssm"] = ssm_lib.ssm_block_decode(params["ssm"], h, cache["ssm"], cfg)
    if cfg.post_norms:
        a_out = _norm(params["post_norm"], a_out, cfg)
    x = x + a_out
    x, _ = _mlp_part(params, x, cfg, kind)
    return x, new_cache


# --------------------------------------------------------------------- #
# Stack: scan over groups + unrolled tail
# --------------------------------------------------------------------- #
def stack_layout(cfg, n_layers: Optional[int] = None, causal: bool = True,
                 allow_cross: bool = True):
    n_layers = n_layers if n_layers is not None else cfg.n_layers
    period = pattern_period(cfg)
    n_groups, tail = divmod(n_layers, period)
    if n_groups == 0:
        period, n_groups, tail = 1, 0, n_layers
    kinds = [layer_kind(cfg, i, causal, allow_cross) for i in range(period)]
    tail_kinds = [
        layer_kind(cfg, n_groups * period + i, causal, allow_cross)
        for i in range(tail)
    ]
    return period, n_groups, kinds, tail_kinds


def stack_specs_tree(cfg, n_layers: Optional[int] = None, causal: bool = True,
                     allow_cross: bool = True) -> dict:
    period, n_groups, kinds, tail_kinds = stack_layout(cfg, n_layers, causal, allow_cross)
    tree: Dict[str, Any] = {}
    if n_groups > 0:
        group = {f"slot{i}": block_specs(cfg, k) for i, k in enumerate(kinds)}
        tree["scan"] = stack_specs(group, n_groups, axis_name="layers")
    if tail_kinds:
        tree["tail"] = {f"layer{i}": block_specs(cfg, k) for i, k in enumerate(tail_kinds)}
    return tree


def _maybe_remat(fn, cfg):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    return fn


def stack_apply(params, x, cfg, ctx, n_layers: Optional[int] = None,
                causal: bool = True, collect_cache: bool = False,
                allow_cross: bool = True):
    """Run the whole stack. Returns (x, aux_total, caches_or_None)."""
    period, n_groups, kinds, tail_kinds = stack_layout(cfg, n_layers, causal, allow_cross)
    caches: Dict[str, Any] = {}

    if n_groups > 0:
        def group_fn(x, group_params):
            # Barrier: without it XLA hoists the first-use f32 upcast of x
            # out of the backward scan, materializing the whole residual
            # stash in f32 (2x the bf16 stash; measured on grok-1). Its
            # differentiation rule barriers the cotangent on the backward
            # path too.
            x = jax.lax.optimization_barrier(x)
            aux = jnp.float32(0)
            gcache = {}
            for i, kind in enumerate(kinds):
                x, a, c = block_apply(
                    group_params[f"slot{i}"], x, cfg, kind, ctx, collect_cache
                )
                aux = aux + a
                if collect_cache:
                    gcache[f"slot{i}"] = c
            return x, (aux, gcache) if collect_cache else (aux, None)

        group_fn = _maybe_remat(group_fn, cfg)

        def scan_body(carry, group_params):
            x, aux = carry
            x, (a, gcache) = group_fn(x, group_params)
            return (x, aux + a), gcache

        (x, aux), gcaches = jax.lax.scan(scan_body, (x, jnp.float32(0)), params["scan"])
        if collect_cache:
            caches["scan"] = gcaches
    else:
        aux = jnp.float32(0)

    if tail_kinds:
        tcaches = {}
        for i, kind in enumerate(tail_kinds):
            x, a, c = block_apply(
                params["tail"][f"layer{i}"], x, cfg, kind, ctx, collect_cache
            )
            aux = aux + a
            if collect_cache:
                tcaches[f"layer{i}"] = c
        if collect_cache:
            caches["tail"] = tcaches
    return x, aux, (caches if collect_cache else None)


def stack_decode(params, x, caches, cfg, ctx, n_layers: Optional[int] = None):
    """One-token step through the stack. Returns (x, new_caches)."""
    period, n_groups, kinds, tail_kinds = stack_layout(cfg, n_layers)

    if n_groups > 0:
        def scan_body(x, inp):
            group_params, gcache = inp
            new_gcache = {}
            for i, kind in enumerate(kinds):
                x, new_gcache[f"slot{i}"] = block_decode(
                    group_params[f"slot{i}"], x, gcache[f"slot{i}"], cfg, kind, ctx
                )
            return x, new_gcache

        x, new_scan = jax.lax.scan(scan_body, x, (params["scan"], caches["scan"]))
        new_caches = {"scan": new_scan}
    else:
        new_caches = {}

    if tail_kinds:
        new_tail = {}
        for i, kind in enumerate(tail_kinds):
            x, new_tail[f"layer{i}"] = block_decode(
                params["tail"][f"layer{i}"], x, caches["tail"][f"layer{i}"], cfg, kind, ctx
            )
        new_caches["tail"] = new_tail
    return x, new_caches


# --------------------------------------------------------------------- #
# Cache spec trees (dry-run inputs, no allocation)
# --------------------------------------------------------------------- #
def _block_cache_specs(cfg, kind: LayerKind, batch: int, max_len: int):
    spec: Dict[str, Any] = {}
    if kind.cross:
        n_src = cfg.encoder.n_frames if cfg.encoder else cfg.n_vision_tokens
        spec["cross_kv"] = {
            "k": ((batch, n_src, cfg.n_kv_heads, cfg.head_dim),
                  ("cache_batch", None, "kv_heads", None), cfg.dtype),
            "v": ((batch, n_src, cfg.n_kv_heads, cfg.head_dim),
                  ("cache_batch", None, "kv_heads", None), cfg.dtype),
            "slot_pos": ((batch, n_src), ("cache_batch", None), jnp.int32),
        }
    if kind.attn:
        spec["attn"] = attn_lib.cache_layer_specs(cfg, batch, max_len, kind.window)
    else:
        spec["ssm"] = ssm_lib.ssm_cache_specs(cfg, batch)
    return spec


def cache_specs_tree(cfg, batch: int, max_len: int, n_layers: Optional[int] = None):
    """(shape, axes, dtype) tree matching stack_decode's cache structure."""
    period, n_groups, kinds, tail_kinds = stack_layout(cfg, n_layers)
    is_sd = lambda x: isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)
    tree: Dict[str, Any] = {}
    if n_groups > 0:
        group = {
            f"slot{i}": _block_cache_specs(cfg, k, batch, max_len)
            for i, k in enumerate(kinds)
        }
        tree["scan"] = jax.tree.map(
            lambda sd: ((n_groups,) + sd[0], (None,) + sd[1], sd[2]), group, is_leaf=is_sd
        )
    if tail_kinds:
        tree["tail"] = {
            f"layer{i}": _block_cache_specs(cfg, k, batch, max_len)
            for i, k in enumerate(tail_kinds)
        }
    return tree
