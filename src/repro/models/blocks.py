"""Composable transformer/SSM blocks and the scanned layer stack.

A *block* = pre-norm mixer (+ residual) then pre-norm FFN (+ residual).
A *group* = the config's pattern of blocks; the model runs ``n_groups``
identical-structure groups via ``lax.scan`` over stacked params (HLO size
stays O(pattern), crucial for the 100-layer dry-runs).

Caches: every block owns a cache slot (possibly ()); a group's cache is a
tuple aligned with the pattern, stacked over groups like the params, so
prefill/decode thread caches through the same scan.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.attention import KVCache, attention, init_attention
from repro.models.layers import init_mlp, mlp, rms_norm
from repro.models.moe import init_moe, moe
from repro.models.ssm import (
    init_mamba,
    init_mlstm,
    init_slstm,
    mamba,
    mamba_state_shape,
    mlstm,
    mlstm_state_shape,
    slstm,
)

Cache = Any  # per-block cache pytree ( () if stateless )


def init_block(key: jax.Array, cfg: ModelConfig, mixer: str, ffn: str, dtype) -> dict:
    km, kf, kn1, kn2 = jax.random.split(key, 4)
    p: dict[str, Any] = {"norm1": jnp.ones((cfg.d_model,), jnp.float32)}
    if mixer in ("attn", "attn_nc", "xattn"):
        p["mixer"] = init_attention(
            km, cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, dtype
        )
    elif mixer == "mamba":
        p["mixer"] = init_mamba(
            km, cfg.d_model, expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim,
            d_state=cfg.ssm_state_dim, dtype=dtype,
        )
    elif mixer == "mlstm":
        p["mixer"] = init_mlstm(
            km, cfg.d_model, expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim, dtype=dtype
        )
    elif mixer == "slstm":
        p["mixer"] = init_slstm(km, cfg.d_model, dtype=dtype)
    else:
        raise ValueError(mixer)
    if ffn == "dense":
        p["norm2"] = jnp.ones((cfg.d_model,), jnp.float32)
        p["ffn"] = init_mlp(kf, cfg.d_model, cfg.d_ff, dtype)
    elif ffn == "moe":
        p["norm2"] = jnp.ones((cfg.d_model,), jnp.float32)
        p["ffn"] = init_moe(kf, cfg.d_model, cfg.d_ff, cfg.num_experts, dtype)
    return p


def init_block_cache(cfg: ModelConfig, mixer: str, batch: int, cache_len: int, dtype) -> Cache:
    """Zeroed cache for one block (length 0)."""
    if mixer == "attn":
        shape = (batch, cache_len, cfg.num_kv_heads, cfg.head_dim)
        return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype), jnp.zeros((batch,), jnp.int32))
    if mixer == "mamba":
        return jnp.zeros(
            mamba_state_shape(cfg.d_model, expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim,
                              d_state=cfg.ssm_state_dim, batch=batch), jnp.float32)
    if mixer == "mlstm":
        return jnp.zeros(
            mlstm_state_shape(cfg.d_model, expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim,
                              batch=batch), jnp.float32)
    if mixer == "slstm":
        z = jnp.zeros((batch, cfg.d_model), jnp.float32)
        return (z, z, jnp.full((batch, cfg.d_model), -1e30, jnp.float32))
    return ()  # xattn recomputes K/V from the (fixed) context; attn_nc stateless


def apply_block(
    bparams: dict,
    x: jax.Array,
    *,
    cfg: ModelConfig,
    mixer: str,
    ffn: str,
    positions: jax.Array,
    context: jax.Array | None,
    cache: Cache,
    mode: str,
) -> tuple[jax.Array, Cache]:
    h = rms_norm(x, bparams["norm1"], cfg.norm_eps)
    new_cache: Cache = ()
    if mixer in ("attn", "attn_nc", "xattn"):
        is_cross = mixer == "xattn"
        attn_mode = mode if (mixer == "attn") else "train"  # cross/enc: stateless
        y, kvc = attention(
            bparams["mixer"], h, positions,
            causal=(mixer == "attn"),
            impl=cfg.attention_impl,
            rope_theta=cfg.rope_theta,
            use_rope=cfg.use_rope and not is_cross,
            kv_x=context if is_cross else None,
            cache=cache if (mixer == "attn" and mode == "decode") else None,
            mode=attn_mode,
        )
        if mixer == "attn" and mode in ("prefill", "decode"):
            new_cache = kvc if mode == "decode" else _fit_cache(kvc, cache)
    elif mixer == "mamba":
        y, st = mamba(bparams["mixer"], h, chunk=cfg.ssm_chunk,
                      state=cache if mode == "decode" else None, mode=mode)
        if mode in ("prefill", "decode"):
            new_cache = st
    elif mixer == "mlstm":
        y, st = mlstm(bparams["mixer"], h, chunk=cfg.ssm_chunk,
                      state=cache if mode == "decode" else None, mode=mode)
        if mode in ("prefill", "decode"):
            new_cache = st
    elif mixer == "slstm":
        y, st = slstm(bparams["mixer"], h, state=cache if mode == "decode" else None, mode=mode)
        if mode in ("prefill", "decode"):
            new_cache = st
    else:
        raise ValueError(mixer)
    x = constrain(x + y, "batch", None, None)

    if ffn in ("dense", "moe"):
        h = rms_norm(x, bparams["norm2"], cfg.norm_eps)
        if ffn == "dense":
            x = constrain(x + mlp(bparams["ffn"], h), "batch", None, None)
        else:
            x = x + moe(
                bparams["ffn"], h,
                top_k=cfg.experts_per_token,
                capacity_factor=cfg.capacity_factor,
                group_size=cfg.moe_group_size,
                dropless=(mode == "decode"),  # tiny token count: exact routing
            )
            x = constrain(x, "batch", None, None)
    return x, new_cache


def _fit_cache(kvc: KVCache, template: Cache) -> KVCache:
    """Pad prefill K/V out to the template's max cache length."""
    if not isinstance(template, KVCache):
        return kvc
    max_len = template.k.shape[1]
    cur = kvc.k.shape[1]
    if cur == max_len:
        return KVCache(kvc.k.astype(template.k.dtype), kvc.v.astype(template.v.dtype), kvc.length)
    pad = ((0, 0), (0, max_len - cur), (0, 0), (0, 0))
    return KVCache(
        jnp.pad(kvc.k.astype(template.k.dtype), pad),
        jnp.pad(kvc.v.astype(template.v.dtype), pad),
        kvc.length,
    )


