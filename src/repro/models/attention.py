"""GQA attention with three interchangeable inner implementations.

  * ``naive``   — materialized scores; smoke tests and short sequences.
  * ``chunked`` — pure-JAX flash (lax.scan over KV blocks, online softmax);
                  the dry-run path: O(S·block) memory, lowers on any backend.
  * ``pallas``  — ``repro.kernels.flash_attention`` (TPU target, interpreted
                  on the CPU).  Forward only: it has no backward pass, so
                  it cannot train.

Modes: ``train`` (full causal self-attn), ``prefill`` (train + returns KV to
cache), ``decode`` (1 new token vs a fixed-size cache, in-place cache update).
KV heads are *not* repeated in HBM on the chunked/pallas paths.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models.layers import apply_rope, init_dense


class KVCache(NamedTuple):
    k: jax.Array        # (B, S_max, Hkv, D)
    v: jax.Array        # (B, S_max, Hkv, D)
    length: jax.Array   # () or (B,) int32 — valid positions (per-slot OK)


def init_attention(key, d_model: int, n_heads: int, n_kv: int, head_dim: int, dtype=jnp.bfloat16) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": init_dense(kq, d_model, n_heads * head_dim, dtype).reshape(d_model, n_heads, head_dim),
        "wk": init_dense(kk, d_model, n_kv * head_dim, dtype).reshape(d_model, n_kv, head_dim),
        "wv": init_dense(kv, d_model, n_kv * head_dim, dtype).reshape(d_model, n_kv, head_dim),
        "wo": init_dense(ko, n_heads * head_dim, d_model, dtype).reshape(n_heads, head_dim, d_model),
    }


def _naive_attn(q, k, v, *, causal: bool, k_len: jax.Array | None = None) -> jax.Array:
    """q: (B,Sq,H,D); k,v: (B,Sk,Hkv,D) -> (B,Sq,H,D).

    ``k_len`` may be () or (B,) — per-slot cache lengths for batched decode.
    """
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    qg = q.reshape(b, sq, hkv, group, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32))
    logits *= 1.0 / (d ** 0.5)
    kj = jnp.arange(sk)
    mask = jnp.ones((1, 1, 1, sq, sk), bool)
    if causal:
        qi = jnp.arange(sq)[:, None] + (sk - sq)
        mask = mask & (kj[None, :] <= qi)[None, None, None]
    if k_len is not None:
        kl = jnp.asarray(k_len)
        if kl.ndim == 0:
            mask = mask & (kj < kl)[None, None, None, None, :]
        else:  # (B,)
            mask = mask & (kj[None, :] < kl[:, None])[:, None, None, None, :]
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)


def _chunked_attn(q, k, v, *, causal: bool, block: int = 512, k_len=None,
                  bf16_operands: bool = True) -> jax.Array:
    """Online-softmax flash attention in pure JAX (scan over KV blocks).

    §Perf: einsum *operands* stay in bf16 (halving the HBM traffic of the
    dominant attention reads) while accumulation is forced to f32 via
    ``preferred_element_type`` — the same contract the MXU gives the Pallas
    kernel.  Running (m, l, acc) statistics remain f32.
    """
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    pad = (-sk) % block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nkb = k.shape[1] // block
    kb = k.reshape(b, nkb, block, hkv, d).swapaxes(0, 1)  # (nkb, B, blk, Hkv, D)
    vb = v.reshape(b, nkb, block, hkv, d).swapaxes(0, 1)
    op_dtype = q.dtype if (bf16_operands and q.dtype == jnp.bfloat16) else jnp.float32
    qg = (q / jnp.asarray(d ** 0.5, q.dtype)).reshape(b, sq, hkv, group, d).astype(op_dtype)
    offset = sk - sq
    valid_len = sk if k_len is None else k_len

    def step(carry, xs):
        m, l, acc = carry
        kblk, vblk, ki = xs
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kblk.astype(op_dtype),
                       preferred_element_type=jnp.float32)
        cols = ki * block + jnp.arange(block)
        vl = jnp.asarray(valid_len)
        if vl.ndim == 0:
            msk = (cols < vl)[None, None, None, None, :]
        else:  # per-slot (B,)
            msk = (cols[None, :] < vl[:, None])[:, None, None, None, :]
        if causal:
            rows = jnp.arange(sq)[:, None] + offset
            msk = msk & (cols[None, :] <= rows)[None, None, None]
        s = jnp.where(msk, s, -1e30)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(op_dtype), vblk.astype(op_dtype),
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, group, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hkv, group, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, group, sq, d), jnp.float32)
    # §Perf iter-2: remat each KV-block step — without this, backward saves
    # the (nkb, B, Hkv, G, Sq, block) score/prob tensors stacked across the
    # scan (~35% of all HBM traffic at 4k train); recomputing them per block
    # trades ~15% extra attention FLOPs (far from the compute roof).
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(step), (m0, l0, a0), (kb, vb, jnp.arange(nkb)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d).astype(q.dtype)


def _pallas_attn(q, k, v, *, causal: bool) -> jax.Array:
    from repro.kernels.flash_attention import ops as fa_ops

    out = fa_ops.flash_attention(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2), causal=causal
    )
    return out.swapaxes(1, 2)


def attention(
    params: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    causal: bool = True,
    impl: str = "chunked",
    rope_theta: float = 10000.0,
    use_rope: bool = True,
    kv_x: jax.Array | None = None,  # cross-attention keys/values source
    cache: KVCache | None = None,
    mode: str = "train",            # train | prefill | decode
) -> tuple[jax.Array, KVCache | None]:
    """Full attention sublayer: qkv proj -> rope -> attn -> out proj.

    Returns (output, new_cache).  new_cache is None in ``train`` mode.
    """
    src = x if kv_x is None else kv_x
    q = constrain(jnp.einsum("bsd,dhk->bshk", x, params["wq"]), "batch", None, "model", None)
    k = constrain(jnp.einsum("bsd,dhk->bshk", src, params["wk"]), "batch", None, "model", None)
    v = constrain(jnp.einsum("bsd,dhk->bshk", src, params["wv"]), "batch", None, "model", None)
    if use_rope:
        q = apply_rope(q, positions, rope_theta)
        if kv_x is None:  # self-attention: keys rotate with their own positions
            kv_pos = positions if mode != "decode" else positions
            k = apply_rope(k, kv_pos, rope_theta)

    new_cache = None
    k_len = None
    if mode == "decode":
        assert cache is not None
        # write the new kv at position cache.length (B,1,Hkv,D); per-slot
        # lengths (B,) use a vmapped per-row update (batched serving)
        idx = jnp.asarray(cache.length)
        if idx.ndim == 0:
            k_all = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype), (0, idx, 0, 0))
            v_all = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype), (0, idx, 0, 0))
        else:
            upd = jax.vmap(lambda cb, nb, ib: jax.lax.dynamic_update_slice(cb, nb, (ib, 0, 0)))
            k_all = upd(cache.k, k.astype(cache.k.dtype), idx)
            v_all = upd(cache.v, v.astype(cache.v.dtype), idx)
        new_cache = KVCache(k_all, v_all, cache.length + x.shape[1])
        k, v = k_all, v_all
        k_len = idx + x.shape[1]
        causal = False  # masking handled by k_len (decode attends all past)
    elif mode == "prefill":
        new_cache = KVCache(k, v, jnp.full((x.shape[0],), x.shape[1], jnp.int32))

    if impl == "naive" or (mode == "decode" and impl != "chunked"):
        out = _naive_attn(q, k, v, causal=causal, k_len=k_len)
    elif impl == "chunked":
        out = _chunked_attn(q, k, v, causal=causal, k_len=k_len)
    elif impl == "pallas":
        out = _pallas_attn(q, k, v, causal=causal)
    else:
        raise ValueError(f"unknown attention impl {impl!r}")
    # §Perf iter-6: pin the projection output to the storage dtype — XLA
    # otherwise hoists the bf16 convert past the dot (f32 dot result), and the
    # TP psum of this tensor is the dominant collective; bf16 halves it.
    y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), params["wo"],
                   preferred_element_type=x.dtype)
    return y, new_cache
