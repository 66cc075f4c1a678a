"""Pallas TPU kernel: causal GQA flash attention (online softmax).

Grid: (batch*heads, q_blocks, kv_blocks) with the kv axis innermost so the
(m, l, acc) running statistics live in VMEM scratch across kv steps.  GQA is
handled in the key/value index_map (head h reads kv-head h // group) so K/V
are never repeated in HBM.  Causal block skipping is done by masking; fully
masked kv blocks for a given q block still stream but contribute zeros (the
structural-skip variant is a §Perf follow-up; the dominant cost term is
unchanged).

VMEM working set per step (fp32): q(bq,d) + k(bk,d) + v(bk,d) + acc(bq,d)
+ scores(bq,bk) + stats ≈ 4*(3*128*128 + 2*128*128 + ...) ≈ 0.5 MB at
bq=bk=128, d=128 — far under budget; bq/bk default to 128 for MXU alignment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, causal, sk_minus_sq, sk_valid, block_q, block_k, nk):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (bq, d)
    k = k_ref[0].astype(jnp.float32)  # (bk, d)
    v = v_ref[0].astype(jnp.float32)  # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = cols < sk_valid  # mask padded keys (exact-padding guarantee)
    if causal:
        qi = pl.program_id(1)
        rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + sk_minus_sq
        valid = valid & (cols <= rows)
    s = jnp.where(valid, s, _NEG_INF)

    m_prev = m_ref[...]          # (bq, 1)
    l_prev = l_ref[...]          # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)       # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _final():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "causal_offset", "sk_valid"),
)
def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    causal_offset: int | None = None,  # real (sk - sq) when inputs are padded
    sk_valid: int | None = None,       # number of real (unpadded) keys
) -> jax.Array:
    """Causal GQA flash attention.

    Args: q (B, Hq, Sq, D); k, v (B, Hkv, Sk, D). Sq % block_q == 0,
    Sk % block_k == 0 (ops.py pads).  Returns (B, Hq, Sq, D) in q.dtype.
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    if sq % bq or sk % bk:
        raise ValueError(f"seq ({sq},{sk}) not divisible by blocks ({bq},{bk})")
    nq, nk = sq // bq, sk // bk
    scale = 1.0 / (d ** 0.5)

    qr = q.reshape(b * hq, sq, d)
    kr = k.reshape(b * hkv, sk, d)
    vr = v.reshape(b * hkv, sk, d)

    def q_map(h, qi, ki):
        return (h, qi, 0)

    def kv_map(h, qi, ki):
        # flattened h = b_idx * hq + head; GQA: kv row = b_idx * hkv + head // group
        return ((h // hq) * hkv + (h % hq) // group, ki, 0)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            scale=scale,
            causal=causal,
            sk_minus_sq=sk - sq if causal_offset is None else causal_offset,
            sk_valid=sk if sk_valid is None else sk_valid,
            block_q=bq,
            block_k=bk,
            nk=nk,
        ),
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            _vmem((bq, 1)),   # m: running max
            _vmem((bq, 1)),   # l: running denominator
            _vmem((bq, d)),   # acc: unnormalized output
        ],
        interpret=resolve_interpret(interpret),
    )(qr, kr, vr)
    return out.reshape(b, hq, sq, d)


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)
