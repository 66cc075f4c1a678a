"""Public dispatch for flash attention: pads seq to block grid and routes
Pallas (TPU) / interpret (CPU validation) / reference.

The Pallas kernel is forward-only: it has no backward pass, and
differentiating through it raises ``NotImplementedError`` naming the
training route (``attention_impl="chunked"``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import gqa_attention_ref


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Causal GQA attention; pads ragged seq lengths (exact — padded keys are
    masked out by causality / get zero weight via -inf logits)."""
    if not use_pallas:
        return gqa_attention_ref(q, k, v, causal=causal).astype(q.dtype)
    b, hq, sq, d = q.shape
    sk = k.shape[2]
    bq = min(block_q, max(8, sq))
    bk = min(block_k, max(128, sk))
    pad_q = (-sq) % bq
    pad_k = (-sk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        # Padded keys appear *after* real keys; with causal masking aligned to
        # the end of the key axis they must be masked for the padded queries
        # too — causal offset handles real queries, and padded query rows are
        # sliced off below.
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    @jax.custom_vjp
    def forward_only(q, k, v):
        return flash_attention_pallas(
            q,
            k,
            v,
            causal=causal,
            block_q=bq,
            block_k=bk,
            interpret=interpret,
            causal_offset=sk - sq,  # mask geometry of the *real* shapes
            sk_valid=sk,
        )

    def fwd(q, k, v):
        return forward_only(q, k, v), None

    def bwd(_, g):
        raise NotImplementedError(
            "the Pallas flash attention kernel is forward-only (it has no "
            "backward pass); train with attention_impl='chunked'"
        )

    forward_only.defvjp(fwd, bwd)
    return forward_only(q, k, v)[:, :, :sq, :]
