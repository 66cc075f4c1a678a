"""Public dispatch for the similarity kernel: pads to block multiples, picks
Pallas (TPU) vs interpret (CPU validation) vs pure-jnp reference."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.similarity.ref import similarity_ref
from repro.kernels.similarity.similarity import similarity_pallas


def _pad_rows(z: jax.Array, mult: int) -> tuple[jax.Array, int]:
    m = z.shape[0]
    pad = (-m) % mult
    if pad:
        z = jnp.concatenate([z, jnp.ones((pad, z.shape[1]), z.dtype)], axis=0)
    return z, m


def similarity(
    zq: jax.Array,
    zk: jax.Array,
    *,
    normalized: bool = False,
    block_q: int = 256,
    block_k: int = 256,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Rescaled cosine Gram matrix; auto-pads ragged shapes to block grid."""
    if not use_pallas:
        return similarity_ref(zq, zk, normalized=normalized)
    bq = min(block_q, max(8, zq.shape[0]))
    bk = min(block_k, max(128, zk.shape[0]))
    zq_p, mq = _pad_rows(zq, bq)
    zk_p, mk = _pad_rows(zk, bk)
    out = similarity_pallas(
        zq_p, zk_p, block_q=bq, block_k=bk, normalized=normalized, interpret=interpret
    )
    return out[:mq, :mk]
