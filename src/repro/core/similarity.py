"""Similarity kernels over feature embeddings.

The paper (App. I.2) evaluates cosine similarity (additively rescaled to be
non-negative), dot-product, and RBF kernels, and settles on rescaled cosine:

    sim(r1, r2) = 0.5 + 0.5 * <r1, r2> / (|r1| |r2|)

All functions here are pure jnp and jit-friendly.  The Pallas-accelerated
blocked Gram kernel lives in ``repro.kernels.similarity``; ``gram_matrix``
dispatches to it when requested (TPU) and otherwise uses the XLA path.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

Metric = Literal["cosine", "dot", "rbf"]


def normalize_rows(z: jax.Array, eps: float = 1e-8) -> jax.Array:
    """L2-normalize row vectors.

    Zero-norm rows survive as exact zero vectors (``0 / eps``) rather than
    raising — deliberately: the gram-free engines use all-zero rows as
    padding sentinels (FL init pins their cover to +inf, graph-cut zeroes
    their column sums).  The cost is that a *genuine* zero-norm data row is
    silently flattened and then scores a constant 0.5 against everything
    under the rescaled cosine, distorting facility-location gains.  Screen
    real ground sets with :func:`repro.health.validate_features`, which
    uses :func:`zero_norm_rows` to detect them before any selection math.
    """
    norm = jnp.linalg.norm(z, axis=-1, keepdims=True)
    return z / jnp.maximum(norm, eps)


def zero_norm_rows(z: jax.Array, eps: float = 1e-8) -> jax.Array:
    """Boolean row mask: rows ``normalize_rows`` would flatten to zero.

    The canonical zero-norm detector shared with the health firewall: a
    row is flagged when its L2 norm is <= ``eps`` (the same floor
    ``normalize_rows`` divides by).  Pure jnp and jit-friendly.
    """
    return jnp.linalg.norm(z, axis=-1) <= eps


def cosine_similarity(zq: jax.Array, zk: jax.Array) -> jax.Array:
    """Rescaled cosine similarity in [0, 1] (paper Eq. 10)."""
    zq = normalize_rows(zq)
    zk = normalize_rows(zk)
    return 0.5 + 0.5 * (zq @ zk.T)


def dot_similarity(
    zq: jax.Array, zk: jax.Array, *, shift: float | jax.Array | None = None
) -> jax.Array:
    """Dot-product similarity, additively shifted to be non-negative.

    The paper performs additive scaling so all pairwise values are >= 0; as a
    jit-friendly surrogate we shift by the batch minimum.  Blocked callers
    must pass the *global* minimum as ``shift`` — a per-tile minimum would
    make the assembled matrix a different function in every block.
    """
    s = zq @ zk.T
    if shift is None:
        shift = jnp.min(s)
    return s - jnp.minimum(shift, 0.0)


def rbf_similarity(
    zq: jax.Array, zk: jax.Array, *, kw: float = 0.1, mean_dist: float | jax.Array | None = None
) -> jax.Array:
    """RBF kernel with bandwidth ``kw * mean_dist`` (paper Eq. 11)."""
    # Squared euclidean distances via the expansion trick.
    qq = jnp.sum(zq * zq, axis=-1, keepdims=True)
    kk = jnp.sum(zk * zk, axis=-1, keepdims=True)
    d2 = jnp.maximum(qq - 2.0 * (zq @ zk.T) + kk.T, 0.0)
    if mean_dist is None:
        mean_dist = jnp.mean(jnp.sqrt(d2 + 1e-12))
    return jnp.exp(-d2 / (kw * mean_dist + 1e-12))


@functools.partial(jax.jit, static_argnames=("metric", "kw"))
def gram_matrix(
    zq: jax.Array,
    zk: jax.Array | None = None,
    *,
    metric: Metric = "cosine",
    kw: float = 0.1,
) -> jax.Array:
    """Full pairwise similarity matrix between ``zq`` rows and ``zk`` rows.

    Computed in float32 regardless of input dtype (greedy gain accumulation is
    sensitive to precision).
    """
    if zk is None:
        zk = zq
    zq = zq.astype(jnp.float32)
    zk = zk.astype(jnp.float32)
    if metric == "cosine":
        return cosine_similarity(zq, zk)
    if metric == "dot":
        return dot_similarity(zq, zk)
    if metric == "rbf":
        return rbf_similarity(zq, zk, kw=kw)
    raise ValueError(f"unknown metric {metric!r}")


def gram_matrix_blocked(
    z: jax.Array,
    *,
    metric: Metric = "cosine",
    block: int = 1024,
    kw: float = 0.1,
    use_pallas: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Blocked Gram matrix for large m: streams (block x d) tiles.

    ``use_pallas=True`` routes each tile through the Pallas similarity kernel
    (``repro.kernels.similarity``): compiled on the TPU, interpreted on the
    CPU unless ``interpret`` says otherwise (``repro.kernels.resolve_interpret``).

    ``dot``'s non-negativity shift and ``rbf``'s mean-distance bandwidth are
    data-dependent *global* statistics: they are computed once over all tiles
    in a first pass and passed into every tile, so the assembled matrix is
    the same function in every block (and matches ``gram_matrix``).
    """
    m = z.shape[0]
    z32 = normalize_rows(z.astype(jnp.float32)) if metric == "cosine" else z.astype(jnp.float32)
    nblocks = (m + block - 1) // block
    tiles = [(bi * block, min(m, (bi + 1) * block)) for bi in range(nblocks)]

    if metric == "cosine":
        rows = []
        for lo, hi in tiles:
            if use_pallas:
                from repro.kernels.similarity import ops as sim_ops

                rows.append(sim_ops.similarity(z32[lo:hi], z32, normalized=True,
                                               interpret=interpret))
            else:
                rows.append(0.5 + 0.5 * (z32[lo:hi] @ z32.T))
        return jnp.concatenate(rows, axis=0)

    # dot/rbf: the shift / bandwidth are GLOBAL data-dependent statistics —
    # a per-tile statistic would make the assembled matrix a different
    # function in every block (and disagree with the one-shot gram_matrix).
    if metric == "dot":
        # the raw tiles ARE the output modulo the shift, so one sweep suffices
        raw = [z32[lo:hi] @ z32.T for lo, hi in tiles]
        shift = jnp.min(jnp.stack([jnp.min(r) for r in raw]))
        return jnp.concatenate(raw, axis=0) - jnp.minimum(shift, 0.0)
    if metric == "rbf":
        # two passes, recomputing each d2 tile in the second: the bandwidth
        # needs every tile before any output can be produced, and holding
        # all d2 tiles alongside the exp tiles would triple peak memory —
        # the one thing a blocked builder exists to bound.
        sumsq = jnp.sum(z32 * z32, axis=-1)

        def d2_tile(lo: int, hi: int) -> jax.Array:
            return jnp.maximum(
                sumsq[lo:hi, None] - 2.0 * (z32[lo:hi] @ z32.T) + sumsq[None, :], 0.0
            )

        total = sum(jnp.sum(jnp.sqrt(d2_tile(lo, hi) + 1e-12)) for lo, hi in tiles)
        mean_dist = total / (m * m)
        return jnp.concatenate(
            [jnp.exp(-d2_tile(lo, hi) / (kw * mean_dist + 1e-12)) for lo, hi in tiles],
            axis=0,
        )
    raise ValueError(f"unknown metric {metric!r}")
