"""Public dispatch for the facility-location gains kernels (pads + routes)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fl_gains.fl_gains import (
    fl_gains_gram_free_delta_pallas,
    fl_gains_gram_free_pallas,
    fl_gains_pallas,
)
from repro.kernels.fl_gains.ref import (
    fl_gains_gram_free_delta_ref,
    fl_gains_gram_free_ref,
    fl_gains_ref,
)


def fl_gains(
    K: jax.Array,
    c: jax.Array,
    *,
    block_i: int = 512,
    block_j: int = 512,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Facility-location marginal gains; auto-pads to the block grid.

    Padding is exact: padded ground rows use c = +big so relu(K - c) = 0;
    padded candidate columns are sliced off the result.
    """
    if not use_pallas:
        return fl_gains_ref(K, c)
    n, n_cand = K.shape
    bi = min(block_i, max(8, n))
    bj = min(block_j, max(128, n_cand))
    pad_i = (-n) % bi
    pad_j = (-n_cand) % bj
    if pad_i or pad_j:
        K = jnp.pad(K, ((0, pad_i), (0, pad_j)))
        c = jnp.pad(c, (0, pad_i), constant_values=jnp.inf)
    out = fl_gains_pallas(K, c, block_i=bi, block_j=bj, interpret=interpret)
    return out[:n_cand]


def fl_gains_gram_free(
    z: jax.Array,
    zc: jax.Array,
    c: jax.Array,
    *,
    block_i: int = 512,
    block_j: int = 512,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Gram-free facility-location marginal gains; auto-pads to the block grid.

    Padding is exact: padded ground rows get c = +big so their on-the-fly
    similarity (0.5 against a zero feature row) can never clear the relu;
    padded candidate rows are sliced off the result; the feature dimension is
    zero-padded to a lane-aligned multiple of 128 (zeros do not change dot
    products).
    """
    if not use_pallas:
        return fl_gains_gram_free_ref(z, zc, c)
    n, d = z.shape
    n_cand = zc.shape[0]
    bi = min(block_i, max(8, n))
    bj = min(block_j, max(128, n_cand))
    pad_i = (-n) % bi
    pad_j = (-n_cand) % bj
    pad_d = (-d) % 128
    if pad_i or pad_d:
        z = jnp.pad(z, ((0, pad_i), (0, pad_d)))
        c = jnp.pad(c, (0, pad_i), constant_values=jnp.inf)
    if pad_j or pad_d:
        zc = jnp.pad(zc, ((0, pad_j), (0, pad_d)))
    out = fl_gains_gram_free_pallas(z, zc, c, block_i=bi, block_j=bj,
                                    interpret=interpret)
    return out[:n_cand]


def fl_gains_gram_free_delta(
    z: jax.Array,
    zc: jax.Array,
    c_old: jax.Array,
    c_new: jax.Array,
    *,
    block_i: int = 512,
    block_j: int = 512,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Lazy-greedy gain correction over a touched-row subset; auto-pads.

    Padding is exact: padded touched rows get c_old = c_new = +big so both
    relu terms vanish identically; padded candidate rows are sliced off; the
    feature dimension is zero-padded to a lane-aligned multiple of 128.

    ``zc`` need not be the full ground set: the sharded lazy path
    (``core.sharded``) passes each device's local candidate block, so one
    call corrects an (n/ndev,)-slice of the cached gain vector per shard —
    the reduction over ``z`` rows is unchanged, keeping per-candidate sums
    bit-exact against the single-device call.
    """
    if not use_pallas:
        return fl_gains_gram_free_delta_ref(z, zc, c_old, c_new)
    b, d = z.shape
    n_cand = zc.shape[0]
    bi = min(block_i, max(8, b))
    bj = min(block_j, max(128, n_cand))
    pad_i = (-b) % bi
    pad_j = (-n_cand) % bj
    pad_d = (-d) % 128
    if pad_i or pad_d:
        z = jnp.pad(z, ((0, pad_i), (0, pad_d)))
        c_old = jnp.pad(c_old, (0, pad_i), constant_values=jnp.inf)
        c_new = jnp.pad(c_new, (0, pad_i), constant_values=jnp.inf)
    if pad_j or pad_d:
        zc = jnp.pad(zc, ((0, pad_j), (0, pad_d)))
    out = fl_gains_gram_free_delta_pallas(z, zc, c_old, c_new,
                                          block_i=bi, block_j=bj,
                                          interpret=interpret)
    return out[:n_cand]
