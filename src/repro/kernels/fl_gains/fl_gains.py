"""Pallas TPU kernels: facility-location greedy gains (the selection hot loop).

Two entry points:

``fl_gains_pallas`` — for a candidate block J and running cache c, computes
``g_j = Σ_i relu(K_ij - c_i)`` with the ground-set axis i as the innermost
(revisited-output) reduction axis, streaming (bi, bj) similarity tiles
HBM→VMEM.  This is the O(n²)-per-step inner loop of facility-location greedy;
blocking keeps each step's working set at

    4 * (bi*bj + bi + bj) bytes ≈ 1.05 MB  (bi=bj=512, fp32)

well inside VMEM, with MXU-friendly 128-aligned tiles (the relu-sum lowers to
VPU reductions; the tile shape choice matters for layout, not the MXU).

``fl_gains_gram_free_pallas`` — the gram-free variant: the (bi, bj) similarity
tile is never read from HBM but fused on the fly on the MXU from row-normalized
feature tiles, ``K_tile = 0.5 + 0.5 · z_tile @ zc_tileᵀ``.  The (n, n) Gram
matrix is never materialized anywhere: HBM holds only the (n, d) features and
the (n,) cover vector, so per-class selection memory drops from O(n²) to
O(n·d + n) while each grid step keeps a

    4 * (bi*d + bj*d + bi*bj + bi + bj) bytes ≈ 2.6 MB  (bi=bj=512, d=128)

working set in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _fl_gains_kernel(k_ref, c_ref, out_ref):
    i = pl.program_id(1)  # reduction (ground-set) axis — innermost
    k_blk = k_ref[...].astype(jnp.float32)   # (bi, bj)
    c_blk = c_ref[...].astype(jnp.float32)   # (bi, 1)
    part = jnp.sum(jnp.maximum(k_blk - c_blk, 0.0), axis=0, keepdims=True)  # (1, bj)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = part

    @pl.when(i > 0)
    def _acc():
        out_ref[...] += part


@functools.partial(jax.jit, static_argnames=("block_i", "block_j", "interpret"))
def fl_gains_pallas(
    K: jax.Array,
    c: jax.Array,
    *,
    block_i: int = 512,
    block_j: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Gains for all candidate columns of K given max-cache c.

    Args:
      K: (n, n_cand); c: (n,).  n % block_i == 0, n_cand % block_j == 0.
    """
    n, n_cand = K.shape
    bi = min(block_i, n)
    bj = min(block_j, n_cand)
    if n % bi or n_cand % bj:
        raise ValueError(f"shape ({n},{n_cand}) not divisible by ({bi},{bj})")
    grid = (n_cand // bj, n // bi)
    out = pl.pallas_call(
        _fl_gains_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bi, bj), lambda j, i: (i, j)),
            pl.BlockSpec((bi, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bj), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, n_cand), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(K, c[:, None])
    return out[0]


def _fl_gains_gram_free_kernel(z_ref, zc_ref, c_ref, out_ref):
    i = pl.program_id(1)  # reduction (ground-set) axis — innermost
    z_blk = z_ref[...].astype(jnp.float32)    # (bi, d)
    zc_blk = zc_ref[...].astype(jnp.float32)  # (bj, d)
    c_blk = c_ref[...].astype(jnp.float32)    # (bi, 1)
    # Fuse the similarity tile on the MXU — the Gram matrix never exists.
    sim = 0.5 + 0.5 * jax.lax.dot_general(
        z_blk, zc_blk,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (bi, bj)
    part = jnp.sum(jnp.maximum(sim - c_blk, 0.0), axis=0, keepdims=True)  # (1, bj)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = part

    @pl.when(i > 0)
    def _acc():
        out_ref[...] += part


def _fl_gains_gram_free_delta_kernel(z_ref, zc_ref, co_ref, cn_ref, out_ref):
    i = pl.program_id(1)  # reduction (touched-rows) axis — innermost
    z_blk = z_ref[...].astype(jnp.float32)    # (bi, d)
    zc_blk = zc_ref[...].astype(jnp.float32)  # (bj, d)
    co_blk = co_ref[...].astype(jnp.float32)  # (bi, 1)
    cn_blk = cn_ref[...].astype(jnp.float32)  # (bi, 1)
    sim = 0.5 + 0.5 * jax.lax.dot_general(
        z_blk, zc_blk,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (bi, bj)
    part = jnp.sum(
        jnp.maximum(sim - cn_blk, 0.0) - jnp.maximum(sim - co_blk, 0.0),
        axis=0, keepdims=True,
    )  # (1, bj)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = part

    @pl.when(i > 0)
    def _acc():
        out_ref[...] += part


@functools.partial(jax.jit, static_argnames=("block_i", "block_j", "interpret"))
def fl_gains_gram_free_delta_pallas(
    z: jax.Array,
    zc: jax.Array,
    c_old: jax.Array,
    c_new: jax.Array,
    *,
    block_i: int = 512,
    block_j: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused lazy-greedy gain correction: both relu terms of the delta share
    one on-the-fly similarity tile (see ``ref.fl_gains_gram_free_delta_ref``).

    The i (touched-rows) axis is the reduction axis, so the kernel is shard
    agnostic on the candidate side: the sharded lazy engine calls it with
    ``zc`` = the device-local candidate block and b unchanged.

    Args:
      z: (b, d) touched ground rows; zc: (n_cand, d); c_old/c_new: (b,).
      b % block_i == 0, n_cand % block_j == 0.
    """
    b, d = z.shape
    n_cand = zc.shape[0]
    bi = min(block_i, b)
    bj = min(block_j, n_cand)
    if b % bi or n_cand % bj:
        raise ValueError(f"shape ({b},{n_cand}) not divisible by ({bi},{bj})")
    grid = (n_cand // bj, b // bi)
    out = pl.pallas_call(
        _fl_gains_gram_free_delta_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bi, d), lambda j, i: (i, 0)),
            pl.BlockSpec((bj, d), lambda j, i: (j, 0)),
            pl.BlockSpec((bi, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((bi, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bj), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, n_cand), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(z, zc, c_old[:, None], c_new[:, None])
    return out[0]


@functools.partial(jax.jit, static_argnames=("block_i", "block_j", "interpret"))
def fl_gains_gram_free_pallas(
    z: jax.Array,
    zc: jax.Array,
    c: jax.Array,
    *,
    block_i: int = 512,
    block_j: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Gram-free gains for all candidate rows of ``zc`` given max-cache ``c``.

    Args:
      z: (n, d) row-normalized ground features; zc: (n_cand, d); c: (n,).
      n % block_i == 0, n_cand % block_j == 0.
    """
    n, d = z.shape
    n_cand = zc.shape[0]
    bi = min(block_i, n)
    bj = min(block_j, n_cand)
    if n % bi or n_cand % bj:
        raise ValueError(f"shape ({n},{n_cand}) not divisible by ({bi},{bj})")
    grid = (n_cand // bj, n // bi)
    out = pl.pallas_call(
        _fl_gains_gram_free_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bi, d), lambda j, i: (i, 0)),
            pl.BlockSpec((bj, d), lambda j, i: (j, 0)),
            pl.BlockSpec((bi, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bj), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, n_cand), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(z, zc, c[:, None])
    return out[0]
