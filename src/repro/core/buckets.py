"""Level-0 partitions batched by pow2 bucket: one device program per chunk.

``MiloPreprocessor`` pads every partition to a power-of-two ground set and
budget (``next_pow2``), so partitions of one dataset fall into a handful of
``(n_pad, k_run)`` bucket groups.  On the plain route (single device, eager
gains, XLA set functions) the partitions of a group run together: a chunk of
the group puts its own rows on the device, gathers each member's rows with a
``[P, n_pad]`` index matrix, builds its stacked engine kernels, and runs the
SGE bank and the WRE importance pass of ``core.greedy`` under ``jax.vmap``,
so a chunk costs one greedy loop instead of ``P``.

Padding is exact, as on the per-partition route: each partition's kernel is
built at its true size and padded with zeros, so on the CPU the batched route
gives the per-partition route's artifacts bit for bit.  ``bucket_chunks``
splits a group so that a chunk's device footprint (its stacked kernels and
its rows' copies, ``chunk_bytes``) stays under a byte budget, a share of the
device's own memory limit (``chunk_byte_limit``).

The Gram program is compiled per chunk geometry, true sizes included
(``bucket_kernels``), since a kernel built at another size rounds
differently on the CPU; the engines are compiled per ``(P, n_pad, k_run,
s)`` alone.
"""
from __future__ import annotations

import functools
import itertools
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.greedy import stochastic_candidate_count
from repro.core.similarity import normalize_rows
from repro.core.submodular import SetFunction

#: share of the device's ``bytes_limit`` a chunk's footprint
#: (``chunk_bytes``) may take; the rest holds the engines' carries and
#: temporaries.  On a TPU v5e ten 8192^2 Grams (2.68 GB, one chunk) with the
#: feature matrix peaked at 2.85 GB, and the compiler's own analysis allows
#: their programs up to twice the Grams
CHUNK_SHARE = 0.25
#: byte budget where the backend reports no memory limit (the CPU)
FALLBACK_CHUNK_BYTES = 1 << 30


def next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def chunk_bytes(n_pad: int, d: int, gram_free: bool) -> int:
    """Device bytes one partition adds to a chunk: its padded float32 Gram
    (none gram-free) and four copies of its rows, as put, gathered,
    normalised, and transposed or padded."""
    return 4 * n_pad * ((0 if gram_free else n_pad) + 4 * d)


class BucketChunk(NamedTuple):
    """Partitions that run as one batched program."""

    n_pad: int                 # padded ground-set size of every member
    k_run: int                 # padded budget of every member
    s: int                     # SGE candidates drawn per step
    members: tuple[int, ...]   # the partitions' positions
    sizes: tuple[int, ...]     # their true sizes, ascending


def bucket_chunks(
    partitions: Sequence[tuple[int, int, int]],
    *,
    d: int,
    gram_free: bool,
    byte_limit: int,
    eps: float,
    exact_s: bool = False,
) -> list[BucketChunk]:
    """Group ``(position, n_c, k_sel)`` partitions by bucket, then chunk.

    A group is the partitions that share ``(n_pad, k_run)`` and the SGE
    candidate count ``s``, which follows from them unless ``exact_s`` draws
    it from the partition's true ``(n_c, k_sel)``.  Members are ordered by
    true size (stable), so a chunk's partitions of one size lie together.
    A group is split into as few chunks as keep each chunk's footprint
    (``chunk_bytes`` a partition) within ``byte_limit``, of sizes that
    differ by at most one; a partition larger than the limit runs alone.
    """
    groups: dict[tuple[int, int, int], list[tuple[int, int]]] = {}
    for pos, n_c, k_sel in partitions:
        n_pad = next_pow2(n_c)
        k_run = min(n_pad, next_pow2(k_sel))
        s = (stochastic_candidate_count(n_c, k_sel, eps) if exact_s
             else stochastic_candidate_count(n_pad, k_run, eps))
        groups.setdefault((n_pad, k_run, s), []).append((n_c, pos))
    chunks = []
    for (n_pad, k_run, s), members in groups.items():
        members.sort(key=lambda m: m[0])
        item = chunk_bytes(n_pad, d, gram_free)
        n_chunks = -(-len(members) // max(1, byte_limit // item))
        size, extra = divmod(len(members), n_chunks)
        lo = 0
        for c in range(n_chunks):
            hi = lo + size + (c < extra)
            chunks.append(BucketChunk(
                n_pad, k_run, s, tuple(pos for _, pos in members[lo:hi]),
                tuple(n_c for n_c, _ in members[lo:hi])))
            lo = hi
    return chunks


def chunk_byte_limit() -> int:
    """``CHUNK_SHARE`` of the default device's memory limit, or the
    fallback where the backend reports none."""
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return int(limit * CHUNK_SHARE) if limit else FALLBACK_CHUNK_BYTES


@functools.partial(jax.jit, static_argnames=("n",))
def sge_key_chain(key: jax.Array, n: int) -> jax.Array:
    """The SGE keys of ``n`` partitions in one call: ``keys[i]`` is the
    second half of the ``i``-th ``jax.random.split`` of the chain that
    starts at ``key``, the key the per-partition loop drew for partition
    ``i``."""

    def step(k, _):
        k, sub = jax.random.split(k)
        return k, sub

    return jax.lax.scan(step, key, None, length=n)[1]


def _cosine_gram(zn: jax.Array, block: int) -> jax.Array:
    """``gram_matrix_blocked``'s cosine tiles over ``[p, n, d]`` normalised
    rows.  The transposed operand is materialised as the eager path's
    transpose is: folded into the product, it takes another CPU matmul
    kernel for small ``n``, which rounds differently."""
    zt = jax.lax.optimization_barrier(jnp.swapaxes(zn, -1, -2))
    n = zn.shape[1]
    tiles = [zn[:, lo:lo + block] @ zt for lo in range(0, n, block)]
    return 0.5 + 0.5 * jnp.concatenate(tiles, axis=1)


@functools.partial(jax.jit, static_argnames=("sizes", "gram_free", "block"))
def bucket_kernels(
    x: jax.Array,
    rows: jax.Array,
    *,
    sizes: tuple[int, ...],
    gram_free: bool,
    block: int,
) -> tuple[jax.Array, jax.Array]:
    """Stacked engine kernels and ``valid`` masks of a chunk.

    ``x`` holds the chunk's own float32 feature rows, ``rows`` each
    member's ``[P, n_pad]`` row indices into ``x`` and ``sizes`` each
    member's true size, equal sizes adjacent.  The program is compiled per
    ``(x.shape, P, n_pad, sizes)``.  Each run of one size builds its kernels at that size,
    as the per-partition route does (a matmul padded to another size rounds
    differently on the CPU), then pads them with zeros: rescaled-cosine
    Grams ``[P, n_pad, n_pad]`` in ``block``-row tiles, or gram-free the
    normalised rows ``[P, n_pad, d]``.
    """
    n_pad = rows.shape[1]
    out, lo = [], 0
    for n_c, run in itertools.groupby(sizes):
        hi = lo + len(list(run))
        zn = normalize_rows(x[rows[lo:hi, :n_c]])
        if gram_free:
            kern = jnp.pad(zn, ((0, 0), (0, n_pad - n_c), (0, 0)))
        else:
            kern = jnp.pad(_cosine_gram(zn, block),
                           ((0, 0), (0, n_pad - n_c), (0, n_pad - n_c)))
        out.append(kern)
        lo = hi
    valid = np.arange(n_pad)[None, :] < np.asarray(sizes)[:, None]
    return (out[0] if len(out) == 1 else jnp.concatenate(out)), jnp.asarray(valid)


def _batched(f, *args):
    """``jax.vmap(f)(*args)``, and a batch of one as ``f`` alone: XLA drops
    a vmapped matvec's unit batch dimension into a CPU kernel that rounds
    differently from the unbatched program's."""
    if args[0].shape[0] == 1:
        return jax.tree.map(lambda a: a[None], f(*(a[0] for a in args)))
    return jax.vmap(f)(*args)


@functools.partial(jax.jit, static_argnames=("engine", "fn", "k", "s",
                                             "n_subsets", "vmapped"))
def bucket_sge(
    engine: Callable,
    fn: SetFunction,
    kernels: jax.Array,
    valid: jax.Array,
    keys: jax.Array,
    members: jax.Array,
    *,
    k: int,
    s: int,
    n_subsets: int,
    vmapped: bool,
) -> jax.Array:
    """``[P, n_subsets, k]`` SGE banks of ``engine`` (``greedy.sge``);
    ``keys[members]`` are the chunk's partitions' keys out of
    ``sge_key_chain``."""

    def one(kern, v, key):
        return engine(fn, kern, k, key, n_subsets=n_subsets,
                      vmapped=vmapped, valid=v, s=s)

    return _batched(one, kernels, valid, keys[members])


@functools.partial(jax.jit, static_argnames=("engine", "fn"))
def bucket_importance(
    engine: Callable, fn: SetFunction, kernels: jax.Array, valid: jax.Array
) -> jax.Array:
    """``[P, n_pad]`` WRE importances of ``engine``
    (``greedy.greedy_importance``), 0 at padded rows."""
    return _batched(lambda kern, v: engine(fn, kern, valid=v), kernels, valid)
