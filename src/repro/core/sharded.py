"""Multi-device sharded selection: gram-free engines over a row-sharded mesh.

The gram-free path (``core.gram_free``) already cut per-class selection
memory from O(n²) to O(n·d + n); this module removes the remaining wall —
one device's memory capping ``n·d`` — by sharding the *row axis* of the
feature matrix ``z`` across a 1-D device mesh
(``distributed.sharding.selection_mesh``) and running the unchanged greedy
engines inside ``shard_map``:

  * ``z`` is sharded ``P("sel", None)``: each device holds ``n/ndev`` rows.
    This is the only O(n·d) object anywhere.
  * Every per-element vector the engines thread — the ``selected`` mask, FL's
    cover ``c``, graph-cut's ``colsum``/``cur``, disparity state — is O(n)
    and stays **replicated**, so the engines' argmax/top-k/scatter logic is
    untouched: each device computes the identical pick from identical
    replicated inputs.
  * Similarity columns ``K[:, j]`` are assembled exactly: the owner shard
    contributes ``z_j`` through a one-hot ``psum`` (all other shards add
    zeros — bit-exact), each shard contracts its own rows, and an ordered
    ``all_gather`` concatenates the chunks.  No cross-shard arithmetic
    touches these values, so graph-cut/disparity trajectories AND gains are
    bit-identical to the single-device run.
  * Facility-location full gains reduce over the ground-set axis: each shard
    accumulates partial gains with the same ``fl_gains_gram_free`` kernel the
    single-device path uses (the kernel's i-axis loop is already shard
    shaped), visiting candidate blocks via a ring ``ppermute`` so full ``z``
    is never materialized, then combines with ``psum``.  The first block of
    the ring is the shard's own ``z_local`` (no rotation needed), so a full
    gains evaluation issues exactly ``n_shards - 1`` hops — statically
    countable in the jaxpr because the schedule is unrolled over the (static)
    shard count.  The cross-shard sum reassociates float additions, so
    FL/graph-cut *gain values* can differ from the single-device path by
    ~1 ulp; selected trajectories are bit-identical on all tested fixtures
    (argmax gaps are many orders above ulp noise).
  * Facility location also exposes the ``SetFunction.lazy`` hooks, so
    ``greedy.lazy_greedy`` runs unchanged inside ``shard_map``: the cover and
    the cached gain vector are replicated, and the delta correction takes a
    *ring-free* candidate path — the touched rows are gathered exactly via
    the one-owner ``psum`` gather (a ``budget × d`` block, small by
    construction), each shard contracts them against its OWN candidate block
    through ``fl_gains_gram_free_delta``, and an ordered ``all_gather``
    concatenates the per-shard corrections.  The delta values are bit-exact
    against the single-device delta (same per-candidate reduction order);
    only the cached base gains carry the ring ``psum``'s ~1 ulp.

``sharded_greedy`` / ``sharded_lazy_greedy`` / ``sharded_stochastic_greedy``
/ ``sharded_sge`` / ``sharded_greedy_importance`` wrap the engines; they
require ``n % ndev == 0`` (the preprocessor's power-of-two buckets satisfy
this for any pow2 mesh) and fall back is the caller's choice —
``MiloPreprocessor`` runs non-divisible (tiny) classes on the single-device
path, which is trajectory-identical anyway.

The ``make_sharded_*`` factories are memoized on their (hashable) params:
two ``preprocess()`` calls with the same knobs receive the *same*
``SetFunction`` object, so ``_compiled``'s lru cache and the engines' jit
static-arg caches hit instead of recompiling every session.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.gram_free import (
    make_gram_free_disparity_min,
    make_gram_free_disparity_sum,
    make_gram_free_facility_location,
    make_gram_free_graph_cut,
)
from repro.core.greedy import (
    GreedyResult,
    LazyGreedyResult,
    _sge_bank,
    greedy,
    greedy_importance,
    lazy_greedy,
    refine,
    stochastic_candidate_count,
    stochastic_greedy,
)
from repro.core.submodular import LazyHooks, SetFunction, State
from repro.distributed import compression as comp_mod
from repro.distributed import multihost
from repro.distributed.compression import CompressionIntegrityError
from repro.distributed.sharding import SELECTION_AXIS as AXIS


# ---------------------------------------------------------------------------
# exact cross-shard primitives (no float reassociation)
# ---------------------------------------------------------------------------

def _my_offset(z_local: jax.Array, axis: str) -> jax.Array:
    return jax.lax.axis_index(axis) * z_local.shape[0]


def _gather_rows(z_local: jax.Array, idx: jax.Array, axis: str) -> jax.Array:
    """Replicated ``z[idx]`` from the row-sharded ``z``: the owning shard
    contributes the row, every other shard contributes exact zeros, so the
    ``psum`` is a bit-exact gather (one non-zero term per index)."""
    chunk = z_local.shape[0]
    off = _my_offset(z_local, axis)
    local = (idx >= off) & (idx < off + chunk)
    rows = jnp.take(z_local, jnp.clip(idx - off, 0, chunk - 1), axis=0)
    return jax.lax.psum(
        jnp.where(local[:, None], rows.astype(jnp.float32), 0.0), axis
    )


def _sim_col(z_local: jax.Array, j: jax.Array, axis: str) -> jax.Array:
    """Replicated rescaled-cosine column ``K[:, j]``: per-row dot products are
    computed on the owning shard (same d-axis reduction as the single-device
    matvec — bit-exact) and concatenated in shard order by ``all_gather``."""
    zj = _gather_rows(z_local, j[None], axis)[0]
    return jax.lax.all_gather(0.5 + 0.5 * (z_local @ zj), axis, tiled=True)


def _all_row_sumsq(z_local: jax.Array, axis: str) -> jax.Array:
    return jax.lax.all_gather(jnp.sum(z_local * z_local, axis=-1), axis,
                              tiled=True)


def _slice_mine(vec: jax.Array, z_local: jax.Array, axis: str) -> jax.Array:
    """This shard's chunk of a replicated per-row vector."""
    return jax.lax.dynamic_slice_in_dim(
        vec, _my_offset(z_local, axis), z_local.shape[0]
    )


def _compressed_psum(x: jax.Array, axis: str, *, rounds: int) -> jax.Array:
    """Error-feedback compressed cross-shard sum with integrity checksums.

    Each round every shard int8-quantizes its residual (round 0: its full
    partial), all-gathers the checksummed payloads, verifies every peer's
    checksum post-collective, and accumulates the decoded sum; the local
    quantization error feeds the next round.  ``rounds`` trades payload for
    fidelity — one round moves n bytes/shard instead of the exact psum's 4n,
    and the residual shrinks geometrically with each extra round.

    A checksum mismatch — a corrupted collective — NaN-poisons the entire
    output in-trace; the wrapper-level host check then raises
    ``CompressionIntegrityError`` instead of letting a silently-skewed gain
    pick subsets.  The escape hatch is not calling this at all
    (``compress=None``), which keeps the exact ``psum`` path bit-identical.
    """
    total = jnp.zeros_like(x, jnp.float32)
    resid = x.astype(jnp.float32)
    for _ in range(rounds):
        p = comp_mod.int8_compress_checked(resid)
        qs = jax.lax.all_gather(p.q, axis)            # (n_shards, n)
        scales = jax.lax.all_gather(p.scale, axis)    # (n_shards,)
        sums = jax.lax.all_gather(p.checksum, axis)   # (n_shards,)
        ok = jnp.all(jax.vmap(comp_mod.payload_checksum)(qs) == sums)
        decoded = jnp.sum(qs.astype(jnp.float32) * scales[:, None], axis=0)
        total = total + jnp.where(ok, decoded, jnp.nan)
        resid = resid - comp_mod.int8_decompress(
            comp_mod.Int8Compressed(p.q, p.scale))
    return total


def _raise_if_corrupt(fn: SetFunction, gains_arr: jax.Array) -> None:
    """Loud failure for the compressed path: a checksum mismatch inside the
    collective NaN-poisons the traced gains; surface it as an exception the
    moment the result reaches the host (the arrays are replicated outputs,
    so this reads no extra device memory)."""
    if "_c8" not in fn.name:
        return
    if np.isnan(np.asarray(gains_arr)).any():
        raise CompressionIntegrityError(
            f"{fn.name}: NaN in selection gains — a compressed cross-shard "
            "collective failed its payload checksum (corrupted transfer); "
            "rerun, or disable compression (compress=None) to use the "
            "exact psum path"
        )


def _place_global(mesh: Mesh, axis: str, z, valid, key=None):
    """Lay inputs out on the mesh when it spans processes.

    Single-process meshes take the unchanged direct-call path (byte-identical
    dispatch to the pre-multihost code); multi-process meshes need inputs
    committed to the global sharding before the jitted shard_map program can
    accept them — each host fills its addressable shards from its own full
    host copy, so placement moves no bytes between hosts.
    """
    if not multihost.mesh_spans_processes(mesh):
        return (z, valid) if key is None else (z, valid, key)
    zg = multihost.global_put(jnp.asarray(z), mesh, P(axis, None))
    vg = multihost.global_put(jnp.asarray(valid), mesh, P(None))
    if key is None:
        return zg, vg
    return zg, vg, multihost.global_put(jnp.asarray(key), mesh, P(None))


def _gathered_z_evaluate(base_evaluate):
    """Tests-only ``evaluate``: rebuild full z (all_gather) and delegate."""

    def evaluate(mask: jax.Array, z_local: jax.Array, *, _axis=AXIS) -> jax.Array:
        z = jax.lax.all_gather(z_local, _axis, tiled=True)
        return base_evaluate(mask, z)

    return evaluate


# ---------------------------------------------------------------------------
# sharded set functions (the engines' "K" argument is the per-device z shard)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def make_sharded_facility_location(
    *,
    n_shards: int,
    axis: str = AXIS,
    use_pallas: bool = False,
    interpret: bool | None = None,
    block_i: int = 512,
    block_j: int = 512,
    compress: str | None = None,
    compress_rounds: int = 2,
) -> SetFunction:
    """Facility location with the cover vector replicated and all gain
    reductions computed per shard through ``fl_gains_gram_free``; exposes
    ``lazy`` hooks so ``lazy_greedy`` composes with the mesh.

    ``compress="int8"`` routes the full-gains ring's O(n) cross-shard
    reduction through ``_compressed_psum`` — error-feedback int8 payloads
    with integrity checksums, ``compress_rounds`` controlling the
    payload/fidelity trade — for meshes whose shards sit across a slow
    inter-host link.  The exact one-owner gathers (``gains_at``, ``update``,
    lazy deltas) are never compressed: they are the bit-exactness-critical
    small payloads.  ``compress=None`` (default) is the escape hatch: the
    exact ``psum`` code path, bit-identical to every prior release."""
    from repro.kernels.fl_gains import ops as fl_ops

    base = make_gram_free_facility_location(
        use_pallas=use_pallas, interpret=interpret,
        block_i=block_i, block_j=block_j,
    )

    def _kernel(z_local, zc, c_loc):
        return fl_ops.fl_gains_gram_free(
            z_local, zc, c_loc, block_i=block_i, block_j=block_j,
            use_pallas=use_pallas, interpret=interpret,
        )

    def init(z_local: jax.Array) -> State:
        ssq = _all_row_sumsq(z_local, axis)
        return jnp.where(ssq > 0.0, 0.0, jnp.inf).astype(jnp.float32)

    def gains(c: State, z_local: jax.Array) -> jax.Array:
        # Ring schedule: candidate blocks visit every shard via ppermute, so
        # each shard accumulates its i-axis partial for ALL n candidates
        # while holding at most two (n/ndev, d) blocks; psum combines the
        # partials.  The t = 0 block is the shard's own z_local, so the
        # schedule needs exactly n_shards - 1 hops; unrolling over the
        # static shard count keeps that hop count a static property of the
        # program (one ppermute eqn per hop in the jaxpr) instead of hiding
        # an extra, discarded rotation inside a fori_loop.
        chunk = z_local.shape[0]
        me = jax.lax.axis_index(axis)
        c_loc = _slice_mine(c, z_local, axis)
        perm = [(i, (i - 1) % n_shards) for i in range(n_shards)]

        blk = z_local
        out = jnp.zeros((n_shards * chunk,), jnp.float32)
        for t in range(n_shards):
            if t:
                blk = jax.lax.ppermute(blk, axis, perm)
            out = jax.lax.dynamic_update_slice(
                out, _kernel(z_local, blk, c_loc),
                (((me + t) % n_shards) * chunk,),
            )
        if compress == "int8":
            return _compressed_psum(out, axis, rounds=compress_rounds)
        return jax.lax.psum(out, axis)

    def gains_at(c: State, z_local: jax.Array, cand: jax.Array) -> jax.Array:
        zc = _gather_rows(z_local, cand, axis)
        c_loc = _slice_mine(c, z_local, axis)
        return jax.lax.psum(_kernel(z_local, zc, c_loc), axis)

    def update(c: State, z_local: jax.Array, j: jax.Array) -> State:
        return jnp.maximum(c, _sim_col(z_local, j, axis))

    def delta_gains(z_local: jax.Array, rows: jax.Array, c_old: jax.Array,
                    c_new: jax.Array) -> jax.Array:
        # Ring-free candidate path: the touched rows (budget × d, small by
        # construction) are gathered exactly via the one-owner psum, each
        # shard corrects its OWN candidate block, and the ordered all_gather
        # concatenates — per-candidate reduction order matches the
        # single-device delta, so the correction itself is bit-exact.
        zr = _gather_rows(z_local, rows, axis)
        d_loc = fl_ops.fl_gains_gram_free_delta(
            zr, z_local, c_old, c_new, block_i=block_i, block_j=block_j,
            use_pallas=use_pallas, interpret=interpret,
        )
        return jax.lax.all_gather(d_loc, axis, tiled=True)

    name = "sharded_facility_location" + ("_pallas" if use_pallas else "")
    if compress == "int8":
        name += f"_c8r{compress_rounds}"
    elif compress is not None:
        raise ValueError(f"unknown compression scheme {compress!r}; "
                         "one of ('int8', None)")
    return SetFunction(name, init, gains, update,
                       _gathered_z_evaluate(base.evaluate), gains_at=gains_at,
                       lazy=LazyHooks(cover=lambda c: c,
                                      delta_gains=delta_gains))


@functools.lru_cache(maxsize=64)
def make_sharded_graph_cut(lam: float = 0.4, *, n_shards: int,
                           axis: str = AXIS) -> SetFunction:
    base = make_gram_free_graph_cut(lam)

    def init(z_local: jax.Array) -> State:
        ssq = _all_row_sumsq(z_local, axis)
        live = ssq > 0.0
        n_live = jnp.sum(live.astype(jnp.float32))
        # Σ_i z_i reduces over the sharded row axis; the psum reassociates the
        # float sum, so colsum (hence gains) can differ from the single-device
        # init by ~1 ulp — trajectories are unaffected on tested fixtures.
        zsum = jax.lax.psum(jnp.sum(z_local, axis=0), axis)
        colsum_loc = 0.5 * n_live + 0.5 * (z_local @ zsum)
        colsum = jax.lax.all_gather(colsum_loc, axis, tiled=True)
        return {
            "colsum": jnp.where(live, colsum, 0.0),
            "diag": jnp.where(live, 0.5 + 0.5 * ssq, 0.0),
            "cur": jnp.zeros((ssq.shape[0],), jnp.float32),
        }

    def update(state: State, z_local: jax.Array, j: jax.Array) -> State:
        return {
            "colsum": state["colsum"],
            "diag": state["diag"],
            "cur": state["cur"] + _sim_col(z_local, j, axis),
        }

    # gains/gains_at read replicated state only — reuse the gram-free closures
    return SetFunction("sharded_graph_cut", init, base.gains, update,
                       _gathered_z_evaluate(base.evaluate),
                       gains_at=base.gains_at)


@functools.lru_cache(maxsize=64)
def make_sharded_disparity_sum(*, n_shards: int, axis: str = AXIS) -> SetFunction:
    base = make_gram_free_disparity_sum()

    def init(z_local: jax.Array) -> State:
        return jnp.zeros((n_shards * z_local.shape[0],), jnp.float32)

    def update(cur: State, z_local: jax.Array, j: jax.Array) -> State:
        return cur + (1.0 - _sim_col(z_local, j, axis))

    return SetFunction("sharded_disparity_sum", init, base.gains, update,
                       _gathered_z_evaluate(base.evaluate),
                       gains_at=base.gains_at)


@functools.lru_cache(maxsize=64)
def make_sharded_disparity_min(*, n_shards: int, axis: str = AXIS) -> SetFunction:
    from repro.core.submodular import _DMIN_CAP

    base = make_gram_free_disparity_min()

    def init(z_local: jax.Array) -> State:
        n = n_shards * z_local.shape[0]
        return {
            "dmin": jnp.full((n,), _DMIN_CAP, jnp.float32),
            "cur": jnp.asarray(_DMIN_CAP, jnp.float32),
            "size": jnp.asarray(0, jnp.int32),
        }

    def update(state: State, z_local: jax.Array, j: jax.Array) -> State:
        dist_j = 1.0 - _sim_col(z_local, j, axis)
        new_cur = jnp.where(
            state["size"] >= 1,
            jnp.minimum(state["cur"], state["dmin"][j]),
            state["cur"],
        )
        return {
            "dmin": jnp.minimum(state["dmin"], dist_j),
            "cur": new_cur,
            "size": state["size"] + 1,
        }

    return SetFunction("sharded_disparity_min", init, base.gains, update,
                       _gathered_z_evaluate(base.evaluate),
                       gains_at=base.gains_at)


def make_sharded_gram_free(name: str, *, n_shards: int, axis: str = AXIS,
                           **kwargs) -> SetFunction:
    """Sharded counterpart of ``gram_free.get_gram_free`` (cosine only)."""
    factories = {
        "facility_location": make_sharded_facility_location,
        "graph_cut": make_sharded_graph_cut,
        "disparity_sum": make_sharded_disparity_sum,
        "disparity_min": make_sharded_disparity_min,
    }
    try:
        factory = factories[name]
    except KeyError:
        raise KeyError(
            f"no sharded gram-free variant of {name!r}; "
            f"available: {sorted(factories)}"
        ) from None
    return factory(n_shards=n_shards, axis=axis, **kwargs)


# ---------------------------------------------------------------------------
# engine wrappers: the unchanged greedy engines inside shard_map
# ---------------------------------------------------------------------------

def _check_shardable(z: jax.Array, mesh: Mesh, axis: str) -> int:
    ndev = mesh.shape[axis]
    n = z.shape[0]
    if n % ndev:
        raise ValueError(
            f"ground-set size {n} is not divisible by the {ndev}-device "
            f"{axis!r} mesh; pad the problem (bucketed preprocessing does) "
            "or run the single-device path"
        )
    return n


@functools.lru_cache(maxsize=128)
def _compiled(kind: str, fn: SetFunction, mesh: Mesh, axis: str, n: int,
              *extra):
    """One jitted shard_map program per (engine, set fn, mesh, shapes).

    ``check_vma=False``: every per-element carry is replicated by
    construction (identical replicated inputs, deterministic ops), but the
    varying-axes checker cannot prove it through fori_loop + psum.
    """
    specs = dict(mesh=mesh, in_specs=(P(axis, None), P(None)),
                 out_specs=P(None), check_vma=False)

    if kind == "greedy":
        (k,) = extra

        def inner(zs, v):
            return greedy(fn, zs, k, valid=v, n=n)

    elif kind == "refine":
        k, lazy_budget, lazy_two_level = extra

        def inner(zs, v):
            return refine(fn, zs, k, valid=v, n=n, lazy_budget=lazy_budget,
                          two_level=lazy_two_level)

    elif kind == "lazy":
        k, budget, two_level = extra

        def inner(zs, v):
            return lazy_greedy(fn, zs, k, budget=budget, valid=v, n=n,
                               two_level=two_level)

    elif kind == "stochastic":
        k, s = extra

        def inner(zs, v, key):
            return stochastic_greedy(fn, zs, k, key, s=s,
                                                valid=v, n=n)

        specs["in_specs"] = (P(axis, None), P(None), P(None))
    elif kind == "bank":
        k, s, n_subsets = extra

        def inner(zs, v, key):
            return _sge_bank(fn, zs, k, key, s=s,
                                        n_subsets=n_subsets, valid=v, n=n)

        specs["in_specs"] = (P(axis, None), P(None), P(None))
    elif kind == "importance":
        lazy_budget, lazy_two_level = extra

        def inner(zs, v):
            return greedy_importance(fn, zs, valid=v, n=n,
                                     lazy_budget=lazy_budget,
                                     lazy_two_level=lazy_two_level)

    else:  # pragma: no cover
        raise ValueError(kind)
    return jax.jit(jax.shard_map(inner, **specs))


def _valid_or_all(n: int, valid: jax.Array | None) -> jax.Array:
    # an all-true mask is bit-equivalent to valid=None in every engine
    # (_selected0 yields the same all-false selected mask) and keeps the
    # shard_map input pytree static
    return jnp.ones((n,), bool) if valid is None else valid


def sharded_greedy(
    fn: SetFunction, z: jax.Array, k: int, *, mesh: Mesh, axis: str = AXIS,
    valid: jax.Array | None = None,
) -> GreedyResult:
    """``greedy`` with z row-sharded over ``mesh`` (trajectory-identical)."""
    n = _check_shardable(z, mesh, axis)
    run = _compiled("greedy", fn, mesh, axis, n, k)
    z, v = _place_global(mesh, axis, z, _valid_or_all(n, valid))
    res = GreedyResult(*run(z, v))
    _raise_if_corrupt(fn, res.gains)
    return res


def sharded_lazy_greedy(
    fn: SetFunction, z: jax.Array, k: int, *, budget: int, mesh: Mesh,
    axis: str = AXIS, valid: jax.Array | None = None,
    two_level: bool = False,
) -> LazyGreedyResult:
    """``lazy_greedy`` with z row-sharded over ``mesh``.

    The cached gain vector is replicated, so the engine's argmax/touched-row
    logic runs unchanged; only the gain *evaluations* are sharded — full
    recomputes via the (n_shards - 1)-hop ring, delta corrections via the
    ring-free gathered-rows path.  ``rows_evaluated`` is the same traced
    counter the single-device engine returns (``budget`` on a lazy step,
    ``n`` on a fallback recompute), counting *ground rows contracted* — the
    per-shard split of each contraction does not change what was evaluated.

    Trajectories match the single-device ``lazy_greedy`` wherever argmax gaps
    exceed the ring psum's ~1 ulp reassociation noise — on the test fixtures
    that is every step (indices bit-identical, gains ≤ 1 ulp).

    ``two_level=True`` right-sizes each lazy gather to the smallest pow2
    level covering the touched rows (bit-identical to single-level; see
    ``greedy.lazy_greedy``) — here that shrinks the one-owner psum payload
    of the gathered touched-row block from ``budget × d`` to ``level × d``
    on calm steps."""
    n = _check_shardable(z, mesh, axis)
    run = _compiled("lazy", fn, mesh, axis, n, k, budget, two_level)
    z, v = _place_global(mesh, axis, z, _valid_or_all(n, valid))
    res = LazyGreedyResult(*run(z, v))
    _raise_if_corrupt(fn, res.gains)
    return res


def sharded_refine(
    fn: SetFunction, z: jax.Array, k: int, *, mesh: Mesh, axis: str = AXIS,
    valid: jax.Array | None = None, lazy_budget: int | None = None,
    lazy_two_level: bool = False,
) -> GreedyResult:
    """``greedy.refine`` (the hierarchical level-1 pass) over row-sharded z.

    Same lazy dispatch rule as the single-device entry point: routes through
    ``lazy_greedy`` when a budget is given and the set function has lazy
    hooks, plain ``greedy`` otherwise.  The union of level-0 winners is small
    relative to the ground set, but on pow2-padded unions that divide the
    mesh this keeps even the refine's O(union²·d) FL gains off a single
    device."""
    n = _check_shardable(z, mesh, axis)
    if not (lazy_budget is not None and fn.lazy is not None
            and 1 <= lazy_budget < n):
        lazy_budget = None
    run = _compiled("refine", fn, mesh, axis, n, k, lazy_budget,
                    lazy_two_level)
    z, v = _place_global(mesh, axis, z, _valid_or_all(n, valid))
    res = GreedyResult(*run(z, v))
    _raise_if_corrupt(fn, res.gains)
    return res


def sharded_stochastic_greedy(
    fn: SetFunction, z: jax.Array, k: int, key: jax.Array, *, s: int,
    mesh: Mesh, axis: str = AXIS, valid: jax.Array | None = None,
) -> GreedyResult:
    """``stochastic_greedy`` over row-sharded z.  The Gumbel draws use the
    replicated key and global n, so candidate sets (hence trajectories) are
    bit-identical to the single-device run."""
    n = _check_shardable(z, mesh, axis)
    run = _compiled("stochastic", fn, mesh, axis, n, k, s)
    z, v, key = _place_global(mesh, axis, z, _valid_or_all(n, valid), key)
    res = GreedyResult(*run(z, v, key))
    _raise_if_corrupt(fn, res.gains)
    return res


def sharded_sge(
    fn: SetFunction, z: jax.Array, k: int, key: jax.Array, *,
    n_subsets: int, eps: float = 0.01, s: int | None = None,
    mesh: Mesh, axis: str = AXIS, valid: jax.Array | None = None,
) -> jax.Array:
    """The full SGE bank (vmapped) over row-sharded z: one shard_map program
    whose collectives batch across the vmapped runs."""
    n = _check_shardable(z, mesh, axis)
    if s is None:
        s = stochastic_candidate_count(n, k, eps)
    run = _compiled("bank", fn, mesh, axis, n, k, s, n_subsets)
    z, v, key = _place_global(mesh, axis, z, _valid_or_all(n, valid), key)
    return run(z, v, key)


def sharded_greedy_importance(
    fn: SetFunction, z: jax.Array, *, mesh: Mesh, axis: str = AXIS,
    valid: jax.Array | None = None, lazy_budget: int | None = None,
    lazy_two_level: bool = False,
) -> jax.Array:
    """``greedy_importance`` over row-sharded z.

    ``lazy_budget`` threads straight through to the engine: when the set
    function provides lazy hooks (sharded facility location does) the full
    pass runs ``lazy_greedy`` — cached gains corrected over touched rows
    only — instead of n ring-gain evaluations; ignored otherwise, exactly as
    on the single-device path.  ``lazy_two_level`` right-sizes each lazy
    gather's psum payload (bit-identical; see ``sharded_lazy_greedy``)."""
    n = _check_shardable(z, mesh, axis)
    run = _compiled("importance", fn, mesh, axis, n, lazy_budget,
                    lazy_two_level)
    z, v = _place_global(mesh, axis, z, _valid_or_all(n, valid))
    out = run(z, v)
    _raise_if_corrupt(fn, out)
    return out
