"""Gram-free set functions: selection directly over features, no (n×n) Gram.

The classwise Gram matrix is MILO preprocessing's memory wall: O(n²) per
class caps the ground-set size long before compute does.  Every set function
in ``core.submodular`` only ever touches the kernel through three access
patterns — a column ``K[:, j]`` (update), a diagonal entry ``K_jj`` (gains),
and for graph-cut a one-time column sum — and under the paper's rescaled
cosine metric

    K_ij = 0.5 + 0.5 · <z_i, z_j>          (z row-normalized)

each of those is an O(n·d) feature contraction.  The factories below rebuild
all four paper set functions in that form: the ``K`` argument threaded
through the greedy engines is the row-normalized feature matrix ``z`` of
shape (n, d), and peak memory is O(n·d + n) instead of O(n²).

Facility location is the one function whose *gain evaluation* still reduces
over the whole ground set; its hot path is the fused Pallas kernel
``kernels.fl_gains.fl_gains_gram_free`` which computes similarity tiles on
the MXU in VMEM and never writes them back.

Padding contract (size bucketing): all-zero feature rows are treated as
padding — facility location pins their cover to +inf at init so they
contribute nothing, and the greedy engines' ``valid`` mask keeps them from
ever being selected.  (A genuinely all-zero embedding is degenerate under
cosine similarity to begin with.)  Because "all-zero" is a *sentinel* here,
a genuinely zero-norm data row reaching this layer is silently treated as
padding — screen real inputs upstream with
``repro.health.validate_features`` (which flags zero-norm rows via
``similarity.zero_norm_rows``) rather than relaxing this contract.

Numerics: trajectories match the Gram-materializing path exactly on the
facility-location column reductions (same values, same reduction order); the
graph-cut column sum is computed in closed form (0.5·n + 0.5·z·Σz) so its
float rounding can differ from a materialized row sum by ~1 ulp — tests
assert trajectory equality on fixtures and allclose on gains.

Every factory is memoized on its (hashable) params: the greedy engines jit
with the ``SetFunction`` as a static argument, and a frozen dataclass of
closures hashes by closure identity — rebuilding the function each
``preprocess()`` call would therefore recompile every engine every session.
Returning the same object for the same params keeps those jit caches (and
``core.sharded._compiled``'s lru cache) warm across calls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.submodular import LazyHooks, SetFunction, State, _DMIN_CAP


def _sim_col(z: jax.Array, j: jax.Array) -> jax.Array:
    """Similarity column K[:, j] computed on the fly: O(n·d)."""
    return 0.5 + 0.5 * (z @ z[j])


def _sim_at(z: jax.Array, cand: jax.Array) -> jax.Array:
    """Candidate similarity block K[:, cand]: (n, s) in O(n·d·s)."""
    return 0.5 + 0.5 * (z @ z[cand].T)


def _row_sumsq(z: jax.Array) -> jax.Array:
    return jnp.sum(z * z, axis=-1)


def _sim_matrix(z: jax.Array) -> jax.Array:
    """Full Gram (tests/``evaluate`` only — never on the selection hot path).

    Rows/cols of padding (all-zero) features are zeroed to match the
    zero-padded materialized Gram the bucketed gram path uses.
    """
    live = _row_sumsq(z) > 0.0
    sim = 0.5 + 0.5 * (z @ z.T)
    return jnp.where(live[:, None] & live[None, :], sim, 0.0)


# ---------------------------------------------------------------------------
# Facility location:  state c[i] = max_{j in S} K_ij  (+inf on padding rows)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def make_gram_free_facility_location(
    *,
    use_pallas: bool = False,
    interpret: bool | None = None,
    block_i: int = 512,
    block_j: int = 512,
) -> SetFunction:
    """Facility location over features; Pallas-fused gains when requested."""
    from repro.kernels.fl_gains import ops as fl_ops

    def init(z: jax.Array) -> State:
        c0 = jnp.zeros((z.shape[0],), jnp.float32)
        return jnp.where(_row_sumsq(z) > 0.0, c0, jnp.inf)

    def gains(c: State, z: jax.Array) -> jax.Array:
        return fl_ops.fl_gains_gram_free(
            z, z, c, block_i=block_i, block_j=block_j,
            use_pallas=use_pallas, interpret=interpret,
        )

    def gains_at(c: State, z: jax.Array, cand: jax.Array) -> jax.Array:
        return fl_ops.fl_gains_gram_free(
            z, z[cand], c, block_i=block_i, block_j=block_j,
            use_pallas=use_pallas, interpret=interpret,
        )

    def update(c: State, z: jax.Array, j: jax.Array) -> State:
        return jnp.maximum(c, _sim_col(z, j))

    def evaluate(mask: jax.Array, z: jax.Array) -> jax.Array:
        K = _sim_matrix(z)
        sel = jnp.where(mask[None, :], K, -jnp.inf)
        best = jnp.max(sel, axis=1)
        return jnp.sum(jnp.where(jnp.any(mask), best, 0.0))

    def delta_gains(z: jax.Array, rows: jax.Array, c_old: jax.Array,
                    c_new: jax.Array) -> jax.Array:
        return fl_ops.fl_gains_gram_free_delta(
            z[rows], z, c_old, c_new, block_i=block_i, block_j=block_j,
            use_pallas=use_pallas, interpret=interpret,
        )

    name = "gram_free_facility_location" + ("_pallas" if use_pallas else "")
    return SetFunction(name, init, gains, update, evaluate, gains_at=gains_at,
                       lazy=LazyHooks(cover=lambda c: c, delta_gains=delta_gains))


# ---------------------------------------------------------------------------
# Graph cut: colsum in closed form, cur accumulated column-wise as usual
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def make_gram_free_graph_cut(lam: float = 0.4) -> SetFunction:
    def init(z: jax.Array) -> State:
        sumsq = _row_sumsq(z)
        live = sumsq > 0.0
        n_live = jnp.sum(live.astype(jnp.float32))
        # Σ_i K_ij = 0.5·n_live + 0.5·<z_j, Σ_i z_i>  (padding rows are zero
        # vectors so they drop out of both terms)
        colsum = 0.5 * n_live + 0.5 * (z @ jnp.sum(z, axis=0))
        colsum = jnp.where(live, colsum, 0.0)
        # K_jj from the same normalized features the gram path would square
        diag = jnp.where(live, 0.5 + 0.5 * sumsq, 0.0)
        return {
            "colsum": colsum,
            "diag": diag,
            "cur": jnp.zeros((z.shape[0],), jnp.float32),
        }

    def gains(state: State, z: jax.Array) -> jax.Array:
        return state["colsum"] - lam * (2.0 * state["cur"] + state["diag"])

    def gains_at(state: State, z: jax.Array, cand: jax.Array) -> jax.Array:
        return state["colsum"][cand] - lam * (
            2.0 * state["cur"][cand] + state["diag"][cand]
        )

    def update(state: State, z: jax.Array, j: jax.Array) -> State:
        return {
            "colsum": state["colsum"],
            "diag": state["diag"],
            "cur": state["cur"] + _sim_col(z, j),
        }

    def evaluate(mask: jax.Array, z: jax.Array) -> jax.Array:
        K = _sim_matrix(z)
        m = mask.astype(K.dtype)
        return jnp.sum(K @ m) - lam * (m @ K @ m)

    return SetFunction("gram_free_graph_cut", init, gains, update, evaluate,
                       gains_at=gains_at)


# ---------------------------------------------------------------------------
# Disparity-sum / disparity-min: state-only gains, O(n·d) column updates
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def make_gram_free_disparity_sum() -> SetFunction:
    def init(z: jax.Array) -> State:
        return jnp.zeros((z.shape[0],), jnp.float32)

    def gains(cur: State, z: jax.Array) -> jax.Array:
        return 2.0 * cur

    def gains_at(cur: State, z: jax.Array, cand: jax.Array) -> jax.Array:
        return 2.0 * cur[cand]

    def update(cur: State, z: jax.Array, j: jax.Array) -> State:
        return cur + (1.0 - _sim_col(z, j))

    def evaluate(mask: jax.Array, z: jax.Array) -> jax.Array:
        K = _sim_matrix(z)
        m = mask.astype(K.dtype)
        return m @ (1.0 - K) @ m - jnp.sum(m * (1.0 - jnp.diagonal(K)))

    return SetFunction("gram_free_disparity_sum", init, gains, update, evaluate,
                       gains_at=gains_at)


@functools.lru_cache(maxsize=64)
def make_gram_free_disparity_min() -> SetFunction:
    def init(z: jax.Array) -> State:
        n = z.shape[0]
        return {
            "dmin": jnp.full((n,), _DMIN_CAP, jnp.float32),
            "cur": jnp.asarray(_DMIN_CAP, jnp.float32),
            "size": jnp.asarray(0, jnp.int32),
        }

    def gains(state: State, z: jax.Array) -> jax.Array:
        return jnp.minimum(state["cur"], state["dmin"]) - state["cur"]

    def gains_at(state: State, z: jax.Array, cand: jax.Array) -> jax.Array:
        return jnp.minimum(state["cur"], state["dmin"][cand]) - state["cur"]

    def update(state: State, z: jax.Array, j: jax.Array) -> State:
        dist_j = 1.0 - _sim_col(z, j)
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

    def evaluate(mask: jax.Array, z: jax.Array) -> jax.Array:
        K = _sim_matrix(z)
        n = K.shape[0]
        d = 1.0 - K
        pair = mask[:, None] & mask[None, :] & ~jnp.eye(n, dtype=bool)
        return jnp.min(jnp.where(pair, d, _DMIN_CAP))

    return SetFunction("gram_free_disparity_min", init, gains, update, evaluate,
                       gains_at=gains_at)


# ---------------------------------------------------------------------------
# Query-conditioned facility location (targeted / SMI-style selection)
# ---------------------------------------------------------------------------

# manual memo (lru_cache can't key on arrays): (shape, dtype, bytes) -> fn.
# Bounded: targeted sessions reuse a handful of query banks, not thousands.
_QUERY_FL_CACHE: dict = {}
_QUERY_FL_CACHE_MAX = 16


def make_query_facility_location(z_query) -> SetFunction:
    """Facility location over a *query* set instead of the ground set.

    SMI-style targeted selection: f(S) = Σ_q max_{a in S} sim(a, q), so the
    per-element gain is Σ_q relu(sim(a, q) − cover_q) — the state is the
    per-query cover (q,), not the per-ground-row cover (n,).  ``z_query``
    must be row-normalized (same contract as the ground features); it is
    closed over as a jit constant, which is fine at the intended scale
    (queries are a handful of exemplars, the ground set is the big side).

    Padding ground rows (all-zero) get similarity exactly 0.5 to every
    query, which could look like positive gain at init — so gains are
    computed against a cover initialized at 0.5, making padding rows' gains
    exactly 0 (and the greedy engines' ``valid`` mask excludes them anyway).
    """
    import numpy as np

    zq = np.ascontiguousarray(np.asarray(z_query, np.float32))
    key = (zq.shape, zq.tobytes())
    hit = _QUERY_FL_CACHE.get(key)
    if hit is not None:
        return hit

    zq_j = jnp.asarray(zq)

    def init(z: jax.Array) -> State:
        # cover starts at 0.5 == sim(zero-row, q): padding contributes 0 gain
        return jnp.full((zq_j.shape[0],), 0.5, jnp.float32)

    def _sim_q(z: jax.Array) -> jax.Array:
        return 0.5 + 0.5 * (z @ zq_j.T)  # (n, q)

    def gains(c: State, z: jax.Array) -> jax.Array:
        return jnp.sum(jnp.maximum(_sim_q(z) - c[None, :], 0.0), axis=1)

    def gains_at(c: State, z: jax.Array, cand: jax.Array) -> jax.Array:
        return gains(c, z[cand])

    def update(c: State, z: jax.Array, j: jax.Array) -> State:
        return jnp.maximum(c, 0.5 + 0.5 * (zq_j @ z[j]))

    def evaluate(mask: jax.Array, z: jax.Array) -> jax.Array:
        sim = jnp.where(mask[:, None], _sim_q(z), -jnp.inf)  # (n, q)
        best = jnp.max(sim, axis=0)
        return jnp.sum(jnp.where(jnp.any(mask), best, 0.0))

    fn = SetFunction("query_facility_location", init, gains, update, evaluate,
                     gains_at=gains_at)
    if len(_QUERY_FL_CACHE) >= _QUERY_FL_CACHE_MAX:
        _QUERY_FL_CACHE.pop(next(iter(_QUERY_FL_CACHE)))
    _QUERY_FL_CACHE[key] = fn
    return fn


def get_gram_free(name: str, **kwargs) -> SetFunction:
    """Gram-free counterpart of ``submodular.get`` (cosine metric only)."""
    factories = {
        "facility_location": make_gram_free_facility_location,
        "graph_cut": make_gram_free_graph_cut,
        "disparity_sum": make_gram_free_disparity_sum,
        "disparity_min": make_gram_free_disparity_min,
    }
    try:
        return factories[name](**kwargs)
    except KeyError:
        raise KeyError(
            f"no gram-free variant of {name!r}; available: {sorted(factories)}"
        ) from None
