"""Set functions from the paper (App. D), in incremental-gain form.

Each set function is expressed as pure functions over a fixed similarity
matrix ``K`` (shape ``(n, n)``, values in [0, 1]):

    init(K)                  -> state                   (pytree of arrays)
    gains(state, K)          -> (n,) marginal gains f(S u j) - f(S) for every j
    gains_at(state, K, cand) -> (s,) marginal gains for candidate indices only
    update(state, K, j)      -> state after adding j to S

This formulation turns greedy maximization into a jit-compiled
``lax.fori_loop`` with *vectorized* gain evaluation — the TPU-native
replacement for submodlib's per-element CPU heaps (see DESIGN.md §2).

``gains_at`` is the stochastic-greedy hot path: a step that samples ``s``
candidates only ever needs those ``s`` gains, so evaluating them directly
(a column gather for facility location, a state gather for the others) is
O(n·s) or O(s) instead of the O(n²) full-vector evaluation.  It must satisfy
``gains_at(state, K, cand) == gains(state, K)[cand]`` elementwise; every
implementation below does so bit-exactly.

Functions:
  * facility_location  (representation, submodular monotone)
  * graph_cut          (representation, submodular monotone for lam <= 0.5)
  * disparity_sum      (diversity, non-submodular; greedy gives 1/4 approx)
  * disparity_min      (diversity, non-submodular; greedy gives 1/2 approx)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

State = Any

# Large-but-finite stand-in for +inf so disparity-min stays NaN-free.
_DMIN_CAP = 2.0


class LazyHooks(NamedTuple):
    """Capabilities the lazy-gain greedy engine needs (``greedy.lazy_greedy``).

    A set function whose full gain evaluation reduces over the ground-set
    axis (facility location) can expose these to let the engine *cache* the
    gain vector and correct it incrementally: after adding ``j``, only rows
    whose cover moved (``K_ij > c_i``) change any element's gain.

    ``cover(state) -> (n,)``: the running per-row cover vector ``c``.
    ``delta_gains(K, rows, c_old_rows, c_new_rows) -> (n,)``: the gain
    correction summed over just ``rows`` — for each candidate ``e``,
    ``sum_i relu(K_ie - c_new_i) - relu(K_ie - c_old_i)`` over the given
    rows.  Rows with an infinite cover in BOTH vectors contribute exact
    zeros, which is how the engine neutralizes budget-padding slots.
    """

    cover: Callable[[State], jax.Array]
    delta_gains: Callable[[jax.Array, jax.Array, jax.Array, jax.Array], jax.Array]


@dataclasses.dataclass(frozen=True)
class SetFunction:
    """Incremental set-function interface (see module docstring)."""

    name: str
    init: Callable[[jax.Array], State]
    gains: Callable[[State, jax.Array], jax.Array]
    update: Callable[[State, jax.Array, jax.Array], State]
    # Evaluate f(S) from scratch for a boolean mask — used by tests/property
    # checks, not by the greedy loop.
    evaluate: Callable[[jax.Array, jax.Array], jax.Array]
    # Candidate-gather gains (stochastic-greedy hot path).  None falls back
    # to gathering from the full gains vector — correct but O(n²) for
    # facility location, so every shipped set function provides one.
    gains_at: Callable[[State, jax.Array, jax.Array], jax.Array] | None = None
    # Lazy-gain hooks (exact-greedy hot path).  None means the function's
    # gains are cheap state lookups (graph-cut, disparity) or it simply
    # opts out; the engines fall back to per-step full evaluation.
    lazy: LazyHooks | None = None


def gains_at(fn: SetFunction, state: State, K: jax.Array, cand: jax.Array) -> jax.Array:
    """``fn.gains(state, K)[cand]`` without the full evaluation when possible."""
    if fn.gains_at is not None:
        return fn.gains_at(state, K, cand)
    return fn.gains(state, K)[cand]


# ---------------------------------------------------------------------------
# Facility location:  f(S) = sum_i max_{j in S} K_ij
# state: c[i] = max_{j in S} K_ij  (0 for empty S since K >= 0)
# gain(j) = sum_i relu(K_ij - c_i)
# ---------------------------------------------------------------------------

def _fl_init(K: jax.Array) -> State:
    return jnp.zeros((K.shape[0],), K.dtype)


def _fl_gains(c: State, K: jax.Array) -> jax.Array:
    return jnp.sum(jax.nn.relu(K - c[:, None]), axis=0)


def _fl_gains_at(c: State, K: jax.Array, cand: jax.Array) -> jax.Array:
    # Column gather: O(n·s) work instead of O(n²).  Same reduction over the
    # same column values as _fl_gains, so the result is bit-exact.
    return jnp.sum(jax.nn.relu(K[:, cand] - c[:, None]), axis=0)


def _fl_update(c: State, K: jax.Array, j: jax.Array) -> State:
    return jnp.maximum(c, K[:, j])


def _fl_eval(mask: jax.Array, K: jax.Array) -> jax.Array:
    sel = jnp.where(mask[None, :], K, -jnp.inf)
    best = jnp.max(sel, axis=1)
    return jnp.sum(jnp.where(jnp.any(mask), best, 0.0))


def _fl_delta_gains(
    K: jax.Array, rows: jax.Array, c_old: jax.Array, c_new: jax.Array
) -> jax.Array:
    # Row gather: only the (b, n) block of rows whose cover moved is read.
    Kb = K[rows, :].astype(jnp.float32)
    return jnp.sum(
        jax.nn.relu(Kb - c_new[:, None]) - jax.nn.relu(Kb - c_old[:, None]),
        axis=0,
    )


_FL_LAZY = LazyHooks(cover=lambda c: c, delta_gains=_fl_delta_gains)


facility_location = SetFunction(
    name="facility_location",
    init=_fl_init,
    gains=_fl_gains,
    update=_fl_update,
    evaluate=_fl_eval,
    gains_at=_fl_gains_at,
    lazy=_FL_LAZY,
)


# ---------------------------------------------------------------------------
# Graph cut: f(S) = sum_{i in D} sum_{j in S} K_ij - lam * sum_{i,j in S} K_ij
# state: (colsum (static), cur[j] = sum_{i in S} K_ij)
# gain(j) = colsum_j - lam * (2 cur_j + K_jj)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def make_graph_cut(lam: float = 0.4) -> SetFunction:
    def init(K: jax.Array) -> State:
        return {"colsum": jnp.sum(K, axis=0), "cur": jnp.zeros((K.shape[0],), K.dtype)}

    def gains(state: State, K: jax.Array) -> jax.Array:
        return state["colsum"] - lam * (2.0 * state["cur"] + jnp.diagonal(K))

    def gains_at(state: State, K: jax.Array, cand: jax.Array) -> jax.Array:
        # K[cand, cand] is the pointwise diagonal gather — O(s), not O(n).
        return state["colsum"][cand] - lam * (2.0 * state["cur"][cand] + K[cand, cand])

    def update(state: State, K: jax.Array, j: jax.Array) -> State:
        return {"colsum": state["colsum"], "cur": state["cur"] + K[:, j]}

    def evaluate(mask: jax.Array, K: jax.Array) -> jax.Array:
        m = mask.astype(K.dtype)
        return jnp.sum(K @ m) - lam * (m @ K @ m)

    return SetFunction("graph_cut", init, gains, update, evaluate, gains_at=gains_at)


graph_cut = make_graph_cut(0.4)


# ---------------------------------------------------------------------------
# Disparity-sum: f(S) = sum_{i,j in S} (1 - K_ij)
# state: cur[j] = sum_{i in S} (1 - K_ij);  gain(j) = 2 * cur_j  (diag is 0)
# ---------------------------------------------------------------------------

def _ds_init(K: jax.Array) -> State:
    return jnp.zeros((K.shape[0],), K.dtype)


def _ds_gains(cur: State, K: jax.Array) -> jax.Array:
    return 2.0 * cur


def _ds_gains_at(cur: State, K: jax.Array, cand: jax.Array) -> jax.Array:
    return 2.0 * cur[cand]


def _ds_update(cur: State, K: jax.Array, j: jax.Array) -> State:
    return cur + (1.0 - K[:, j])


def _ds_eval(mask: jax.Array, K: jax.Array) -> jax.Array:
    m = mask.astype(K.dtype)
    return m @ (1.0 - K) @ m - jnp.sum(m * (1.0 - jnp.diagonal(K)))


disparity_sum = SetFunction(
    "disparity_sum", _ds_init, _ds_gains, _ds_update, _ds_eval, gains_at=_ds_gains_at
)


# ---------------------------------------------------------------------------
# Disparity-min: f(S) = min_{i != j in S} (1 - K_ij)
# state: (dmin[j] = min_{i in S} (1 - K_ij), cur = f(S), size)
# Greedy argmax on gains == farthest-point traversal.
# ---------------------------------------------------------------------------

def _dm_init(K: jax.Array) -> State:
    n = K.shape[0]
    return {
        "dmin": jnp.full((n,), _DMIN_CAP, K.dtype),
        "cur": jnp.asarray(_DMIN_CAP, K.dtype),
        "size": jnp.asarray(0, jnp.int32),
    }


def _dm_gains(state: State, K: jax.Array) -> jax.Array:
    new_f = jnp.minimum(state["cur"], state["dmin"])
    return new_f - state["cur"]


def _dm_gains_at(state: State, K: jax.Array, cand: jax.Array) -> jax.Array:
    return jnp.minimum(state["cur"], state["dmin"][cand]) - state["cur"]


def _dm_update(state: State, K: jax.Array, j: jax.Array) -> State:
    dist_j = 1.0 - K[:, j]
    new_cur = jnp.where(state["size"] >= 1, jnp.minimum(state["cur"], state["dmin"][j]), state["cur"])
    dmin = jnp.minimum(state["dmin"], dist_j)
    return {"dmin": dmin, "cur": new_cur, "size": state["size"] + 1}


def _dm_eval(mask: jax.Array, K: jax.Array) -> jax.Array:
    n = K.shape[0]
    d = 1.0 - K
    pair = mask[:, None] & mask[None, :] & ~jnp.eye(n, dtype=bool)
    return jnp.min(jnp.where(pair, d, _DMIN_CAP))


disparity_min = SetFunction(
    "disparity_min", _dm_init, _dm_gains, _dm_update, _dm_eval, gains_at=_dm_gains_at
)


@functools.lru_cache(maxsize=64)
def make_facility_location_pallas(*, interpret: bool | None = None,
                                  block_i: int = 512, block_j: int = 512) -> SetFunction:
    """Facility location with the Pallas ``fl_gains`` kernel as the gain
    engine (the O(n²)-per-step hot loop of greedy selection; DESIGN.md §6).

    TPU deployment path; on the CPU it runs interpreted (slow — tests
    use small n).  Semantics identical to ``facility_location``
    (tests/test_kernels.py proves greedy-trajectory equality).
    """
    from repro.kernels.fl_gains import ops as fl_ops

    def gains(c: State, K: jax.Array) -> jax.Array:
        return fl_ops.fl_gains(K, c, block_i=block_i, block_j=block_j,
                               interpret=interpret)

    def gains_at(c: State, K: jax.Array, cand: jax.Array) -> jax.Array:
        # gather the s candidate columns, then run the kernel on (n, s)
        return fl_ops.fl_gains(K[:, cand], c, block_i=block_i, block_j=block_j,
                               interpret=interpret)

    return SetFunction("facility_location_pallas", _fl_init, gains, _fl_update,
                       _fl_eval, gains_at=gains_at, lazy=_FL_LAZY)


REGISTRY = {
    "facility_location": facility_location,
    "graph_cut": graph_cut,
    "disparity_sum": disparity_sum,
    "disparity_min": disparity_min,
}


def get(name: str, **kwargs) -> SetFunction:
    if name == "graph_cut" and kwargs:
        return make_graph_cut(**kwargs)
    return REGISTRY[name]
