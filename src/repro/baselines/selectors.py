"""Subset-selection baselines from the paper's experiments (§4).

The classes here are the *legacy* entry points exposing the deprecated
``indices_for_epoch`` protocol; new code should build the same strategies
through the ``repro.selection`` registry (``build_selector("craig_pb", ...)``)
which wraps them in the weighted ``SelectionPlan`` protocol.  The actual
selection math lives in the module-level functions (``craig_pb_select``,
``gradmatch_omp_select``, ``glister_select``) shared by both paths.

Model-independent strategies (selection cost off the critical path):

  RandomSelector          — fixed random subset (paper: RANDOM)
  AdaptiveRandomSelector  — fresh random subset every R epochs (ADAPTIVE-RANDOM)
  MiloFixedSelector       — fixed subset maximizing disparity-min (MILO (Fixed))
  EL2NSelector            — keep hardest/easiest by EL2N score [Paul et al.'21]
  SelfSupPruneSelector    — self-supervised prototype-distance pruning
                            [Sorscher et al.'22] (App. I.8 comparison)

Model-dependent per-epoch strategies (selection uses the *current* model):

  CraigPBSelector         — per-batch CRAIG: facility location over last-layer
                            gradient similarity [Mirzasoleiman'20, per-batch
                            variant of Killamsetty'21]
  GradMatchPBSelector     — per-batch GRAD-MATCH: OMP matching of the full
                            gradient sum [Killamsetty'21]
  GlisterSelector         — greedy validation-gain selection [Killamsetty'21]

The model-dependent ones take ``grad_fn(indices) -> (n, d) per-sample (proxy)
gradients`` and ``val_grad_fn() -> (d,)``; the trainer wires these to the
last-layer-gradient approximation exactly as CORDS does.  Their *cost* is the
paper's argument: each refresh is O(n·d + selection), on the training
critical path — MILO moves all of it to preprocessing.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.greedy import greedy
from repro.core.similarity import gram_matrix
from repro.core.submodular import disparity_min, facility_location


# --------------------------------------------------------------------------
# selection math (shared by the legacy classes and repro.selection wrappers)
# --------------------------------------------------------------------------

def _normalize_weights(w: np.ndarray) -> np.ndarray:
    """Scale weights to mean 1 so the weighted loss keeps its usual scale."""
    w = np.asarray(w, np.float32)
    total = float(w.sum())
    if not np.isfinite(total) or total <= 0.0:
        return np.ones_like(w)
    return w * (len(w) / total)


def craig_pb_select(g: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """CRAIG: facility-location medoids of the gradient-similarity kernel.

    Returns (indices, weights) where weight_j is the mass of the cluster
    represented by medoid j (CRAIG's γ coefficients), normalized to mean 1.
    """
    K = gram_matrix(jnp.asarray(g))
    idx = np.asarray(greedy(facility_location, K, k).indices, np.int64)
    # every sample is "covered" by its most similar medoid; the medoid's
    # loss weight is how many samples it stands in for.  Reduce on device:
    # only the (n,) assignment vector crosses to the host, not the n^2 kernel
    assign = np.asarray(jnp.argmax(K[:, jnp.asarray(idx)], axis=1))
    w = np.bincount(assign, minlength=len(idx)).astype(np.float32)
    return idx, _normalize_weights(w)


def gradmatch_omp_select(
    g: np.ndarray, k: int, lam: float = 0.5
) -> tuple[np.ndarray, np.ndarray]:
    """GRAD-MATCH: OMP-style matching of the mean gradient.

    Returns (indices, weights) with the non-negative OMP coefficients as
    weights (normalized to mean 1).
    """
    g = np.asarray(g, np.float64)
    target = g.mean(0)
    residual = target.copy()
    chosen: list[int] = []
    coefs: list[float] = []
    for _ in range(k):
        scores = g @ residual
        scores[chosen] = -np.inf
        j = int(np.argmax(scores))
        chosen.append(j)
        # per-element weight via nonneg projection (simplified OMP)
        denom = (g[j] @ g[j]) + lam
        w = max(0.0, (g[j] @ residual) / denom)
        coefs.append(w)
        residual = residual - w * g[j]
    return np.asarray(chosen, np.int64), _normalize_weights(np.asarray(coefs))


def glister_select(
    g: np.ndarray, gv: np.ndarray, k: int, eta: float = 0.1
) -> np.ndarray:
    """GLISTER: greedy validation-gain selection (bilevel approximation):
    score(j) ≈ <g_j, g_val> taken greedily with residual updates."""
    g = np.asarray(g, np.float64)
    gv = np.asarray(gv, np.float64)
    chosen: list[int] = []
    acc = np.zeros_like(gv)
    for _ in range(k):
        # validation gain if j's gradient step is added
        scores = g @ (gv - eta * acc)
        scores[chosen] = -np.inf
        j = int(np.argmax(scores))
        chosen.append(j)
        acc = acc + g[j]
    return np.asarray(chosen, np.int64)


# --------------------------------------------------------------------------
# model-independent baselines
# --------------------------------------------------------------------------

@dataclasses.dataclass
class RandomSelector:
    n: int
    k: int
    seed: int = 0

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        self._idx = rng.choice(self.n, size=self.k, replace=False)

    def indices_for_epoch(self, epoch: int) -> np.ndarray:
        return self._idx


@dataclasses.dataclass
class AdaptiveRandomSelector:
    n: int
    k: int
    R: int = 1
    seed: int = 0

    def indices_for_epoch(self, epoch: int) -> np.ndarray:
        window = epoch // self.R
        rng = np.random.default_rng(self.seed * 7919 + window)
        return rng.choice(self.n, size=self.k, replace=False)


@dataclasses.dataclass
class MiloFixedSelector:
    """Fixed subset maximizing disparity-min over frozen-encoder features.

    ``gram_free=True`` runs the selection directly over row-normalized
    features (O(n·d) memory) instead of materializing the (n, n) Gram —
    identical trajectories, see ``repro.core.gram_free``.

    ``shard_selection=True`` additionally shards the feature rows across all
    local devices (``repro.core.sharded``; implies the gram-free route) —
    still trajectory-identical, falling back to the local path when n does
    not divide the device count or only one device exists.
    """

    features: np.ndarray
    k: int
    gram_free: bool = False
    shard_selection: bool = False

    def __post_init__(self):
        if self.gram_free or self.shard_selection:
            from repro.core.gram_free import make_gram_free_disparity_min
            from repro.core.similarity import normalize_rows

            z = normalize_rows(jnp.asarray(self.features, jnp.float32))
            if self.shard_selection:
                from repro.core import sharded as sharded_mod
                from repro.distributed.sharding import selection_mesh

                mesh = selection_mesh(axis=sharded_mod.AXIS)
                ndev = mesh.shape[sharded_mod.AXIS]
                if ndev > 1 and z.shape[0] % ndev == 0:
                    fn = sharded_mod.make_sharded_gram_free(
                        "disparity_min", n_shards=ndev
                    )
                    res = sharded_mod.sharded_greedy(fn, z, self.k, mesh=mesh)
                    self._idx = np.asarray(res.indices, np.int64)
                    return
            fn = make_gram_free_disparity_min()
            self._idx = np.asarray(greedy(fn, z, self.k).indices, np.int64)
            return
        K = gram_matrix(jnp.asarray(self.features))
        self._idx = np.asarray(greedy(disparity_min, K, self.k).indices, np.int64)

    def indices_for_epoch(self, epoch: int) -> np.ndarray:
        return self._idx


@dataclasses.dataclass
class EL2NSelector:
    """Data-diet scoring: EL2N = ||p - onehot(y)||2, computed from an early
    model snapshot; keeps hardest (or easiest) k."""

    scores: np.ndarray
    k: int
    keep: str = "hard"  # hard | easy

    def __post_init__(self):
        order = np.argsort(self.scores)
        self._idx = (order[-self.k:] if self.keep == "hard" else order[: self.k]).astype(np.int64)

    def indices_for_epoch(self, epoch: int) -> np.ndarray:
        return self._idx


@dataclasses.dataclass
class SelfSupPruneSelector:
    """[Sorscher'22]: k-means prototypes in feature space; prune by distance
    to the nearest prototype (keep hardest = farthest for large budgets)."""

    features: np.ndarray
    k: int
    n_prototypes: int = 10
    seed: int = 0

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        z = self.features
        protos = z[rng.choice(len(z), self.n_prototypes, replace=False)].copy()
        for _ in range(10):  # lloyd iterations
            d = ((z[:, None] - protos[None]) ** 2).sum(-1)
            assign = d.argmin(1)
            for c in range(self.n_prototypes):
                m = assign == c
                if m.any():
                    protos[c] = z[m].mean(0)
        dist = ((z[:, None] - protos[None]) ** 2).sum(-1).min(1)
        self._idx = np.argsort(dist)[-self.k:].astype(np.int64)  # hardest

    def indices_for_epoch(self, epoch: int) -> np.ndarray:
        return self._idx


# --------------------------------------------------------------------------
# model-dependent baselines (selection on the training critical path)
# --------------------------------------------------------------------------

@dataclasses.dataclass
class CraigPBSelector:
    """Facility location over per-sample gradient similarity, every R epochs."""

    grad_fn: Callable[[], np.ndarray]   # () -> (n, d) current per-sample grads
    k: int
    R: int = 10

    def indices_for_epoch(self, epoch: int) -> np.ndarray:
        if epoch % self.R == 0 or not hasattr(self, "_idx"):
            self._idx, self._weights = craig_pb_select(self.grad_fn(), self.k)
        return self._idx


@dataclasses.dataclass
class GradMatchPBSelector:
    """OMP-style matching of the mean gradient, every R epochs."""

    grad_fn: Callable[[], np.ndarray]
    k: int
    R: int = 10
    lam: float = 0.5

    def indices_for_epoch(self, epoch: int) -> np.ndarray:
        if epoch % self.R == 0 or not hasattr(self, "_idx"):
            self._idx, self._weights = gradmatch_omp_select(
                self.grad_fn(), self.k, self.lam
            )
        return self._idx


@dataclasses.dataclass
class GlisterSelector:
    """Greedy maximization of validation-set gain (bilevel approximation)."""

    grad_fn: Callable[[], np.ndarray]
    val_grad_fn: Callable[[], np.ndarray]
    k: int
    R: int = 10
    eta: float = 0.1

    def indices_for_epoch(self, epoch: int) -> np.ndarray:
        if epoch % self.R == 0 or not hasattr(self, "_idx"):
            self._idx = glister_select(
                self.grad_fn(), self.val_grad_fn(), self.k, self.eta
            )
        return self._idx
