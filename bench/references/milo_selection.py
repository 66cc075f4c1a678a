"""Plain reference for MILO's selection artifact, and its bfloat16 control.

The reference follows the paper (arXiv:2301.13287, Alg. 1-3) in float64
numpy, class by class, over the same rows the artifact was built from:

- the rescaled-cosine Gram ``K = 0.5 + 0.5 cos`` of the class's rows;
- WRE: the full greedy pass of disparity-min (``f(S) = min_{i != j in S}
  1 - K_ij``), which records each row's marginal gain when it is taken, the
  first row of the class first;
- the Taylor-softmax ``1 + g + g^2/2`` of those gains within the class,
  scaled by the class's share of the rows;
- SGE: graph-cut ``f(S) = sum_{i in V, j in S} K_ij - lam sum_{i, j in S} K_ij``
  over the class; each bank subset's value is held to its exact greedy's.

``compare`` holds an artifact to it.  Greedy orders that near-ties resolve
one way or another are equally valid: where two rows' gains at a step lie
within the Gram's rounding of each other, either may be taken first, and
the one taken first gets the step's gain.  So the gains are compared twice.
As sorted lists per class, which a tie swaps within, by the mean gap over
the mean gain (a Wasserstein distance): rounding in the Gram moves every
gain a little and shows there.  And row by row, which is what MILO serves
and what a row mapped to the wrong place breaks: each row's gain against
its own reference gain, or that of a row it was near-tied with at a step of
the reference's pass, whichever is closer.  The probabilities are compared
row by row the same way.  A bank subset is held to its class budget, to
distinct rows and to its graph-cut shortfall below the exact greedy.
``control_artifact`` is the same reference in bfloat16, as a JAX program,
to stand in the program's place.
"""
from __future__ import annotations

import dataclasses

import numpy as np

CAP = 2.0  # disparity-min's stand-in for +inf, as in the paper's code
# Two rows are near-tied at a step when their gains differ by less than
# TIE_ABS + TIE_REL * |gain of the row taken| (and are not equal, since an
# exact tie goes to the lowest index everywhere).  A float32 Gram from
# one-pass bfloat16 products moves most gains by about 1e-5; a wider tie
# would pair so many rows that a row shifted by one could pass.
TIE_ABS = 3e-5
TIE_REL = 3e-3


def class_budgets(sizes: list[int], k: int) -> list[int]:
    """Largest-remainder split of ``k`` over classes of ``sizes`` rows."""
    sizes = np.asarray(sizes, np.float64)
    quota = sizes * (k / sizes.sum())
    out = np.floor(quota).astype(np.int64)
    for i in np.argsort(-(quota - out), kind="stable")[: k - int(out.sum())]:
        out[i] += 1
    return [int(b) for b in out]


def disparity_min_gains(dist: np.ndarray):
    """Each row's marginal gain in the full greedy pass of disparity-min over
    the distance matrix ``dist`` (ties go to the lowest index), and the
    near-tied pairs ``(a, b)``, both ways round: at the step that took one
    of the two, the other's gain lay within the tie of it."""
    n = len(dist)
    dmin = np.full(n, CAP, dist.dtype)
    cur = CAP
    taken = np.zeros(n, bool)
    gains = np.zeros(n, dist.dtype)
    a, b = [], []
    for t in range(n):
        g = np.minimum(cur, dmin) - cur
        g[taken] = -np.inf
        j = int(np.argmax(g))
        gains[j] = g[j]
        near = np.nonzero((g >= g[j] - TIE_ABS - TIE_REL * abs(g[j]))
                          & (g != g[j]))[0]
        a.append(np.full(len(near), j))
        b.append(near)
        if t:
            cur = min(cur, dmin[j])
        dmin = np.minimum(dmin, dist[:, j])
        taken[j] = True
    a, b = np.concatenate(a), np.concatenate(b)
    return gains, (np.concatenate([a, b]), np.concatenate([b, a]))


def row_gap(got: np.ndarray, want: np.ndarray, pairs) -> float:
    """Sum over rows of the gap between a row's value and the reference's
    for it, or for a row it was near-tied with if that is closer, over the
    sum of the reference's values."""
    a, b = pairs
    err = np.abs(got - want)
    np.minimum.at(err, a, np.abs(got[a] - want[b]))
    return float(err.sum() / np.abs(want).sum())


def taylor_softmax(g: np.ndarray) -> np.ndarray:
    w = 1.0 + g + 0.5 * g * g
    return w / w.sum()


def graph_cut_value(z: np.ndarray, colsum: np.ndarray, s: np.ndarray,
                    lam: float) -> float:
    """Graph-cut value of the rows ``s`` of the unit rows ``z``: the sum of
    ``K`` over ``s`` x ``s`` is ``|s|^2 / 2 + |sum of z[s]|^2 / 2``."""
    zs = z[s].sum(axis=0)
    return float(colsum[s].sum() - lam * 0.5 * (len(s) ** 2 + zs @ zs))


def graph_cut_greedy(K: np.ndarray, colsum: np.ndarray, k: int, lam: float):
    n = len(K)
    cur = np.zeros(n)
    diag = np.diagonal(K)
    taken = np.zeros(n, bool)
    out = np.empty(k, np.int64)
    for t in range(k):
        g = colsum - lam * (2.0 * cur + diag)
        g[taken] = -np.inf
        j = int(np.argmax(g))
        out[t] = j
        taken[j] = True
        cur += K[:, j]
    return out


@dataclasses.dataclass
class ClassReference:
    rows: np.ndarray       # global indices of the class, ascending
    gains: np.ndarray      # float64 disparity-min gains, by row
    probs: np.ndarray      # float64 class-weighted probabilities, by row
    pairs: tuple           # near-tied rows (a, b), local indices
    budget: int
    z: np.ndarray          # float64 unit rows of the class
    colsum: np.ndarray
    greedy_value: float    # exact greedy graph-cut value at the budget


def reference(x: np.ndarray, y: np.ndarray, traffic: dict) -> list[ClassReference]:
    """The float64 reference of every class of ``(x, y)``."""
    m = len(x)
    classes = np.unique(y)
    rows = [np.nonzero(y == c)[0] for c in classes]
    k = max(1, int(round(traffic["subset_fraction"] * m)))
    budgets = class_budgets([len(r) for r in rows], k)
    lam = traffic["graph_cut_lambda"]
    out = []
    for r, b in zip(rows, budgets):
        z = x[r].astype(np.float64)
        z /= np.maximum(np.linalg.norm(z, axis=1, keepdims=True), 1e-8)
        K = 0.5 + 0.5 * (z @ z.T)
        gains, pairs = disparity_min_gains(1.0 - K)
        colsum = K.sum(axis=0)
        best = graph_cut_greedy(K, colsum, b, lam)
        out.append(ClassReference(
            rows=r, gains=gains, probs=taylor_softmax(gains) * (len(r) / m),
            pairs=pairs, budget=b, z=z, colsum=colsum,
            greedy_value=graph_cut_value(z, colsum, best, lam)))
    return out


def compare(artifact, ref: list[ClassReference], traffic: dict) -> dict:
    """The numbers that decide ``correct`` for one artifact.

    ``imp_gap``: mean gap between the artifact's sorted WRE gains of a class
    and the reference's, over the reference's mean absolute gain; the worst
    class.  ``imp_row``: the gains row by row, near-ties allowed for
    (``row_gap``); the worst class.  ``prob_row``: the same for the
    probabilities, which also carries each class's probability mass.
    ``bank_bad``: bank subsets of the wrong size, with a repeated row, or
    off their class budget.  ``gc_short``: widest relative shortfall of a
    bank subset's graph-cut value below the exact greedy.
    """
    imp = np.asarray(artifact.wre_importance, np.float64)
    probs = np.asarray(artifact.wre_probs, np.float64)
    bank = np.asarray(artifact.sge_subsets, np.int64)
    m = len(imp)
    k = sum(c.budget for c in ref)
    lam = traffic["graph_cut_lambda"]
    label = np.empty(m, np.int64)
    for i, c in enumerate(ref):
        label[c.rows] = i
    out = {"imp_gap": 0.0, "imp_row": 0.0, "prob_row": 0.0,
           "bank_bad": 0.0, "gc_short": 0.0}
    if bank.ndim != 2 or bank.shape != (traffic["n_sge_subsets"], k):
        out["bank_bad"] += 1
    for c in ref:
        g = imp[c.rows]
        out["imp_gap"] = max(out["imp_gap"], float(
            np.abs(np.sort(g) - np.sort(c.gains)).mean() / np.abs(c.gains).mean()))
        out["imp_row"] = max(out["imp_row"], row_gap(g, c.gains, c.pairs))
        out["prob_row"] = max(out["prob_row"],
                              row_gap(probs[c.rows], c.probs, c.pairs))
    for subset in bank:
        inside = (subset >= 0) & (subset < m)
        if not inside.all() or len(np.unique(subset)) != len(subset):
            out["bank_bad"] += 1
            continue
        for i, c in enumerate(ref):
            mine = subset[label[subset] == i]
            if len(mine) != c.budget:
                out["bank_bad"] += 1
                continue
            local = np.searchsorted(c.rows, mine)
            value = graph_cut_value(c.z, c.colsum, local, lam)
            out["gc_short"] = max(out["gc_short"], (c.greedy_value - value)
                                  / abs(c.greedy_value))
    return out


@dataclasses.dataclass
class Artifact:
    """The fields of ``MiloMetadata`` that ``compare`` reads."""

    sge_subsets: np.ndarray
    wre_probs: np.ndarray
    wre_importance: np.ndarray


def control_artifact(x: np.ndarray, y: np.ndarray, traffic: dict,
                     dtype="bfloat16") -> Artifact:
    """The reference computed in ``dtype`` on the default JAX device, as an
    artifact: Gram, greedy state, gains and probabilities all in ``dtype``;
    every bank slot is the exact greedy graph-cut subset."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    lam = traffic["graph_cut_lambda"]

    @jax.jit
    def wre(z):
        z = z.astype(dt)
        z = z / jnp.linalg.norm(z.astype(jnp.float32), axis=1,
                                keepdims=True).astype(dt)
        K = (0.5 + 0.5 * jnp.matmul(z, z.T, preferred_element_type=dt)).astype(dt)
        dist = (1.0 - K).astype(dt)
        n = K.shape[0]

        def step(t, c):
            dmin, cur, taken, gains = c
            g = jnp.where(taken, -jnp.inf, jnp.minimum(cur, dmin) - cur)
            j = jnp.argmax(g)
            cur = jnp.where(t > 0, jnp.minimum(cur, dmin[j]), cur)
            return (jnp.minimum(dmin, dist[:, j]), cur, taken.at[j].set(True),
                    gains.at[j].set(g[j]))

        c0 = (jnp.full((n,), CAP, dt), jnp.asarray(CAP, dt),
              jnp.zeros((n,), bool), jnp.zeros((n,), dt))
        gains = jax.lax.fori_loop(0, n, step, c0)[3]
        w = (1.0 + gains + 0.5 * gains * gains).astype(dt)
        return gains, (w / jnp.sum(w)).astype(dt), K

    def sge(K, b):
        colsum = jnp.sum(K, axis=0, dtype=dt)
        diag = jnp.diagonal(K)

        def step(t, c):
            cur, taken, out = c
            g = jnp.where(taken, -jnp.inf, colsum - lam * (2.0 * cur + diag))
            j = jnp.argmax(g)
            return (cur + K[:, j]).astype(dt), taken.at[j].set(True), out.at[t].set(j)

        c0 = (jnp.zeros(K.shape[0], dt), jnp.zeros(K.shape[0], bool),
              jnp.zeros((b,), jnp.int32))
        return jax.lax.fori_loop(0, b, step, c0)[2]

    m = len(x)
    classes = np.unique(y)
    rows = [np.nonzero(y == c)[0] for c in classes]
    k = max(1, int(round(traffic["subset_fraction"] * m)))
    budgets = class_budgets([len(r) for r in rows], k)
    imp = np.zeros(m, np.float32)
    probs = np.zeros(m, np.float32)
    picks = []
    for r, b in zip(rows, budgets):
        gains, p, K = wre(jnp.asarray(x[r]))
        imp[r] = np.asarray(gains.astype(jnp.float32))
        probs[r] = np.asarray(p.astype(jnp.float32)) * (len(r) / m)
        picks.append(r[np.asarray(jax.jit(sge, static_argnums=1)(K, b))])
    subset = np.concatenate(picks)
    bank = np.stack([subset] * traffic["n_sge_subsets"])
    return Artifact(sge_subsets=bank, wre_probs=probs, wre_importance=imp)
