"""Sharded-vs-single-device selection equivalence (ISSUE 3 tentpole).

The multi-device tests need a multi-device platform, which on CPU must be
forced via ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` *before*
jax initializes.  Under the plain tier-1 run (one device) a wrapper test
re-invokes this file in a subprocess with the flag set — so the equivalence
suite is exercised either way; CI's sharded-smoke job also runs it directly
with the flag exported.

Equivalence contract (see core.sharded):
  * selected trajectories (indices) bit-identical for all four engines,
  * gains bit-identical for the state-only set functions (disparity sum/min:
    no cross-shard arithmetic ever combines float values),
  * gains within float32 reduction-order rounding for facility location /
    graph cut (the psum over shard partials reassociates the row sum),
  * per-device memory: the z shard holds exactly n/ndev rows.
"""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

MULTI = jax.device_count() >= 8

multi_device = pytest.mark.skipif(
    not MULTI, reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8"
)


@pytest.mark.skipif(MULTI, reason="already on a multi-device platform")
def test_sharded_suite_under_forced_8_device_cpu():
    """Tier-1 entry point: run this file's multi-device tests for real."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider", __file__],
        env=env, cwd=Path(__file__).parents[1], capture_output=True, text=True,
        timeout=1500,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "passed" in r.stdout and "skipped" in r.stdout  # wrapper skipped


# ---------------------------------------------------------------------------
# multi-device equivalence
# ---------------------------------------------------------------------------

def _fixture(n: int, d: int = 16, seed: int = 0) -> jnp.ndarray:
    from repro.core.similarity import normalize_rows

    rng = np.random.default_rng(seed)
    return normalize_rows(jnp.asarray(rng.normal(size=(n, d)).astype(np.float32)))


def _mesh():
    from repro.distributed.sharding import selection_mesh

    return selection_mesh(8)


_GAINS_BIT_EXACT = {"disparity_sum", "disparity_min"}

# A tied step: the two candidates' exact (float64) gains at the shared
# prefix differ by at most TIE_ULPS float32 ulps.  The ring psum reassociates
# the cached base gains (<= 1 ulp each) and later lazy corrections can add a
# few ulps of drift, so such a pair may resolve either way.
TIE_ULPS = 4


def _fl_gains_f64(z, prefix, valid=None):
    """Exact facility-location gains after selecting ``prefix``."""
    z64 = np.asarray(z, np.float64)
    live = (np.ones(len(z64), bool) if valid is None
            else np.asarray(valid, bool))
    sim = np.where(live[:, None] & live[None, :], 0.5 + 0.5 * z64 @ z64.T, 0.0)
    cover = sim[:, prefix].max(axis=1) if len(prefix) else np.zeros(len(z64))
    cover = np.where(live, cover, np.inf)
    return np.maximum(sim - cover[:, None], 0.0).sum(axis=0)


def _assert_same_trajectory_up_to_one_tie(z, a, b, valid=None):
    """Indices identical except at most one float32 near-tie step."""
    ia, ib = np.asarray(a.indices), np.asarray(b.indices)
    diff = np.flatnonzero(ia != ib)
    if not len(diff):
        return
    assert len(diff) == 1, f"trajectories differ at steps {diff.tolist()}"
    t = int(diff[0])
    g = _fl_gains_f64(z, ia[:t], valid)
    ulp = np.spacing(np.float32(g[ia[t]]))
    assert abs(g[ia[t]] - g[ib[t]]) <= TIE_ULPS * ulp, (
        f"step {t}: {ia[t]} vs {ib[t]} is not a float32 near-tie "
        f"({g[ia[t]]} vs {g[ib[t]]})")
    ga, gb = np.float32(a.gains[t]), np.float32(b.gains[t])
    assert abs(ga - gb) <= TIE_ULPS * np.spacing(ga), (t, ga, gb)


def _swapped_ties(z, imp_a, imp_b, valid=None) -> set:
    """Elements whose WRE importance differs between two exhaustive FL runs.

    Each must belong to a pair (e, f) where run a took e at step t and run
    b took f there instead, the pair being a near-tie at run a's prefix
    (each run takes the other element later, so both importances move);
    every other element must agree to rounding.  Returns the swapped
    elements.
    """
    ia, ib = np.asarray(imp_a, np.float64), np.asarray(imp_b, np.float64)
    bad = set(np.flatnonzero(~np.isclose(ia, ib, rtol=1e-5, atol=1e-5)).tolist())
    swapped = set(bad)
    order = np.argsort(-ia, kind="stable")  # run a's pick order
    pos = np.empty_like(order)
    pos[order] = np.arange(len(order))
    while bad:
        e = min(bad, key=lambda x: pos[x])
        t = int(pos[e])
        g = _fl_gains_f64(z, order[:t], valid)
        np.testing.assert_allclose(ia[e], g[e], rtol=1e-5, atol=1e-5)
        tie = TIE_ULPS * np.spacing(np.float32(g[e]))
        # run b's pick at step t: its recorded gain is its gain there
        partners = [f for f in bad - {e} if abs(g[e] - g[f]) <= tie
                    and np.isclose(ib[f], g[f], rtol=1e-5, atol=1e-5)]
        assert partners, f"element {e} (step {t}) changed without a near-tie"
        bad -= {e, partners[0]}
    return swapped


@multi_device
@pytest.mark.parametrize(
    "name", ["facility_location", "graph_cut", "disparity_sum", "disparity_min"]
)
def test_sharded_greedy_matches_single_device(name):
    from repro.core import get_gram_free, greedy, make_sharded_gram_free, sharded_greedy

    z = _fixture(256)
    k = 24
    a = greedy(get_gram_free(name), z, k)
    b = sharded_greedy(
        make_sharded_gram_free(name, n_shards=8), z, k, mesh=_mesh()
    )
    np.testing.assert_array_equal(np.asarray(a.indices), np.asarray(b.indices),
                                  err_msg=name)
    if name in _GAINS_BIT_EXACT:
        np.testing.assert_array_equal(np.asarray(a.gains), np.asarray(b.gains),
                                      err_msg=name)
    else:
        np.testing.assert_allclose(np.asarray(a.gains), np.asarray(b.gains),
                                   rtol=1e-5, atol=1e-6, err_msg=name)


@multi_device
@pytest.mark.parametrize("name", ["facility_location", "graph_cut"])
def test_sharded_stochastic_greedy_and_sge_bank(name):
    """The Gumbel candidate draws use the replicated key and global n, so the
    stochastic trajectories are bit-identical too — singly and vmapped."""
    from repro.core import (
        get_gram_free,
        make_sharded_gram_free,
        sge,
        sharded_sge,
        sharded_stochastic_greedy,
        stochastic_greedy,
    )
    from repro.core.greedy import stochastic_candidate_count

    z = _fixture(256, seed=1)
    k = 20
    s = stochastic_candidate_count(256, k, 0.01)
    key = jax.random.PRNGKey(7)
    fn1 = get_gram_free(name)
    fns = make_sharded_gram_free(name, n_shards=8)
    a = stochastic_greedy(fn1, z, k, key, s=s)
    b = sharded_stochastic_greedy(fns, z, k, key, s=s, mesh=_mesh())
    np.testing.assert_array_equal(np.asarray(a.indices), np.asarray(b.indices))
    bank1 = sge(fn1, z, k, key, n_subsets=3)
    bank8 = sharded_sge(fns, z, k, key, n_subsets=3, mesh=_mesh())
    np.testing.assert_array_equal(np.asarray(bank1), np.asarray(bank8))


@multi_device
def test_sharded_greedy_importance_disparity_min_bit_exact():
    """The WRE default hard function: full n-step pass incl. a bucketed valid
    mask, bit-identical importance (exhaustion guard included)."""
    from repro.core import (
        get_gram_free,
        greedy_importance,
        make_sharded_gram_free,
        sharded_greedy_importance,
    )

    z = _fixture(256, seed=2)
    valid = jnp.arange(256) < 200
    zp = z.at[200:].set(0.0)
    fn1 = get_gram_free("disparity_min")
    fns = make_sharded_gram_free("disparity_min", n_shards=8)
    a = greedy_importance(fn1, zp, valid=valid)
    b = sharded_greedy_importance(fns, zp, mesh=_mesh(), valid=valid)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.all(np.asarray(a)[200:] == 0.0)


@multi_device
def test_sharded_greedy_importance_facility_location():
    from repro.core import (
        get_gram_free,
        greedy_importance,
        make_sharded_gram_free,
        sharded_greedy_importance,
    )

    z = _fixture(128, seed=3)
    a = greedy_importance(get_gram_free("facility_location"), z)
    b = sharded_greedy_importance(
        make_sharded_gram_free("facility_location", n_shards=8), z, mesh=_mesh()
    )
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# sharded lazy gains (ISSUE 4 tentpole)
# ---------------------------------------------------------------------------

@multi_device
@pytest.mark.parametrize("n,seed,masked", [(256, 0, False), (256, 1, False),
                                           (128, 3, False), (128, 4, True)])
def test_sharded_lazy_greedy_matches_single_device_lazy(n, seed, masked):
    """Shortlist-horizon lazy runs: indices identical up to one float32
    near-tie (the ring psum reassociates the cached base gains by ≤1 ulp;
    the delta corrections themselves are bit-exact; the masked n=128 seed-4
    fixture has one pick, step 29, whose two candidates' exact gains differ
    by 0.36 ulp), gains within rounding, and the traced rows-evaluated
    counter identical — the delta path really ran under shard_map (a silent
    eager fallback would charge n rows every step)."""
    from repro.core import (
        get_gram_free,
        lazy_greedy,
        make_sharded_gram_free,
        sharded_lazy_greedy,
    )

    z = _fixture(n, seed=seed)
    valid = None
    if masked:
        n_live = n - n // 4
        z = z.at[n_live:].set(0.0)
        valid = jnp.arange(n) < n_live
    k, budget = n // 4, n // 8
    fn1 = get_gram_free("facility_location")
    fns = make_sharded_gram_free("facility_location", n_shards=8)
    a = lazy_greedy(fn1, z, k, budget=budget, valid=valid)
    b = sharded_lazy_greedy(fns, z, k, budget=budget, mesh=_mesh(),
                            valid=valid)
    _assert_same_trajectory_up_to_one_tie(z, a, b, valid)
    np.testing.assert_allclose(np.asarray(a.gains), np.asarray(b.gains),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(a.rows_evaluated),
                                  np.asarray(b.rows_evaluated))
    # at least one step must have taken the lazy path for this to prove
    # anything; budget = n/8 guarantees it on these fixtures
    assert (np.asarray(b.rows_evaluated) == budget).any()


@multi_device
def test_sharded_lazy_importance_full_run_matches():
    """The composed WRE pass (sharded_greedy_importance(lazy_budget=...)):
    full exhaustive run over the ground set, importance equal to the
    single-device lazy pass to float-rounding ulps, except for pairs taken
    in swapped order at a float32 near-tie (the caveat documented in
    greedy.lazy_greedy)."""
    from repro.core import (
        get_gram_free,
        greedy_importance,
        make_sharded_gram_free,
        sharded_greedy_importance,
    )

    z = _fixture(128, seed=3)
    fn1 = get_gram_free("facility_location")
    fns = make_sharded_gram_free("facility_location", n_shards=8)
    a = greedy_importance(fn1, z, lazy_budget=16)
    b = sharded_greedy_importance(fns, z, mesh=_mesh(), lazy_budget=16)
    _swapped_ties(z, a, b)
    assert (np.asarray(a) == 0.0).tolist() == (np.asarray(b) == 0.0).tolist()


@multi_device
def test_ring_schedule_issues_exactly_n_shards_minus_one_hops():
    """The over-rotation fix (ROADMAP PR-3 follow-up): the first ring block
    is the shard's own z_local, so a full-gains evaluation must contain
    exactly n_shards - 1 ppermute eqns — statically countable now that the
    schedule is unrolled over the static shard count — and stay bit-exact
    against the psum-combined reference reduction."""
    from jax.sharding import PartitionSpec as P

    from repro.core import get_gram_free, make_sharded_gram_free

    z = _fixture(256, seed=5)
    mesh = _mesh()
    fns = make_sharded_gram_free("facility_location", n_shards=8)

    def full_gains(zs):
        return fns.gains(fns.init(zs), zs)

    run = jax.shard_map(full_gains, mesh=mesh, in_specs=P("sel", None),
                        out_specs=P(None), check_vma=False)
    jaxpr = str(jax.make_jaxpr(run)(z))
    assert jaxpr.count("ppermute") == 7
    fn1 = get_gram_free("facility_location")
    np.testing.assert_allclose(np.asarray(jax.jit(run)(z)),
                               np.asarray(fn1.gains(fn1.init(z), z)),
                               rtol=1e-6, atol=1e-6)


@multi_device
def test_preprocessor_lazy_plus_sharded_composes():
    """MiloPreprocessor(lazy_gains=True, shard_selection=True) routes large
    classes through the sharded lazy engine (no silent eager fallback) and
    reproduces the single-device lazy artifact: SGE bank bit-identical,
    WRE importance within reduction-order ulps except for pairs taken in
    swapped order at a float32 near-tie."""
    from repro.core import MiloPreprocessor
    from repro.core import sharded as sharded_mod

    rng = np.random.default_rng(14)
    sizes = [97, 83, 70, 45, 5]  # buckets 128/128/128/64/8 + a tiny class
    labels = np.concatenate([np.full(s, i) for i, s in enumerate(sizes)])
    feats = rng.normal(size=(len(labels), 12)).astype(np.float32)
    key = jax.random.PRNGKey(0)
    kw = dict(subset_fraction=0.1, gram_free=True, lazy_gains=True,
              hard_fn="facility_location")
    base = MiloPreprocessor(**kw).preprocess(feats, labels, key)

    seen_budgets = []
    orig = sharded_mod.sharded_greedy_importance

    def spy(fn, z, **kwargs):
        seen_budgets.append(kwargs.get("lazy_budget"))
        return orig(fn, z, **kwargs)

    sharded_mod.sharded_greedy_importance = spy
    try:
        shard = MiloPreprocessor(**kw, shard_selection=True).preprocess(
            feats, labels, key)
    finally:
        sharded_mod.sharded_greedy_importance = orig
    # every mesh-routed class carried a real touched-rows budget
    assert seen_budgets and all(b is not None for b in seen_budgets)
    np.testing.assert_array_equal(base.sge_subsets, shard.sge_subsets)
    for c in range(len(sizes)):
        rows = np.flatnonzero(labels == c)
        zc = feats[rows] / np.linalg.norm(feats[rows], axis=1, keepdims=True)
        swapped = _swapped_ties(zc, base.wre_importance[rows],
                                shard.wre_importance[rows])
        if swapped:
            # a swap moves the within-class Taylor-softmax, never the
            # class's probability mass
            np.testing.assert_allclose(base.wre_probs[rows].sum(),
                                       shard.wre_probs[rows].sum(), rtol=1e-5)
        else:
            np.testing.assert_allclose(base.wre_probs[rows],
                                       shard.wre_probs[rows],
                                       rtol=1e-5, atol=1e-7)
    assert shard.config["shard_selection"] is True
    assert shard.config["lazy_gains"] is True


@multi_device
def test_sharded_factories_are_memoized():
    """Two sessions with the same knobs must receive the SAME SetFunction
    objects, or every jit/shard-program cache keys on fresh closures and
    recompiles per session (the stale shard-program cache bug)."""
    from repro.core import make_sharded_gram_free

    for name in ("facility_location", "graph_cut", "disparity_sum",
                 "disparity_min"):
        assert make_sharded_gram_free(name, n_shards=8) is \
            make_sharded_gram_free(name, n_shards=8), name
    assert make_sharded_gram_free("graph_cut", n_shards=8) is not \
        make_sharded_gram_free("graph_cut", n_shards=4)


@multi_device
def test_sharded_valid_mask_never_selects_padding():
    from repro.core import make_sharded_gram_free, sharded_sge

    z = _fixture(128, seed=4).at[96:].set(0.0)
    valid = jnp.arange(128) < 96
    fns = make_sharded_gram_free("graph_cut", n_shards=8)
    subs = np.asarray(sharded_sge(fns, z, 9, jax.random.PRNGKey(5),
                                  n_subsets=4, mesh=_mesh(), valid=valid))
    assert subs.max() < 96
    for run in subs:
        assert len(set(run.tolist())) == 9


@multi_device
def test_shard_memory_scaling_per_device_rows():
    """Acceptance: the only O(n·d) array is sharded — each device holds
    exactly n/ndev feature rows; a pre-sharded input runs unchanged."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import get_gram_free, greedy, make_sharded_gram_free, sharded_greedy

    n, d = 512, 16
    z = _fixture(n, d=d, seed=5)
    mesh = _mesh()
    zs = jax.device_put(z, NamedSharding(mesh, P("sel", None)))
    shapes = {s.data.shape for s in zs.addressable_shards}
    assert shapes == {(n // 8, d)}
    assert len(zs.addressable_shards) == 8
    res = sharded_greedy(
        make_sharded_gram_free("disparity_min", n_shards=8), zs, 16, mesh=mesh
    )
    ref = greedy(get_gram_free("disparity_min"), z, 16)
    np.testing.assert_array_equal(np.asarray(res.indices), np.asarray(ref.indices))


@multi_device
def test_sharded_rejects_non_divisible_ground_set():
    from repro.core import make_sharded_gram_free, sharded_greedy

    z = _fixture(130, seed=6)
    fns = make_sharded_gram_free("graph_cut", n_shards=8)
    with pytest.raises(ValueError, match="not divisible"):
        sharded_greedy(fns, z, 8, mesh=_mesh())


@multi_device
def test_preprocessor_shard_selection_matches_single_device():
    """End to end: sharded preprocessing produces a bit-identical artifact
    (SGE bank AND WRE importance), including classes whose pow2 bucket is
    mesh-divisible and tiny classes that fall back to the local path."""
    from repro.core import MiloPreprocessor

    rng = np.random.default_rng(14)
    sizes = [97, 83, 70, 45, 5]  # buckets 128/128/128/64/8 — plus a tiny class
    labels = np.concatenate([np.full(s, i) for i, s in enumerate(sizes)])
    feats = rng.normal(size=(len(labels), 12)).astype(np.float32)
    key = jax.random.PRNGKey(0)
    base = MiloPreprocessor(subset_fraction=0.1, gram_free=True).preprocess(
        feats, labels, key)
    shard = MiloPreprocessor(subset_fraction=0.1, gram_free=True,
                             shard_selection=True).preprocess(feats, labels, key)
    np.testing.assert_array_equal(base.sge_subsets, shard.sge_subsets)
    np.testing.assert_array_equal(base.wre_importance, shard.wre_importance)
    np.testing.assert_array_equal(base.wre_probs, shard.wre_probs)
    assert shard.config["shard_selection"] is True


@multi_device
def test_milo_fixed_shard_selection_matches():
    from repro.selection import build_selector

    rng = np.random.default_rng(15)
    feats = rng.normal(size=(256, 12)).astype(np.float32)
    a = build_selector("milo_fixed", features=feats, k=24, gram_free=True)
    b = build_selector("milo_fixed", features=feats, k=24, shard_selection=True)
    np.testing.assert_array_equal(a.plan(0).indices, b.plan(0).indices)


@multi_device
def test_selection_mesh_validates_device_count():
    from repro.distributed.sharding import selection_mesh

    assert selection_mesh().shape["sel"] == jax.device_count()
    assert selection_mesh(4).shape["sel"] == 4
    with pytest.raises(ValueError, match="out of range"):
        selection_mesh(10**6)


@multi_device
def test_sharded_two_level_gather_bit_identical_and_smaller_payload():
    """ISSUE 5 satellite: the two-level gather budget under shard_map.
    Right-sizing the touched-row gather to the smallest covering pow2 level
    shrinks the one-owner psum payload (rows_evaluated records the level
    actually gathered) while the indices stay identical to the single-level
    sharded run.  The gains agree to float32 rounding, as between sharded
    and single-device lazy runs: the delta kernel sums over a gather of
    another length, which the CPU backend may reduce in another order."""
    from repro.core import make_sharded_gram_free, sharded_lazy_greedy
    from repro.core.greedy import _gather_levels

    n, budget = 256, 32
    z = _fixture(n, seed=6)
    fns = make_sharded_gram_free("facility_location", n_shards=8)
    a = sharded_lazy_greedy(fns, z, n, budget=budget, mesh=_mesh())
    b = sharded_lazy_greedy(fns, z, n, budget=budget, mesh=_mesh(),
                            two_level=True)
    np.testing.assert_array_equal(np.asarray(a.indices), np.asarray(b.indices))
    np.testing.assert_allclose(np.asarray(a.gains), np.asarray(b.gains),
                               rtol=1e-6, atol=1e-6)
    ra, rb = np.asarray(a.rows_evaluated), np.asarray(b.rows_evaluated)
    np.testing.assert_array_equal(ra == n, rb == n)  # same fallback steps
    lazy_a, lazy_b = ra[ra < n], rb[rb < n]
    assert np.all(lazy_a == budget)
    assert set(lazy_b.tolist()) <= set(_gather_levels(budget))
    assert lazy_b.sum() < lazy_a.sum()  # the psum payload really shrank


@multi_device
def test_sharded_two_level_importance_matches_single_device():
    """sharded_greedy_importance(lazy_two_level=True) equals the
    single-device two-level pass (which itself is bit-identical to the
    single-level one) to the documented ring-psum rounding, up to pairs
    taken in swapped order at a float32 near-tie."""
    from repro.core import (
        get_gram_free,
        greedy_importance,
        make_sharded_gram_free,
        sharded_greedy_importance,
    )

    z = _fixture(128, seed=7)
    fn1 = get_gram_free("facility_location")
    fns = make_sharded_gram_free("facility_location", n_shards=8)
    a = greedy_importance(fn1, z, lazy_budget=16, lazy_two_level=True)
    b = sharded_greedy_importance(fns, z, mesh=_mesh(), lazy_budget=16,
                                  lazy_two_level=True)
    _swapped_ties(z, a, b)
