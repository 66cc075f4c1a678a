"""Hierarchical partition-then-refine selection (ISSUE 9).

The load-bearing claims pinned here:
  * the flat (``by_class``, ``refine_factor=1``) path through the refactored
    ``PartitionStrategy`` pipeline is BIT-identical to the class-wise loop it
    replaced — every artifact array against that loop written out in the
    test with the engines directly, and the config key-for-key, for the gram
    and gram-free routes;
  * partition strategies produce disjoint covers with the documented
    block-size / label-purity / determinism properties;
  * ``proportional_budgets`` honors the min-1 floor (the [1,1,1,97] k=4
    starvation regression lives in test_exploration.py);
  * the two-level pipeline's objective stays within 5% of the exact flat
    greedy on a seeded n=4096 facility-location fixture (quantified ratio);
  * firewall quarantine composes with hierarchical decomposition — the
    two-level local→union→global index maps never resurrect a quarantined
    row, and the artifact still re-indexes over the full ground set;
  * hierarchical provenance is stamped into the artifact and ENFORCED on
    reuse (session load + adopt refuse a partition/refine mismatch);
  * ``milo_hier`` / ``milo_targeted`` are buildable through the registry
    and produce valid fixed plans;
  * warmup pre-compiles the hierarchical geometry: a real hierarchical
    preprocess after warmup records zero backend compiles.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.core.gram_free import make_gram_free_facility_location
from repro.core.greedy import greedy, lazy_greedy, refine
from repro.core.milo import MiloPreprocessor, hierarchical_select, targeted_select
from repro.core.partition import (
    BalancedBlocks,
    ByClass,
    RandomBlocks,
    make_partition_strategy,
    partition_by_class,
    proportional_budgets,
)
from repro.core.similarity import normalize_rows
from repro.core.metadata import MetadataMismatchError
from repro.selection import MiloSession, MiloSessionConfig, build_selector
from repro.testing.faults import poison_features


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

def _golden_dataset():
    rng = np.random.default_rng(0)
    feats = rng.normal(size=(240, 16)).astype(np.float32)
    labels = rng.integers(0, 4, size=240).astype(np.int64)
    return feats, labels


def _fl_value(feats: np.ndarray, idx: np.ndarray) -> float:
    """Exact facility-location objective (rescaled cosine) of a subset."""
    z = feats.astype(np.float64)
    z /= np.linalg.norm(z, axis=1, keepdims=True)
    sim = 0.5 + 0.5 * z @ z[np.asarray(idx)].T
    return float(sim.max(axis=1).sum())


# ---------------------------------------------------------------------------
# partition strategies
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", [
    ByClass(),
    RandomBlocks(block_size=32, seed=3),
    BalancedBlocks(block_size=32),
])
def test_partition_strategies_cover_and_disjoint(strategy):
    _, labels = _golden_dataset()
    m = len(labels)
    parts = strategy.partition(labels, m)
    seen = np.concatenate([p.indices for p in parts])
    assert len(seen) == m
    assert np.array_equal(np.sort(seen), np.arange(m))


def test_by_class_matches_legacy_partition():
    _, labels = _golden_dataset()
    legacy_parts = partition_by_class(labels)
    new = ByClass().partition(labels, len(labels))
    assert len(new) == len(legacy_parts)
    for a, b in zip(new, legacy_parts):
        assert a.label == b.label
        np.testing.assert_array_equal(a.indices, b.indices)
    # no labels -> one catch-all partition over the whole ground set
    solo = ByClass().partition(None, 7)
    assert len(solo) == 1
    np.testing.assert_array_equal(solo[0].indices, np.arange(7))


def test_random_blocks_size_bound_and_seed_determinism():
    parts = RandomBlocks(block_size=32, seed=3).partition(None, 240)
    assert all(len(p.indices) <= 32 for p in parts)
    again = RandomBlocks(block_size=32, seed=3).partition(None, 240)
    for a, b in zip(parts, again):
        np.testing.assert_array_equal(a.indices, b.indices)
    other = RandomBlocks(block_size=32, seed=4).partition(None, 240)
    assert any(not np.array_equal(a.indices, b.indices)
               for a, b in zip(parts, other))


def test_balanced_blocks_keep_class_purity():
    _, labels = _golden_dataset()
    parts = BalancedBlocks(block_size=30).partition(labels, len(labels))
    assert all(len(p.indices) <= 30 for p in parts)
    for p in parts:
        assert np.all(labels[p.indices] == p.label)
    # more partitions than classes: the oversize classes got split
    assert len(parts) > len(np.unique(labels))


def test_make_partition_strategy_registry():
    assert make_partition_strategy("by_class").name == "by_class"
    s = make_partition_strategy("random_blocks", block_size=7, seed=9)
    assert (s.block_size, s.seed) == (7, 9)
    assert make_partition_strategy("balanced_blocks", block_size=5).block_size == 5
    with pytest.raises(ValueError, match="unknown partition strategy"):
        make_partition_strategy("kmeans")
    with pytest.raises(ValueError, match="block_size"):
        RandomBlocks(block_size=0)


# ---------------------------------------------------------------------------
# flat-path neutrality: the refactor must not move a single bit
# ---------------------------------------------------------------------------

def _classwise_reference(feats, labels, *, gram_free, k_frac=0.1,
                         n_subsets=4, key=None):
    """The class-wise preprocessing loop the partition pipeline replaced,
    written out with the engines directly: per class (in label order) one
    key split, the pow2-bucketed SGE bank (graph cut) and WRE importance
    (disparity min), a within-class Taylor-softmax weighted by class mass."""
    import jax.numpy as jnp

    from repro.core import gram_free as gf
    from repro.core import submodular
    from repro.core.exploration import taylor_softmax
    from repro.core.greedy import greedy_importance, sge
    from repro.core.partition import merge_class_selections
    from repro.core.similarity import gram_matrix_blocked

    m = len(labels)
    k = max(1, int(round(k_frac * m)))
    parts = partition_by_class(labels)
    budgets = proportional_budgets(parts, k)
    if gram_free:
        easy = gf.make_gram_free_graph_cut(0.4)
        hard = gf.get_gram_free("disparity_min")
    else:
        easy = submodular.make_graph_cut(0.4)
        hard = submodular.get("disparity_min")
    key = jax.random.PRNGKey(0) if key is None else key
    banks, probs, imp = [], np.zeros(m, np.float32), np.zeros(m, np.float32)
    for part, k_c in zip(parts, budgets):
        key, k_sge = jax.random.split(key)
        n_c = len(part.indices)
        z = jnp.asarray(feats[part.indices])
        A = (normalize_rows(z.astype(jnp.float32)) if gram_free
             else gram_matrix_blocked(z, metric="cosine", block=2048))
        n_pad = 1 << max(0, n_c - 1).bit_length()
        k_run = min(n_pad, 1 << max(0, k_c - 1).bit_length())
        pad = (0, n_pad - n_c)
        A = jnp.pad(A, (pad, (0, 0)) if gram_free else (pad, pad))
        valid = jnp.arange(n_pad) < n_c
        subs = sge(easy, A, k_run, k_sge, n_subsets=n_subsets, eps=0.01,
                   valid=valid)
        banks.append(np.asarray(subs, np.int64)[:, :k_c])
        imp_c = np.asarray(greedy_importance(hard, A, valid=valid),
                           np.float32)[:n_c]
        imp[part.indices] = imp_c
        p_c = np.asarray(taylor_softmax(jnp.asarray(imp_c)), np.float32)
        probs[part.indices] = p_c * (n_c / m)
    bank = np.stack([merge_class_selections(parts, [b[i] for b in banks])
                     for i in range(n_subsets)])
    return bank, probs / probs.sum(), imp, budgets


def _flat_config(gram_free: bool) -> dict:
    """The flat path's artifact config, key for key (no partition keys)."""
    return dict(
        subset_fraction=0.1, k=24, n_sge_subsets=4, eps=0.01,
        easy_fn="graph_cut", hard_fn="disparity_min", graph_cut_lambda=0.4,
        classwise=True, metric="cosine", gram_free=gram_free,
        bucket_classes=True, lazy_gains=False, lazy_threshold=0.125,
        lazy_two_level=False, exact_sge_candidates=False,
        shard_selection=False, encoder_id="precomputed", prep_seed=0,
    )


@pytest.mark.parametrize("gram_free", [False, True])
def test_flat_path_bit_identical_to_pre_refactor_golden(gram_free):
    from repro.core.metadata import config_hash

    feats, labels = _golden_dataset()
    pre = MiloPreprocessor(subset_fraction=0.1, n_sge_subsets=4,
                           gram_free=gram_free)
    md = pre.preprocess(feats, labels, jax.random.PRNGKey(0), prep_seed=0)
    bank, probs, imp, budgets = _classwise_reference(
        feats, labels, gram_free=gram_free)
    np.testing.assert_array_equal(np.asarray(md.sge_subsets, np.int64), bank)
    np.testing.assert_array_equal(np.asarray(md.wre_importance, np.float32),
                                  imp)
    np.testing.assert_array_equal(np.asarray(md.wre_probs, np.float32), probs)
    # legacy hash stability: the flat path stamps exactly the pre-hierarchy
    # keys, so its config_hash (and every reuse check keyed on it) holds
    assert md.config == _flat_config(gram_free)
    assert md.config_hash() == config_hash(_flat_config(gram_free))
    assert list(md.class_budgets) == budgets == [6, 4, 8, 6]


# ---------------------------------------------------------------------------
# hierarchical artifacts
# ---------------------------------------------------------------------------

def test_hierarchical_artifact_valid_and_stamped():
    feats, labels = _golden_dataset()
    pre = MiloPreprocessor(subset_fraction=0.1, n_sge_subsets=4,
                           gram_free=True, partition="random_blocks",
                           partition_block=64, refine_factor=2)
    md = pre.preprocess(feats, labels, jax.random.PRNGKey(0), prep_seed=0)
    k = md.k
    assert md.sge_subsets.shape == (4, k)
    for slot in np.asarray(md.sge_subsets):
        assert len(set(slot.tolist())) == k, "bank rows must be unique"
        assert slot.min() >= 0 and slot.max() < len(labels)
    assert md.config["partition"] == "random_blocks"
    assert md.config["partition_block"] == 64
    assert md.config["partition_seed"] == 0
    assert md.config["refine_factor"] == 2
    probs = np.asarray(md.wre_probs, np.float64)
    assert np.isfinite(probs).all() and probs.min() >= 0
    np.testing.assert_allclose(probs.sum(), 1.0, rtol=1e-5)
    assert sum(md.class_budgets) == k
    # deterministic: a second pass is bit-identical
    md2 = pre.preprocess(feats, labels, jax.random.PRNGKey(0), prep_seed=0)
    np.testing.assert_array_equal(md.sge_subsets, md2.sge_subsets)
    np.testing.assert_array_equal(md.wre_probs, md2.wre_probs)


def test_refine_factor_alone_activates_hierarchical_stamping():
    """rf > 1 changes the bank (wider level-0 + refine) even under the
    paper's by_class split, so it must be stamped and enforced."""
    feats, labels = _golden_dataset()
    md = MiloPreprocessor(subset_fraction=0.1, n_sge_subsets=4,
                          gram_free=True, refine_factor=2).preprocess(
        feats, labels, jax.random.PRNGKey(0), prep_seed=0)
    assert md.config["partition"] == "by_class"
    assert md.config["refine_factor"] == 2
    for slot in np.asarray(md.sge_subsets):
        assert len(set(slot.tolist())) == md.k


# ---------------------------------------------------------------------------
# approximation quality: two-level vs exact flat greedy (quantified)
# ---------------------------------------------------------------------------

def test_hierarchical_fl_objective_within_5pct_of_exact_flat_greedy():
    rng = np.random.default_rng(7)
    n, d, k = 4096, 32, 128
    feats = rng.normal(size=(n, d)).astype(np.float32)

    zn = normalize_rows(np.asarray(feats))
    flat = greedy(make_gram_free_facility_location(), zn, k)
    f_flat = _fl_value(feats, np.asarray(flat.indices))

    idx, info = hierarchical_select(
        feats, k, partition="random_blocks", block_size=512,
        refine_factor=2, gram_free=True, return_info=True)
    assert idx.shape == (k,)
    assert len(set(idx.tolist())) == k
    assert info["n_partitions"] == 8
    assert info["peak_partition_rows"] <= 512
    f_hier = _fl_value(feats, idx)
    ratio = f_hier / f_flat
    assert ratio >= 0.95, f"hierarchical/flat objective ratio {ratio:.4f}"


def test_hierarchical_select_edge_cases():
    rng = np.random.default_rng(1)
    feats = rng.normal(size=(40, 8)).astype(np.float32)
    # k == 0 and k > n both clamp cleanly
    assert hierarchical_select(feats, 0).shape == (0,)
    idx = hierarchical_select(feats, 100, partition="random_blocks",
                              block_size=16)
    assert len(set(idx.tolist())) == 40
    # one partition (block >= n) degrades to plain greedy
    one = hierarchical_select(feats, 5, partition="random_blocks",
                              block_size=64, refine_factor=2)
    zn = normalize_rows(np.asarray(feats))
    direct = np.asarray(greedy(make_gram_free_facility_location(), zn, 10).indices)
    # level-0 oversamples to 10 winners; refine keeps an FL-greedy 5 of them
    assert set(one.tolist()) <= set(direct.tolist())


# ---------------------------------------------------------------------------
# quarantine x hierarchy: two-level index maps compose with the firewall
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gram_free", [False, True])
def test_quarantine_composes_with_hierarchical_decomposition(gram_free):
    rng = np.random.default_rng(0)
    labs = rng.integers(0, 3, 80).astype(np.int64)
    feats = (rng.normal(size=(80, 6)) + 0.5 * labs[:, None]).astype(np.float32)
    bad = poison_features(feats, nan_rows=[5], zero_rows=[17, 40])
    pre = MiloPreprocessor(subset_fraction=0.25, n_sge_subsets=2,
                           gram_free=gram_free, firewall="quarantine",
                           partition="random_blocks", partition_block=16,
                           refine_factor=2)
    md = pre.preprocess(bad, labs, jax.random.PRNGKey(0))
    # artifact re-indexes over the FULL ground set through BOTH remaps:
    # quarantine keep-map o (partition local -> union -> global)
    assert md.wre_probs.shape[0] == 80
    for q in (5, 17, 40):
        assert md.wre_probs[q] == 0.0
        assert md.wre_importance[q] == 0.0
        assert not np.any(md.sge_subsets == q)
    assert np.isfinite(np.asarray(md.wre_probs)).all()
    for slot in np.asarray(md.sge_subsets):
        assert len(set(slot.tolist())) == md.k
        assert slot.min() >= 0 and slot.max() < 80
    assert md.config["firewall"] == "quarantine"
    assert md.config["data_health"]["quarantined_rows"] == [5, 17, 40]
    assert md.config["partition"] == "random_blocks"
    assert md.config["refine_factor"] == 2
    md2 = pre.preprocess(bad, labs, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(md.sge_subsets, md2.sge_subsets)
    np.testing.assert_array_equal(md.wre_probs, md2.wre_probs)


# ---------------------------------------------------------------------------
# artifact reuse: hierarchical provenance is enforced, not advisory
# ---------------------------------------------------------------------------

def _session_cfg(path, **kw):
    return MiloSessionConfig(subset_fraction=0.1, n_sge_subsets=2,
                             metadata_path=str(path), **kw)


def test_artifact_reuse_enforces_partition_config(tmp_path):
    feats, labels = _golden_dataset()
    path = tmp_path / "hier.npz"
    hier = dict(partition="random_blocks", partition_block=64,
                refine_factor=2)
    MiloSession(_session_cfg(path, **hier)).preprocess(feats, labels)

    # same hierarchical config: loads without recompute
    s2 = MiloSession(_session_cfg(path, **hier))
    s2.preprocess(feats, labels)
    assert s2.loaded_from_artifact

    # any partition/refine disagreement refuses the artifact
    for bad in (dict(partition="by_class"),
                dict(partition="random_blocks", partition_block=32,
                     refine_factor=2),
                dict(partition="random_blocks", partition_block=64,
                     partition_seed=1, refine_factor=2),
                dict(partition="random_blocks", partition_block=64,
                     refine_factor=3)):
        with pytest.raises(MetadataMismatchError, match="partition|refine"):
            MiloSession(_session_cfg(path, **bad)).preprocess(feats, labels)

    # legacy flat artifact: flat session loads, hierarchical session refuses
    flat_path = tmp_path / "flat.npz"
    MiloSession(_session_cfg(flat_path)).preprocess(feats, labels)
    s3 = MiloSession(_session_cfg(flat_path))
    s3.preprocess(feats, labels)
    assert s3.loaded_from_artifact
    with pytest.raises(MetadataMismatchError, match="partition"):
        MiloSession(_session_cfg(flat_path, **hier)).preprocess(feats, labels)


def test_adopt_metadata_enforces_partition_config(tmp_path):
    feats, labels = _golden_dataset()
    hier = dict(partition="random_blocks", partition_block=64,
                refine_factor=2)
    md = MiloSession(MiloSessionConfig(
        subset_fraction=0.1, n_sge_subsets=2, **hier)).build_metadata(
        feats, labels)
    flat_session = MiloSession(MiloSessionConfig(
        subset_fraction=0.1, n_sge_subsets=2))
    with pytest.raises(MetadataMismatchError, match="partition"):
        flat_session.adopt_metadata(md)
    hier_session = MiloSession(MiloSessionConfig(
        subset_fraction=0.1, n_sge_subsets=2, **hier))
    assert hier_session.adopt_metadata(md) is md


# ---------------------------------------------------------------------------
# refine engine
# ---------------------------------------------------------------------------

def test_refine_matches_greedy_and_lazy_trajectories():
    rng = np.random.default_rng(11)
    zn = normalize_rows(np.asarray(rng.normal(size=(256, 16)).astype(np.float32)))
    fn = make_gram_free_facility_location()
    k = 24
    eager = greedy(fn, zn, k)
    plain = refine(fn, zn, k)
    np.testing.assert_array_equal(np.asarray(plain.indices),
                                  np.asarray(eager.indices))
    lazy = refine(fn, zn, k, lazy_budget=32)
    np.testing.assert_array_equal(np.asarray(lazy.indices),
                                  np.asarray(eager.indices))
    ref = lazy_greedy(fn, zn, k, budget=32)
    np.testing.assert_array_equal(np.asarray(lazy.indices),
                                  np.asarray(ref.indices))


# ---------------------------------------------------------------------------
# targeted (query-conditioned) selection
# ---------------------------------------------------------------------------

def test_targeted_select_covers_queries():
    rng = np.random.default_rng(2)
    labs = rng.integers(0, 4, 400).astype(np.int64)
    feats = (rng.normal(size=(400, 16)) + 2.0 * labs[:, None]).astype(np.float32)
    target = 2
    q_idx = np.where(labs == target)[0][:12]
    queries = feats[q_idx]
    k = 8
    idx, info = targeted_select(feats, queries, k, labels=labs,
                                refine_factor=4, return_info=True)
    assert idx.shape == (k,) and len(set(idx.tolist())) == k
    assert info["n_partitions"] == 4

    def coverage(sel):
        z = feats.astype(np.float64)
        z /= np.linalg.norm(z, axis=1, keepdims=True)
        q = queries.astype(np.float64)
        q /= np.linalg.norm(q, axis=1, keepdims=True)
        return float((0.5 + 0.5 * z[np.asarray(sel)] @ q.T).max(axis=0).mean())

    # query FL saturates once each query has a near-duplicate in the subset
    # (picks past that point are near-zero-gain), so the sharp claims are
    # coverage dominance over the untargeted pipeline and a concentrated
    # majority — not a 100% hit-rate
    untargeted = hierarchical_select(feats, k, labels=labs,
                                     partition="by_class", refine_factor=4)
    assert coverage(idx) > coverage(untargeted)
    hit = float(np.mean(labs[idx] == target))
    base = float(np.mean(labs[untargeted] == target))
    assert hit >= 0.5 and hit > base, f"targeted hit {hit} vs baseline {base}"


def test_registry_builds_hier_and_targeted_selectors():
    rng = np.random.default_rng(3)
    labs = rng.integers(0, 3, 150).astype(np.int64)
    feats = (rng.normal(size=(150, 8)) + labs[:, None]).astype(np.float32)

    hier = build_selector("milo_hier", features=feats, k=15, labels=labs,
                          partition="balanced_blocks", partition_block=32,
                          refine_factor=2)
    plan = hier.plan(0)
    plan.validate(len(feats))
    assert plan.phase == "fixed"
    assert len(set(plan.indices.tolist())) == 15
    assert plan.provenance["selector"] == "milo_hier"
    # fixed plan: identical across epochs
    np.testing.assert_array_equal(plan.indices, hier.plan(5).indices)

    targeted = build_selector("milo_targeted", features=feats,
                              queries=feats[labs == 1][:6], k=5, labels=labs)
    tplan = targeted.plan(0)
    tplan.validate(len(feats))
    assert len(set(tplan.indices.tolist())) == 5
    assert tplan.provenance["selector"] == "milo_targeted"


# ---------------------------------------------------------------------------
# warmup covers the hierarchical geometry
# ---------------------------------------------------------------------------

def _count_backend_compiles(run):
    compiles: list[str] = []

    def listener(name, duration, **kwargs):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(name)

    from jax._src import monitoring as _monitoring

    unregister = getattr(
        _monitoring, "_unregister_event_duration_listener_by_callback", None)
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        run()
    finally:
        if unregister is not None:
            unregister(listener)
        else:  # pragma: no cover
            jax.monitoring.clear_event_listeners()
    return len(compiles)


def test_warmup_precompiles_hierarchical_programs():
    """MiloServer.warm replays the strategy's decomposition through warmup;
    after it, a real hierarchical preprocess must compile NOTHING new."""
    rng = np.random.default_rng(41)
    labels = np.concatenate([np.repeat(np.arange(3), 30), np.full(14, 3)])
    feats = rng.normal(size=(len(labels), 8)).astype(np.float32)
    pre = MiloPreprocessor(subset_fraction=0.1, gram_free=True,
                           lazy_gains=True, hard_fn="facility_location",
                           partition="random_blocks", partition_block=32,
                           refine_factor=2)
    parts = pre.partition_strategy().partition(labels, len(labels))
    k = max(1, int(round(0.1 * len(labels))))
    buckets = [(len(p.indices), b)
               for p, b in zip(parts, proportional_budgets(parts, k))]
    assert pre.warmup(buckets, d=feats.shape[1]) >= 1
    md = None

    def run():
        nonlocal md
        md = pre.preprocess(feats, labels, jax.random.PRNGKey(0))

    n_compiles = _count_backend_compiles(run)
    assert n_compiles == 0, f"preprocess compiled {n_compiles} after warmup"
    assert md.config["partition"] == "random_blocks"
