"""Level-0 partitions batched by pow2 bucket (``core.buckets``).

The batched route runs every partition of a ``(n_pad, k_run)`` bucket group
as one device program per chunk.  It has to give the per-partition route's
artifacts bit for bit on the CPU, draw the same SGE keys, split groups by
bytes alone, and leave the lazy, Pallas, sharded and unbucketed routes on the
per-partition loop.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.milo as milo
from repro.core import MiloPreprocessor
from repro.core.buckets import (
    BucketChunk,
    bucket_chunks,
    chunk_bytes,
    sge_key_chain,
)
from repro.core.exploration import taylor_softmax
from repro.core.greedy import stochastic_candidate_count


# ---------------------------------------------------------------------------
# grouping and chunking, a pure function of shapes
# ---------------------------------------------------------------------------

def test_bucket_chunks_groups_partitions_by_bucket():
    parts = [(0, 500, 50), (1, 300, 30), (2, 512, 51), (3, 40, 4),
             (4, 257, 26), (5, 33, 3)]
    chunks = bucket_chunks(parts, d=8, gram_free=False, byte_limit=1 << 40,
                           eps=0.01)
    s = stochastic_candidate_count
    # groups in order of first appearance, members by true size
    assert chunks == [
        BucketChunk(512, 64, s(512, 64, 0.01), (0, 2), (500, 512)),
        BucketChunk(512, 32, s(512, 32, 0.01), (4, 1), (257, 300)),
        BucketChunk(64, 4, s(64, 4, 0.01), (5, 3), (33, 40)),
    ]


@pytest.mark.parametrize("gram_free, limit, sizes", [
    # Gram route, d = 8: 4 * 64 * (64 + 4 * 8) = 24,576 B a partition
    (False, 3 * 24576, [3, 3, 2, 2]),       # 10 partitions, at most 3 each
    (False, 5 * 24576, [5, 5]),
    (False, 1 << 40, [10]),
    (False, 100, [1] * 10),                 # each larger than the limit
    # gram-free: 4 * 64 * 4 * 8 = 8,192 B a partition
    (True, 4 * 8192, [4, 3, 3]),
    (True, 4 * 8192 - 1, [3, 3, 2, 2]),
])
def test_bucket_chunks_split_a_group_under_the_byte_limit(gram_free, limit,
                                                          sizes):
    parts = [(i, 60, 6) for i in range(10)]
    chunks = bucket_chunks(parts, d=8, gram_free=gram_free, byte_limit=limit,
                           eps=0.01)
    assert [len(c.members) for c in chunks] == sizes
    assert sorted(i for c in chunks for i in c.members) == list(range(10))
    item = chunk_bytes(64, 8, gram_free)
    assert item == 4 * 64 * ((0 if gram_free else 64) + 4 * 8)
    assert all(len(c.members) * item <= max(limit, item) for c in chunks)


def test_bucket_chunks_exact_candidates_split_groups_by_draw_size():
    parts = [(0, 40, 4), (1, 60, 4), (2, 50, 3)]
    padded = bucket_chunks(parts, d=8, gram_free=False, byte_limit=1 << 40,
                           eps=0.01)
    exact = bucket_chunks(parts, d=8, gram_free=False, byte_limit=1 << 40,
                          eps=0.01, exact_s=True)
    assert [(c.members, c.sizes) for c in padded] == [((0, 2, 1),
                                                       (40, 50, 60))]
    assert sorted(c.s for c in exact) == sorted(
        stochastic_candidate_count(n, k, 0.01) for _, n, k in parts)
    assert len(exact) == 3


# ---------------------------------------------------------------------------
# the SGE key chain
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make_key", [jax.random.PRNGKey, jax.random.key])
@pytest.mark.parametrize("n", [1, 7])
def test_sge_key_chain_is_the_loops_split_sequence(make_key, n):
    key = make_key(12345)
    loop = []
    for _ in range(n):
        key, sub = jax.random.split(key)
        loop.append(sub)
    chain = sge_key_chain(make_key(12345), n)
    if jax.dtypes.issubdtype(chain.dtype, jax.dtypes.prng_key):
        chain = jax.random.key_data(chain)
        loop = [jax.random.key_data(k) for k in loop]
    np.testing.assert_array_equal(np.asarray(chain), np.stack(loop))


def test_uniform_probabilities_of_an_unselected_partition():
    """A partition without a budget has zero importances; the host's uniform
    vector is the Taylor-softmax of those zeros, bit for bit."""
    for n in (1, 3, 7, 50, 333, 4096):
        host = np.full((n,), np.float32(1.0) / np.float32(n))
        np.testing.assert_array_equal(
            host, np.asarray(taylor_softmax(jnp.zeros((n,), jnp.float32))))


# ---------------------------------------------------------------------------
# the batched route against the per-partition route
# ---------------------------------------------------------------------------

def _dataset():
    """Classes of 1-100 rows over the buckets 1, 8, 32, 64 and 128; a
    budget of 0 for the smallest when k is below the class count."""
    rng = np.random.default_rng(3)
    sizes = [20, 30, 33, 45, 60, 64, 100, 5, 90, 31, 1, 1, 40, 40, 40]
    labels = np.repeat(np.arange(len(sizes)), sizes)
    labels = labels[rng.permutation(len(labels))]
    feats = rng.normal(size=(len(labels), 12)).astype(np.float32)
    return feats, labels


def _per_partition(monkeypatch, pre, feats, labels):
    def loop_only(self, geoms, d, **kw):
        return [], [i for i, (_, k) in enumerate(geoms) if k > 0]

    with monkeypatch.context() as m:
        m.setattr(MiloPreprocessor, "_plan", loop_only)
        return pre.preprocess(feats, labels, jax.random.PRNGKey(9))


@pytest.mark.parametrize("kwargs", [
    dict(gram_free=False),
    dict(gram_free=True),
    dict(gram_free=False, exact_sge_candidates=True, refine_factor=2),
    dict(gram_free=True, subset_fraction=0.01),   # budgets of 0
])
def test_batched_route_equals_the_per_partition_route(monkeypatch, kwargs):
    feats, labels = _dataset()
    pre = MiloPreprocessor(**{"subset_fraction": 0.1, "n_sge_subsets": 3,
                              **kwargs})
    # a limit of two partitions of the 64 bucket splits it into chunks
    item = chunk_bytes(64, feats.shape[1], pre.gram_free)
    monkeypatch.setattr(milo, "chunk_byte_limit", lambda: 2 * item)
    parts = pre.partition_strategy().partition(labels, len(labels))
    k = max(1, int(round(pre.subset_fraction * len(labels))))
    geoms = [(len(p.indices), min(len(p.indices), pre.refine_factor * b))
             for p, b in zip(parts, milo.proportional_budgets(parts, k))]
    chunks, loop = pre._plan(geoms, feats.shape[1], bucket=True, mesh=None,
                             hard=pre._set_fn(pre.hard_fn))
    assert loop == [] and len({c.n_pad for c in chunks}) >= 2
    if all(k_sel > 0 for _, k_sel in geoms):
        assert len({c.n_pad for c in chunks}) >= 3
        assert [c.n_pad for c in chunks].count(64) >= 2
        assert any(len(set(c.sizes)) > 1 for c in chunks)
    else:
        assert sum(len(c.members) for c in chunks) < len(geoms)

    batched = pre.preprocess(feats, labels, jax.random.PRNGKey(9))
    looped = _per_partition(monkeypatch, pre, feats, labels)
    np.testing.assert_array_equal(batched.sge_subsets, looped.sge_subsets)
    np.testing.assert_array_equal(batched.wre_importance,
                                  looped.wre_importance)
    np.testing.assert_array_equal(batched.wre_probs, looped.wre_probs)
    assert batched.config == looped.config


# ---------------------------------------------------------------------------
# which partitions take which route
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kwargs, batched", [
    (dict(), True),
    (dict(gram_free=True), True),
    (dict(gram_free=True, lazy_gains=True, hard_fn="facility_location"),
     False),
    (dict(use_pallas=True), False),
    (dict(bucket_classes=False), False),
    (dict(metric="dot"), False),
])
def test_only_the_plain_route_is_batched(kwargs, batched):
    pre = MiloPreprocessor(**kwargs)
    geoms = [(300, 30), (200, 20), (5, 0)]
    bucket = pre.bucket_classes
    chunks, loop = pre._plan(geoms, 16, bucket=bucket, mesh=None,
                             hard=pre._set_fn(pre.hard_fn))
    members = sorted(i for c in chunks for i in c.members)
    assert (members, loop) == (([0, 1], []) if batched else ([], [0, 1]))


def test_a_single_partition_keeps_the_per_partition_route(monkeypatch):
    """One partition is not bucketed, so it runs the per-partition loop."""
    calls = []
    real = MiloPreprocessor._partition_engines

    def counted(self, *a, **kw):
        calls.append(len(a[0]))
        return real(self, *a, **kw)

    monkeypatch.setattr(MiloPreprocessor, "_partition_engines", counted)
    feats = np.random.default_rng(0).normal(size=(50, 6)).astype(np.float32)
    MiloPreprocessor().preprocess(feats, None, jax.random.PRNGKey(0))
    assert calls == [50]


def test_warmup_compiles_what_a_split_batched_preprocess_runs(monkeypatch):
    """Warm-up routes and chunks as preprocess does, so a preprocess whose
    groups are split into chunks compiles nothing after it."""
    from tests.test_selection_engine import _count_backend_compiles

    feats, labels = _dataset()
    monkeypatch.setattr(milo, "chunk_byte_limit",
                        lambda: 3 * chunk_bytes(64, feats.shape[1], False))
    pre = MiloPreprocessor(subset_fraction=0.1, n_sge_subsets=3)
    parts = pre.partition_strategy().partition(labels, len(labels))
    k = max(1, int(round(0.1 * len(labels))))
    budgets = milo.proportional_budgets(parts, k)
    warmed = pre.warmup([(len(p.indices), b) for p, b in zip(parts, budgets)],
                        d=feats.shape[1])
    geoms = [(len(p.indices), b) for p, b in zip(parts, budgets)]
    chunks, _ = pre._plan(geoms, feats.shape[1], bucket=True, mesh=None,
                          hard=pre._set_fn(pre.hard_fn))
    assert warmed == len({(c.n_pad, c.k_run, c.s, c.sizes) for c in chunks})
    n = _count_backend_compiles(
        lambda: pre.preprocess(feats, labels, jax.random.PRNGKey(1)))
    assert n == 0


def test_a_chunk_puts_only_its_own_rows(monkeypatch):
    """Each chunk puts its members' rows, not the matrix: with a budget the
    matrix alone exceeds, a chunk's rows stay within it and the artifact is
    the per-partition route's."""
    import repro.core.buckets as buckets

    feats, labels = _dataset()
    pre = MiloPreprocessor(subset_fraction=0.1, n_sge_subsets=3,
                           gram_free=True)
    limit = 2 * chunk_bytes(64, feats.shape[1], True)
    assert feats.nbytes > limit
    monkeypatch.setattr(milo, "chunk_byte_limit", lambda: limit)
    puts = []
    real = buckets.bucket_kernels

    def spy(x, rows, **kw):
        puts.append((x.shape[0], kw["sizes"]))
        return real(x, rows, **kw)

    monkeypatch.setattr(milo, "bucket_kernels", spy)
    batched = pre.preprocess(feats, labels, jax.random.PRNGKey(9))
    assert len(puts) >= 5
    for n_rows, sizes in puts:
        assert n_rows == sum(sizes) < len(feats)
        assert n_rows * feats.shape[1] * 4 <= limit
    looped = _per_partition(monkeypatch, pre, feats, labels)
    np.testing.assert_array_equal(batched.sge_subsets, looped.sge_subsets)
    np.testing.assert_array_equal(batched.wre_probs, looped.wre_probs)


def test_distinct_class_sizes_compile_one_gram_program_per_chunk(monkeypatch):
    """The Gram program is keyed on a chunk's true sizes, the engines on its
    shape alone: twelve distinct sizes in four bucket groups compile one
    Gram and one SGE program per chunk, not per class, one WRE program per
    ``(P, n_pad)``, and warm-up leaves preprocess nothing to compile."""
    from repro.core import buckets
    from tests.test_selection_engine import _count_backend_compiles

    rng = np.random.default_rng(5)
    sizes = [33, 35, 38, 41, 47, 52, 66, 70, 75, 81, 90, 101]
    labels = np.repeat(np.arange(len(sizes)), sizes)
    labels = labels[rng.permutation(len(labels))]
    feats = rng.normal(size=(len(labels), 7)).astype(np.float32)
    pre = MiloPreprocessor(subset_fraction=0.1, n_sge_subsets=2)
    parts = pre.partition_strategy().partition(labels, len(labels))
    budgets = milo.proportional_budgets(
        parts, int(round(0.1 * len(labels))))
    geoms = [(len(p.indices), b) for p, b in zip(parts, budgets)]
    chunks, loop = pre._plan(geoms, feats.shape[1], bucket=True, mesh=None,
                             hard=pre._set_fn(pre.hard_fn))
    assert loop == [] and len(chunks) == len({(c.n_pad, c.k_run, c.s)
                                              for c in chunks})
    programs = (buckets.bucket_kernels, buckets.bucket_sge,
                buckets.bucket_importance)
    for f in programs:
        f.clear_cache()
    pre.warmup(geoms, d=feats.shape[1])
    assert {f.__name__: f._cache_size() for f in programs} == {"bucket_kernels": len(chunks),
                     "bucket_sge": len(chunks),
                     "bucket_importance": len({(len(c.members), c.n_pad)
                                               for c in chunks})}
    n = _count_backend_compiles(
        lambda: pre.preprocess(feats, labels, jax.random.PRNGKey(2)))
    assert n == 0
