"""The split-chunk path of ``sel-imagenet1k``, at a size a CPU test run holds.

The cell's one bucket group of 1,000 partitions runs as ten chunks on a
v5e, each gathering its own scattered rows on the host.  Here a cut of its
shape (40 classes of 130 rows, so ``n_pad`` 256 and ``k_run`` 16, rows in an
order shuffled from the seed) is split into four chunks by a smaller chunk
budget, built by ``bench/drivers/selection.py`` as the cell builds its
artifacts, and held to the float64 reference under the cell's own limits
(``bench/limits/sel-imagenet1k.json``).
"""
from __future__ import annotations

import time

import jax
import numpy as np
import pytest

import repro.core.milo as milo
from bench import data
from bench.drivers import selection
from bench.manifest import ROOT, Manifest
from bench.references import milo_selection as ref
from bench.run import run_cell
from repro.core.buckets import chunk_bytes

M = Manifest(ROOT)
CELL = M.workload("sel-imagenet1k")
TRAFFIC = M.traffic(CELL["traffic"])
LIMITS = M.limits(CELL["name"])
CONFIG = {"name": "imagenet1k-cut", "classes": 40, "rows_per_class": 130,
          "width": 32}
SEED = 2**33 + 15
N_PAD = 256
# ten partitions a chunk: the group of 40 splits into four
SPLIT_LIMIT = 10 * chunk_bytes(N_PAD, CONFIG["width"], False)


def _plan(pre, n_classes: int):
    k = max(1, int(round(TRAFFIC["subset_fraction"] * n_classes
                         * CONFIG["rows_per_class"])))
    geoms = [(CONFIG["rows_per_class"], k // n_classes)] * n_classes
    return pre._plan(geoms, CONFIG["width"], bucket=True, mesh=None,
                     hard=pre._set_fn(pre.hard_fn))


def _correct(checks: dict) -> bool:
    return all(np.isfinite(v) and v <= LIMITS[n] for n, v in checks.items())


@pytest.fixture(scope="module")
def built():
    """The cut's rows, its chunks under the split budget, the artifact built
    split and unsplit with one preprocessing seed, and the reference."""
    x, y = selection.inputs(CONFIG, SEED)
    base = selection.session_config(TRAFFIC, data.subseed(SEED, 0))
    prep_seed = data.subseed(SEED, 1)
    whole = selection.build(base, x, y, prep_seed)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(milo, "chunk_byte_limit", lambda: SPLIT_LIMIT)
        chunks, loop = _plan(base.preprocessor(), CONFIG["classes"])
        split = selection.build(base, x, y, prep_seed)
    assert loop == []
    return x, y, chunks, split, whole, ref.reference(x, y, TRAFFIC)


def test_the_group_splits_into_chunks_of_scattered_rows(built):
    x, y, chunks, *_ = built
    assert len(chunks) >= 4
    assert {(c.n_pad, c.k_run) for c in chunks} == {(N_PAD, 16)}
    for c in chunks:
        own = np.sort(np.concatenate([np.nonzero(y == i)[0]
                                      for i in c.members]))
        # fewer than all rows, not one contiguous run of the table
        assert len(own) < len(x)
        assert own[-1] - own[0] + 1 > len(own)
    whole_chunks, _ = _plan(selection.session_config(TRAFFIC, 0)
                            .preprocessor(), CONFIG["classes"])
    assert len(whole_chunks) == 1


def test_split_artifact_is_correct(built):
    _, _, _, split, _, reference = built
    checks = ref.compare(split, reference, TRAFFIC)
    assert set(checks) == set(LIMITS)
    assert _correct(checks), checks


def test_one_chunks_importances_on_another_chunks_classes_are_not_correct(
        built):
    _, _, chunks, split, _, reference = built
    imp = np.array(split.wre_importance)
    for a, b in zip(chunks[0].members, chunks[1].members):
        imp[reference[b].rows] = split.wre_importance[reference[a].rows]
    planted = ref.Artifact(sge_subsets=split.sge_subsets,
                           wre_probs=split.wre_probs, wre_importance=imp)
    checks = ref.compare(planted, reference, TRAFFIC)
    assert not _correct(checks)
    assert checks["imp_row"] > LIMITS["imp_row"]


def test_split_artifact_equals_the_unsplit_one_bit_for_bit(built):
    _, _, _, split, whole, _ = built
    np.testing.assert_array_equal(split.sge_subsets, whole.sge_subsets)
    np.testing.assert_array_equal(split.wre_importance, whole.wre_importance)
    np.testing.assert_array_equal(split.wre_probs, whole.wre_probs)
    assert split.config == whole.config


def test_a_split_run_of_the_cell_is_correct(monkeypatch):
    """Set-up, window and check of the cell through ``run_cell``, with the
    cut's shape and the split budget."""
    monkeypatch.setattr(milo, "chunk_byte_limit", lambda: SPLIT_LIMIT)
    r = run_cell(M, CELL, seed=SEED, seconds=0.2, trace=False,
                 devices=jax.devices(), t_start=time.perf_counter(),
                 config=CONFIG)
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"select_s", "setup_s"}
    assert r["metrics"]["select_s"]["value"] > 0
