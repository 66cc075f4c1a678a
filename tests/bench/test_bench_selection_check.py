"""The selection cells' check, at a size a CPU test run holds.

Each test drives the rest of a run (set-up, window, check) through
``run_cell`` with the look for a chip skipped, against the cell's own limits
(``bench/limits/sel-cifar100.json``).  The program passes; the bfloat16
control in the program's place fails; so does the program with an answer
altered where it is produced, or mapped to the wrong rows.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.manifest import ROOT, Manifest
from bench.references import milo_selection as ref
from bench.run import run_cell

M = Manifest(ROOT)
CELL = M.workload("sel-cifar100")
CONFIG = {"name": "small-sel", "classes": 4, "rows_per_class": 300, "width": 96}
TRAFFIC = M.traffic(CELL["traffic"])
LIMITS = M.limits(CELL["name"])


def _run(seed: int = 5) -> dict:
    return run_cell(M, CELL, seed=seed, seconds=0.2, trace=False,
                    devices=jax.devices(), t_start=time.perf_counter(),
                    config=CONFIG, traffic=TRAFFIC, limits=LIMITS)


def test_program_is_correct():
    r = _run()
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["checks"]) == set(LIMITS)


def test_bfloat16_control_is_not_correct(monkeypatch):
    from repro.selection.session import MiloSession

    def control(self, features, labels=None, **_):
        return ref.control_artifact(features, labels, TRAFFIC, "bfloat16")

    monkeypatch.setattr(MiloSession, "build_metadata", control)
    r = _run()
    assert r["correct"] is False
    assert r["checks"]["imp_gap"]["value"] > LIMITS["imp_gap"]


def test_wre_gain_altered_is_not_correct(monkeypatch):
    import repro.core.milo as milo

    real = milo.greedy_importance

    def altered(*a, **kw):
        g = real(*a, **kw)
        return g.at[jnp.argmin(g)].set(0.0)

    monkeypatch.setattr(milo, "greedy_importance", altered)
    r = _run()
    assert r["correct"] is False
    assert r["checks"]["imp_gap"]["value"] > LIMITS["imp_gap"]


def test_sge_subset_altered_is_not_correct(monkeypatch):
    import repro.core.milo as milo

    real = milo.run_sge

    def altered(*a, **kw):
        subs = real(*a, **kw)
        return subs.at[:, 1].set(subs[:, 0])

    monkeypatch.setattr(milo, "run_sge", altered)
    r = _run()
    assert r["correct"] is False
    assert r["checks"]["bank_bad"]["value"] > LIMITS["bank_bad"]


@pytest.mark.parametrize("fault", ["permuted", "shifted"])
def test_importances_on_the_wrong_rows_are_not_correct(monkeypatch, fault):
    """A class's importances permuted, or shifted by one row, where the
    padded engine output is cut back to the class."""
    from repro.core.milo import MiloPreprocessor

    real = MiloPreprocessor._class_selection

    def mapped(self, *a, **kw):
        subs, imp = real(self, *a, **kw)
        if fault == "permuted":
            return subs, imp[np.random.default_rng(0).permutation(len(imp))]
        return subs, np.roll(imp, 1)

    monkeypatch.setattr(MiloPreprocessor, "_class_selection", mapped)
    r = _run()
    assert r["correct"] is False
    assert r["checks"]["imp_row"]["value"] > LIMITS["imp_row"]


def test_probabilities_on_the_wrong_rows_are_not_correct(monkeypatch):
    import repro.core.milo as milo

    real = milo.taylor_softmax
    monkeypatch.setattr(milo, "taylor_softmax", lambda g: real(g)[::-1])
    r = _run()
    assert r["correct"] is False
    assert r["checks"]["prob_row"]["value"] > LIMITS["prob_row"]
    assert r["checks"]["imp_row"]["value"] <= LIMITS["imp_row"]


@pytest.mark.parametrize("seed", [0, 2**33 + 7])
def test_reference_gains_match_a_direct_greedy(seed):
    """The reference's greedy against a brute-force evaluation of
    disparity-min on a handful of rows."""
    rng = np.random.default_rng(seed)
    z = rng.normal(size=(9, 5))
    z /= np.linalg.norm(z, axis=1, keepdims=True)
    dist = 1.0 - (0.5 + 0.5 * z @ z.T)
    gains, _ = ref.disparity_min_gains(dist)

    def f(s):
        if len(s) < 2:
            return ref.CAP
        return min(dist[i, j] for i in s for j in s if i != j)

    chosen, total = [], 0.0
    for _ in range(9):
        cand = [j for j in range(9) if j not in chosen]
        j = max(cand, key=lambda c: (f(chosen + [c]) - f(chosen), -c))
        total += f(chosen + [j]) - f(chosen)
        assert gains[j] == pytest.approx(f(chosen + [j]) - f(chosen))
        chosen.append(j)
    assert gains.sum() == pytest.approx(total)
