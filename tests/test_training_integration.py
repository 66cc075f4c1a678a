"""Integration: trainer + MILO pipeline + checkpoint restart; serving engine;
baselines; tuner."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # optional dep: property tests guard individually
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover
    given = settings = st = None

from repro.baselines.selectors import (
    AdaptiveRandomSelector,
    CraigPBSelector,
    EL2NSelector,
    GlisterSelector,
    GradMatchPBSelector,
    MiloFixedSelector,
    RandomSelector,
    SelfSupPruneSelector,
)
from repro.configs import registry
from repro.core import CurriculumConfig, MiloPreprocessor, MiloSelector
from repro.data.datasets import TokenLMDataset
from repro.data.pipeline import FullSelector, Pipeline
from repro.models import lm
from repro.optim.optimizers import adamw, sgd_nesterov
from repro.optim.schedules import cosine, cyclic, linear_decay
from repro.serve.lm_engine import Request, ServeEngine
from repro.train.train_state import init_train_state, make_train_step
from repro.train.trainer import Trainer, TrainerConfig
from repro.tuning.tuner import RandomSearch, TPESearch, hyperband, kendall_tau


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = registry.smoke("internlm2-1.8b")
    ds = TokenLMDataset(n_docs=96, seq_len=32, vocab=cfg.vocab_size, seed=0)
    return cfg, ds


def _make_trainer(cfg, ds, selector, epochs, ckpt=None, lr=2e-3):
    pipe = Pipeline(ds.batch, selector, batch_size=8, seed=0, prefetch=False)
    opt = adamw()
    steps = max(1, pipe.steps_per_epoch() * epochs)
    step_fn = make_train_step(cfg, opt, cosine(lr, steps))
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    tr = Trainer(step_fn, pipe, TrainerConfig(
        epochs=epochs, checkpoint_dir=ckpt,
        checkpoint_every_steps=4 if ckpt else 0, async_checkpoint=False,
        log_every_steps=1))
    return tr, state


def test_training_reduces_loss_with_milo(tiny_setup):
    cfg, ds = tiny_setup
    pre = MiloPreprocessor(subset_fraction=0.5, n_sge_subsets=2, classwise=False,
                           gram_block=128)
    md = pre.preprocess(ds.features(), None, jax.random.PRNGKey(0))
    sel = MiloSelector(md, CurriculumConfig(total_epochs=10))
    tr, state = _make_trainer(cfg, ds, sel, epochs=10, lr=3e-3)
    state = tr.fit(state)
    losses = [h["loss"] for h in tr.history if "loss" in h]
    assert losses[-1] < losses[0] * 0.9, (losses[0], losses[-1])


def test_checkpoint_restart_resumes_exactly(tiny_setup, tmp_path):
    cfg, ds = tiny_setup
    ck = str(tmp_path / "ck")
    sel = FullSelector(ds.n)
    tr, state = _make_trainer(cfg, ds, sel, epochs=1, ckpt=ck)
    final = tr.fit(state)
    steps_done = int(final.step)
    # new trainer restores from the final checkpoint and does nothing more
    tr2, state2 = _make_trainer(cfg, ds, sel, epochs=1, ckpt=ck)
    resumed = tr2.fit(state2)
    assert int(resumed.step) == steps_done
    a = np.asarray(jax.tree.leaves(final.params)[0], np.float32)
    b = np.asarray(jax.tree.leaves(resumed.params)[0], np.float32)
    np.testing.assert_array_equal(a, b)


def test_optimizers_and_schedules_step():
    cfg = registry.smoke("yi-6b")
    ds = TokenLMDataset(n_docs=16, seq_len=16, vocab=cfg.vocab_size)
    batch = ds.batch(np.arange(8))
    for opt in (adamw(), sgd_nesterov()):
        state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
        step = jax.jit(make_train_step(cfg, opt, cosine(1e-3, 10)))
        state, m = step(state, batch)
        assert np.isfinite(float(m["loss"]))
    for sched in (cosine(0.1, 100, warmup=10), cyclic(0.01, 0.1, 20), linear_decay(0.1, 0.1, 5)):
        vals = [float(sched(s)) for s in range(0, 100, 7)]
        assert all(v >= 0 for v in vals)


def test_serving_engine_batches_requests():
    cfg = registry.smoke("internlm2-1.8b")
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(params, cfg, max_batch=2, max_len=32)
    rng = np.random.default_rng(0)
    for rid in range(4):  # more requests than slots -> queueing
        eng.submit(Request(rid, rng.integers(0, cfg.vocab_size, size=5).astype(np.int32),
                           max_new_tokens=4))
    done = eng.run(max_steps=100)
    assert len(done) == 4
    for r in done:
        assert len(r.generated) == 4
        assert all(0 <= t < cfg.vocab_size for t in r.generated)


def test_baseline_selectors_contract():
    n, k = 64, 16
    feats = np.random.default_rng(0).normal(size=(n, 8)).astype(np.float32)

    def grad_fn():
        return np.random.default_rng(1).normal(size=(n, 8)).astype(np.float32)

    def val_grad_fn():
        return np.random.default_rng(2).normal(size=(8,)).astype(np.float32)

    selectors = [
        RandomSelector(n, k),
        AdaptiveRandomSelector(n, k, R=2),
        MiloFixedSelector(feats, k),
        EL2NSelector(np.random.default_rng(3).random(n), k),
        SelfSupPruneSelector(feats, k, n_prototypes=4),
        CraigPBSelector(grad_fn, k, R=2),
        GradMatchPBSelector(grad_fn, k, R=2),
        GlisterSelector(grad_fn, val_grad_fn, k, R=2),
    ]
    for sel in selectors:
        for e in (0, 1, 2):
            idx = np.asarray(sel.indices_for_epoch(e))
            assert idx.shape == (k,), type(sel).__name__
            assert len(set(idx.tolist())) == k
            assert idx.min() >= 0 and idx.max() < n
    # adaptive selectors change across windows; fixed ones don't
    ar = AdaptiveRandomSelector(n, k, R=1)
    assert set(ar.indices_for_epoch(0).tolist()) != set(ar.indices_for_epoch(1).tolist())
    rs = RandomSelector(n, k)
    assert set(rs.indices_for_epoch(0).tolist()) == set(rs.indices_for_epoch(5).tolist())


def test_hyperband_finds_good_config():
    # toy objective: score peaks at lr ~ 0.1, improves with budget
    def objective(cfg, budget):
        lr = cfg["lr"]
        return -abs(np.log10(lr) + 1.0) + 0.05 * np.log1p(budget)

    space = {"lr": ("log", 1e-4, 1.0)}
    res = hyperband(objective, RandomSearch(space, seed=0), max_budget=9, eta=3)
    assert 0.01 < res.best_config["lr"] < 1.0
    res_tpe = hyperband(objective, TPESearch(space, seed=0), max_budget=9, eta=3)
    assert abs(np.log10(res_tpe.best_config["lr"]) + 1.0) < 1.0


def test_kendall_tau():
    a = np.asarray([1.0, 2.0, 3.0, 4.0])
    assert kendall_tau(a, a) == 1.0
    assert kendall_tau(a, -a) == -1.0
    assert abs(kendall_tau(a, np.asarray([1.0, 2.0, 4.0, 3.0]))) < 1.0


def _kendall_tau_loop(a, b):
    """The former O(n²) pair-loop implementation, kept as the property-test
    oracle for the vectorized sign-outer-product version."""
    n = len(a)
    num = 0
    den = 0
    for i in range(n):
        for j in range(i + 1, n):
            x = np.sign(a[i] - a[j])
            y = np.sign(b[i] - b[j])
            if x and y:
                num += int(x == y) - int(x != y)
                den += 1
    return num / den if den else 0.0


def test_kendall_tau_matches_loop_with_ties():
    rng = np.random.default_rng(0)
    a = rng.integers(0, 4, size=12).astype(float)   # plenty of ties
    b = rng.integers(0, 4, size=12).astype(float)
    assert kendall_tau(a, b) == pytest.approx(_kendall_tau_loop(a, b))
    # all-tied vectors have no comparable pairs
    assert kendall_tau(np.ones(5), np.arange(5.0)) == 0.0
    assert kendall_tau(np.arange(2.0), np.arange(2.0)) == 1.0


if st is not None:
    @settings(max_examples=50, deadline=None)
    @given(
        st.lists(st.integers(-5, 5), min_size=2, max_size=20),
        st.lists(st.integers(-5, 5), min_size=2, max_size=20),
    )
    def test_kendall_tau_property_vs_loop(xs, ys):
        n = min(len(xs), len(ys))
        a = np.asarray(xs[:n], float)
        b = np.asarray(ys[:n], float)
        assert kendall_tau(a, b) == pytest.approx(_kendall_tau_loop(a, b))


def test_train_lm_launcher_function_on_milo_subsets():
    """``repro.launch.train.train_lm``: the launcher's body as a function of
    (config, sequence length); the step is compiled before the run and the
    per-step losses come back."""
    from repro.launch.train import train_lm

    cfg = registry.smoke("internlm2-1.8b")
    out = train_lm(cfg, 32, epochs=2, subset_fraction=0.25, batch_size=4,
                   n_docs=32, seed=0)
    assert out["seq_len"] == 32 and out["subset_k"] == 8
    assert out["steps"] == 4 and len(out["losses"]) == 4
    assert all(np.isfinite(out["losses"]))
    assert out["compile_s"] > 0 and out["step_s"] > 0


def test_compile_cache_dir_env_wins_else_checkout(monkeypatch):
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
