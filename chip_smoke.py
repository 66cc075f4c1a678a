#!/usr/bin/env python3
"""Run MILO's main path once on one TPU chip and check what comes out.

Three phases, in this one process, through the entry points a user calls:

1. ``selection``: ``MiloSession`` at the CIFAR-100 train shape (50,000 rows
   of 768-wide features in 100 classes of 500, made from ``--seed``), with
   the paper's set functions (graph-cut SGE, disparity-min WRE) at
   ``subset_fraction=0.1``, then ``session.train`` for a few epochs on the
   fused training engine.
2. ``gram_free_fl``: ``MiloPreprocessor`` on the gram-free facility-location
   route with the Pallas kernels compiled, over one partition of
   8,192 x 768 rows.  Its greedy objective is compared with a plain numpy
   greedy over the same rows.
3. ``lm``: ``repro.launch.train.train_lm`` trains internlm2-1.8b at its
   published widths, cut in depth to fit one chip, for two epochs over a
   MILO-selected subset of 2,048-token documents (10 steps).

Each phase prints one JSON line with its backend compile seconds, its
steady wall time and the device's ``peak_bytes_in_use`` so far.  The last
line is ``{"ok": true, "device": {...}}``.  Without a TPU the script exits
non-zero before any phase runs.

    python chip_smoke.py               # the three phases on one chip
    python chip_smoke.py --four-chips  # only: sharded selection on a
                                       # 4-device mesh vs one device
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# internlm2-1.8b keeps 6 of its 24 layers (its pattern has a period of one
# layer): compiled for a v5e, the 6-layer train step at batch 1 takes
# 7.7 GB of arguments and temporaries, well inside 16 GB
LM_LAYERS = 6
LM_SEQ_LEN = 2048

# Objective tolerance against the float64 numpy greedy.  On a TPU an f32
# matmul at default precision takes one bf16 pass, which moves each rescaled
# cosine by about 1e-4; a greedy step may then prefer a candidate whose exact
# gain is lower by up to twice its gain error, so near-ties resolve
# differently from the exact run.  Summed over the k picks this costs the
# objective well under 1e-3 of its value on these rows; 2e-3 leaves room.
FL_OBJECTIVE_RTOL = 2e-3


class _CompileClock:
    """Backend compile seconds and count, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def mark(self) -> tuple[float, int]:
        return self.seconds, self.count


def _tpu_device():
    """The first device; exits non-zero when it is not a TPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind})")
    return dev


def _peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def _report(phase: str, clock: _CompileClock, mark, dev, **fields) -> None:
    secs, count = clock.mark()
    print(json.dumps({
        "phase": phase,
        "compile_s": secs - mark[0],
        "compiles": count - mark[1],
        **fields,
        "peak_bytes_in_use": _peak_bytes(dev),
    }), flush=True)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# phase 1: selection + classifier training at the CIFAR-100 train shape
# ---------------------------------------------------------------------------

def cifar100_shaped(seed: int, *, n_classes: int = 100, per_class: int = 500,
                    test_per_class: int = 100, dim: int = 768):
    """Train/test features with CIFAR-100's train geometry, from ``seed``."""
    from repro.data.datasets import GaussianMixtureDataset

    per = per_class + test_per_class
    ds = GaussianMixtureDataset(n=n_classes * per, n_classes=n_classes,
                                dim=dim, seed=seed)
    rng = np.random.default_rng(seed)
    # rows come grouped by class; shuffle within each class before the cut
    order = np.concatenate(
        [c * per + rng.permutation(per) for c in range(n_classes)])
    cls = order.reshape(n_classes, per)
    train, test = cls[:, :per_class].ravel(), cls[:, per_class:].ravel()
    return ds.x[train], ds.y[train], ds.x[test], ds.y[test]


def phase_selection(seed: int, clock: _CompileClock, dev, *, epochs: int = 4,
                    **shape) -> None:
    from repro.selection import MiloSession, MiloSessionConfig

    x, y, tx, ty = cifar100_shaped(seed, **shape)
    n_classes = int(y.max()) + 1
    mark = clock.mark()
    k = int(round(0.1 * len(x)))
    session = MiloSession(MiloSessionConfig(
        subset_fraction=0.1, easy_fn="graph_cut", hard_fn="disparity_min",
        fused_training=True, total_epochs=epochs, batch_size=k // 10,
        seed=seed,
    ))
    t0 = time.perf_counter()
    md = session.preprocess(x, y)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = session.build_metadata(x, y)
    steady_s = time.perf_counter() - t0

    bank = np.asarray(md.sge_subsets)
    _check(bank.shape == (8, k), f"SGE bank shape {bank.shape} != (8, {k})")
    per_class_k = k // n_classes
    for slot in bank:
        _check(len(np.unique(slot)) == k, "SGE subset repeats a row")
        counts = np.bincount(y[slot], minlength=n_classes)
        _check(np.all(counts == per_class_k),
               f"SGE subset is not {per_class_k} rows per class")
    probs = np.asarray(md.wre_probs, np.float64)
    _check(np.isfinite(probs).all() and probs.min() >= 0,
           "WRE probabilities not finite and non-negative")
    _check(abs(probs.sum() - 1.0) < 1e-4, f"WRE probabilities sum {probs.sum()}")
    _check(np.array_equal(bank, again.sge_subsets)
           and np.array_equal(md.wre_importance, again.wre_importance),
           "a second preprocess of the same rows gave another artifact")

    report = session.train(x, y, test_x=tx, test_y=ty, epochs=epochs)
    losses = [h["loss"] for h in report.history if "loss" in h]
    _check(all(math.isfinite(v) for v in losses), "non-finite training loss")
    _check(report.final_acc > 0.5,
           f"test accuracy {report.final_acc} on {n_classes} classes")
    _report("selection", clock, mark, dev, rows=len(x), dim=x.shape[1],
            classes=n_classes, k=k, preprocess_cold_s=cold_s,
            preprocess_steady_s=steady_s, train_steady_s=report.train_time,
            train_steps=report.steps, test_acc=report.final_acc)


# ---------------------------------------------------------------------------
# phase 2: gram-free facility location with the compiled Pallas kernels
# ---------------------------------------------------------------------------

def clustered_rows(seed: int, n: int, dim: int, clusters: int = 64):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(clusters, dim))
    rows = centers[rng.integers(0, clusters, n)] + rng.normal(size=(n, dim))
    return rows.astype(np.float32)


def fl_objective(z64: np.ndarray, idx: np.ndarray) -> float:
    """Facility location (rescaled cosine) of ``idx`` over unit rows z64."""
    cover = np.zeros(len(z64))
    for lo in range(0, len(idx), 1024):
        sim = 0.5 + 0.5 * z64 @ z64[idx[lo:lo + 1024]].T
        cover = np.maximum(cover, sim.max(axis=1))
    return float(cover.sum())


def numpy_greedy_fl(z64: np.ndarray, k: int) -> np.ndarray:
    """Plain float64 greedy facility location: exact gains, kept current by
    correcting only the rows whose cover the last pick raised."""
    n = len(z64)
    cover = np.zeros(n)
    gains = np.zeros(n)
    for lo in range(0, n, 1024):
        gains += (0.5 + 0.5 * z64[lo:lo + 1024] @ z64.T).sum(axis=0)
    picked = np.zeros(n, bool)
    order = []
    for _ in range(k):
        j = int(np.argmax(np.where(picked, -np.inf, gains)))
        order.append(j)
        picked[j] = True
        col = 0.5 + 0.5 * z64 @ z64[j]
        rows = np.nonzero(col > cover)[0]
        for lo in range(0, len(rows), 1024):
            r = rows[lo:lo + 1024]
            sim = 0.5 + 0.5 * z64[r] @ z64.T
            gains -= (np.maximum(sim - cover[r, None], 0.0)
                      - np.maximum(sim - col[r, None], 0.0)).sum(axis=0)
        cover[rows] = col[rows]
    return np.asarray(order)


def greedy_order(importance: np.ndarray, k: int) -> np.ndarray:
    """The first k greedy picks: FL gains never grow, so the k largest
    inclusion gains are the first k picks."""
    return np.argsort(-np.asarray(importance, np.float64), kind="stable")[:k]


def assert_kernel_compiled(pre, n: int, dim: int) -> None:
    """The WRE engine program ``pre`` runs on one (n, dim) partition holds a
    compiled Pallas call (``tpu_custom_call``), not an interpreted one."""
    import jax
    import jax.numpy as jnp

    from repro.core.greedy import greedy_importance

    fn = pre._set_fn(pre.hard_fn)
    z = jax.ShapeDtypeStruct((n, dim), jnp.float32)
    text = greedy_importance.lower(
        fn, z, lazy_budget=pre._lazy_budget(n, fn)).as_text()
    _check("tpu_custom_call" in text,
           "the gram-free FL engine holds no tpu_custom_call")


def phase_gram_free_fl(seed: int, clock: _CompileClock, dev, *,
                       n: int = 8192, dim: int = 768) -> None:
    import jax

    from repro.core import MiloPreprocessor

    feats = clustered_rows(seed + 1, n, dim)
    pre = MiloPreprocessor(
        subset_fraction=0.1, gram_free=True, use_pallas=True,
        easy_fn="facility_location", hard_fn="facility_location",
        lazy_gains=True,
    )
    assert_kernel_compiled(pre, n, dim)
    mark = clock.mark()
    key = jax.random.PRNGKey(seed)
    t0 = time.perf_counter()
    md = pre.preprocess(feats, None, key)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    pre.preprocess(feats, None, key)
    steady_s = time.perf_counter() - t0

    k = md.k
    _check(np.isfinite(md.wre_importance).all(), "non-finite FL importance")
    z64 = feats.astype(np.float64)
    z64 /= np.linalg.norm(z64, axis=1, keepdims=True)
    chip = greedy_order(md.wre_importance, k)
    ref = numpy_greedy_fl(z64, k)
    f_chip, f_ref = fl_objective(z64, chip), fl_objective(z64, ref)
    rel = abs(f_chip - f_ref) / f_ref
    _check(rel <= FL_OBJECTIVE_RTOL,
           f"FL objective {f_chip} vs numpy greedy {f_ref}: rel {rel}")
    overlap = len(np.intersect1d(chip, ref)) / k
    _report("gram_free_fl", clock, mark, dev, rows=n, dim=dim, k=k,
            preprocess_cold_s=cold_s, preprocess_steady_s=steady_s,
            objective=f_chip, reference_objective=f_ref, rel_diff=rel,
            rtol=FL_OBJECTIVE_RTOL, index_overlap=overlap)


# ---------------------------------------------------------------------------
# phase 3: LM training on MILO subsets through the launcher
# ---------------------------------------------------------------------------

def phase_lm(seed: int, clock: _CompileClock, dev, *, cfg=None,
             seq_len: int = LM_SEQ_LEN) -> None:
    from repro.configs import registry
    from repro.launch.train import train_lm

    if cfg is None:
        full = registry.get("internlm2-1.8b")
        cfg = dataclasses.replace(full, num_layers=LM_LAYERS)
        print(json.dumps({"depth_cut": f"{full.name}: {LM_LAYERS} of "
                          f"{full.num_layers} layers"}), flush=True)
    mark = clock.mark()
    # MILO's fixed subset: 5 of 20 documents, batch 1, two epochs -> 10 steps.
    # Every document draws its own tokens from the whole vocabulary, so only
    # documents seen before get easier; the second epoch revisits the first's.
    out = train_lm(cfg, seq_len, epochs=2, subset_fraction=0.25,
                   selector="milo_fixed", batch_size=1, n_docs=20, seed=seed)
    losses = out["losses"]
    ln_v = math.log(cfg.vocab_size)
    _check(len(losses) == out["steps"] and out["steps"] > 1,
           f"{out['steps']} steps, {len(losses)} losses")
    _check(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    # random tied embeddings of std 0.02 give logits of std ~0.02*sqrt(d),
    # which lifts the first loss above ln(V) by about half their variance
    _check(abs(losses[0] - ln_v) < 1.0,
           f"first loss {losses[0]} is not near ln(V) = {ln_v}")
    half = len(losses) // 2
    first, second = np.mean(losses[:half]), np.mean(losses[half:])
    _check(second < first, f"loss did not fall from epoch 1 to 2: {losses}")
    _report("lm", clock, mark, dev, arch=cfg.name, layers=cfg.num_layers,
            d_model=cfg.d_model, seq_len=seq_len, steps=out["steps"],
            train_compile_s=out["compile_s"], step_s=out["step_s"],
            preprocess_s=out["preprocess_s"], ln_vocab=ln_v,
            first_loss=losses[0], epoch1_mean_loss=first,
            epoch2_mean_loss=second, losses=losses)


# ---------------------------------------------------------------------------
# --four-chips: sharded selection on a 4-device mesh vs one device
# ---------------------------------------------------------------------------

def phase_sharded(seed: int, clock: _CompileClock, dev, *, n: int = 16384,
                  dim: int = 768) -> None:
    import jax

    from repro.core import MiloPreprocessor

    _check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, not 4")
    feats = clustered_rows(seed + 2, n, dim)
    kw = dict(subset_fraction=0.1, gram_free=True, use_pallas=True,
              easy_fn="facility_location", hard_fn="facility_location",
              lazy_gains=True)
    key = jax.random.PRNGKey(seed)
    out = {}
    for shard in (False, True):
        mark = clock.mark()
        t0 = time.perf_counter()
        md = MiloPreprocessor(**kw, shard_selection=shard).preprocess(
            feats, None, key)
        wall = time.perf_counter() - t0
        out[shard] = md
        secs, count = clock.mark()
        print(json.dumps({"run": "sharded_4" if shard else "one_device",
                          "wall_s": wall, "compile_s": secs - mark[0],
                          "compiles": count - mark[1]}), flush=True)
    mark = clock.mark()
    k = out[True].k
    z64 = feats.astype(np.float64)
    z64 /= np.linalg.norm(z64, axis=1, keepdims=True)
    one = greedy_order(out[False].wre_importance, k)
    four = greedy_order(out[True].wre_importance, k)
    f_one, f_four = fl_objective(z64, one), fl_objective(z64, four)
    rel = abs(f_four - f_one) / f_one
    _check(rel <= FL_OBJECTIVE_RTOL,
           f"sharded FL objective {f_four} vs one device {f_one}: rel {rel}")
    bank_overlap = np.mean([
        len(np.intersect1d(a, b)) / k
        for a, b in zip(out[False].sge_subsets, out[True].sge_subsets)])
    _report("sharded_selection", clock, mark, dev, rows=n, dim=dim, k=k,
            devices=4, objective_one=f_one, objective_four=f_four,
            rel_diff=rel, rtol=FL_OBJECTIVE_RTOL,
            wre_index_overlap=len(np.intersect1d(one, four)) / k,
            sge_bank_overlap=float(bank_overlap))


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only sharded selection on a 4-device mesh "
                         "against one device")
    args = ap.parse_args(argv)

    dev = _tpu_device()
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    print(json.dumps({"compile_cache": enable_compile_cache()}), flush=True)
    clock = _CompileClock()
    if args.four_chips:
        phase_sharded(args.seed, clock, dev)
    else:
        phase_selection(args.seed, clock, dev)
        phase_gram_free_fl(args.seed, clock, dev)
        phase_lm(args.seed, clock, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))


if __name__ == "__main__":
    main()
