"""End-to-end training launcher: ``--arch <id>`` + MILO-selected data.

``train_lm(cfg, seq_len, ...)`` runs the whole path once — MILO
preprocessing -> curriculum pipeline -> jit train step (state donated) ->
checkpoints -> restart — and returns a summary with the per-step losses, the
train step's compile time and the steady time per step.  The CLI wraps it;
``--smoke`` swaps in the family's reduced config for CPU runs.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch granite-moe-1b-a400m \\
      --epochs 4 --subset-fraction 0.25 --smoke --ckpt /tmp/ckpt
"""
from __future__ import annotations

import argparse
import json
import time

SELECTORS = ("milo", "random", "adaptive_random", "full", "milo_fixed")


def train_lm(
    cfg,
    seq_len: int,
    *,
    epochs: int = 4,
    subset_fraction: float = 0.25,
    selector: str = "milo",
    batch_size: int = 16,
    lr: float = 1e-3,
    ckpt: str | None = None,
    seed: int = 0,
    n_docs: int = 512,
) -> dict:
    """Train ``cfg`` on ``seq_len``-token documents chosen by ``selector``.

    The train step is compiled ahead of the run on the first batch's
    shapes, so ``compile_s`` is that compile and ``step_s`` the mean wall
    time of the steps that follow it (``fit`` ends on a blocking read).
    """
    import jax

    from repro.core import MiloPreprocessor
    from repro.data.datasets import TokenLMDataset
    from repro.data.pipeline import Pipeline
    from repro.optim.optimizers import adamw
    from repro.optim.schedules import cosine
    from repro.selection import build_selector
    from repro.train.train_state import init_train_state, make_train_step
    from repro.train.trainer import Trainer, TrainerConfig

    ds = TokenLMDataset(n_docs=n_docs, seq_len=seq_len, vocab=cfg.vocab_size,
                        seed=seed)
    t0 = time.perf_counter()
    k = max(1, int(ds.n * subset_fraction))
    if selector == "milo":
        pre = MiloPreprocessor(subset_fraction=subset_fraction, n_sge_subsets=4,
                               classwise=False)
        md = pre.preprocess(ds.features(), None, jax.random.PRNGKey(seed))
        sel = build_selector("milo", metadata=md, total_epochs=epochs, seed=seed)
        k = md.k
    elif selector in ("random", "adaptive_random"):
        sel = build_selector(selector, n=ds.n, k=k, seed=seed)
    elif selector == "milo_fixed":
        sel = build_selector("milo_fixed", features=ds.features(), k=k)
    elif selector == "full":
        sel = build_selector("full", n=ds.n)
        k = ds.n
    else:
        raise ValueError(f"unknown selector {selector!r}; one of {SELECTORS}")
    preprocess_s = time.perf_counter() - t0

    pipeline = Pipeline(ds.batch, sel, batch_size, seed=seed)
    opt = adamw()
    total_steps = max(1, pipeline.steps_per_epoch() * epochs)
    # donating the state lets the update reuse its buffers: without it the
    # old and new TrainState (params + f32 moments) are live together
    train_step = jax.jit(make_train_step(cfg, opt, cosine(lr, total_steps)),
                         donate_argnums=0)
    state = init_train_state(jax.random.PRNGKey(seed), cfg, opt)
    batches = pipeline.epoch(0)
    first = next(batches)
    batches.close()
    t0 = time.perf_counter()
    train_step.lower(state, first).compile()
    compile_s = time.perf_counter() - t0

    trainer = Trainer(
        train_step, pipeline,
        TrainerConfig(epochs=epochs, checkpoint_dir=ckpt,
                      checkpoint_every_steps=20 if ckpt else 0,
                      log_every_steps=1),
    )
    t0 = time.perf_counter()
    state = trainer.fit(state)
    steps = int(jax.block_until_ready(state.step))
    fit_s = time.perf_counter() - t0
    return {
        "arch": cfg.name, "num_layers": cfg.num_layers, "seq_len": seq_len,
        "selector": selector, "subset_k": int(k),
        "preprocess_s": preprocess_s, "compile_s": compile_s,
        "steps": steps, "step_s": fit_s / max(1, steps),
        "losses": [h["loss"] for h in trainer.history if "loss" in h],
        "final": trainer.history[-1] if trainer.history else {},
        "stragglers": trainer.monitor.flagged,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--subset-fraction", type=float, default=0.25)
    ap.add_argument("--selector", default="milo", choices=SELECTORS)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-docs", type=int, default=512)
    ap.add_argument("--seq-len", type=int, default=64)
    args = ap.parse_args()

    from repro.configs import registry
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = registry.smoke(args.arch) if args.smoke else registry.get(args.arch)
    out = train_lm(
        cfg, args.seq_len, epochs=args.epochs,
        subset_fraction=args.subset_fraction, selector=args.selector,
        batch_size=args.batch_size, lr=args.lr, ckpt=args.ckpt,
        seed=args.seed, n_docs=args.n_docs,
    )
    out.pop("losses")
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
