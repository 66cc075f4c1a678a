"""Selection cells: complete MILO artifacts built back to back.

Set-up makes the features from the seed and compiles the engine programs of
exactly this cell's class geometries (``MiloPreprocessor.warmup``).  The
window calls ``MiloSession.build_metadata`` (the uncached compute unit behind
``preprocess`` and the server's artifact store) over the same rows until
``--seconds`` have passed, each artifact with its own preprocessing seed, so
each has its own SGE bank.  ``select_s`` is the window over the artifacts
completed in it.  Afterwards every artifact is held to the float64
reference (``bench/references/milo_selection.py``).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import data
from bench.references import milo_selection as ref


def session_config(traffic: dict, seed: int):
    from repro.selection import MiloSessionConfig

    return MiloSessionConfig(
        subset_fraction=traffic["subset_fraction"],
        n_sge_subsets=traffic["n_sge_subsets"], eps=traffic["eps"],
        easy_fn=traffic["easy_fn"], hard_fn=traffic["hard_fn"],
        graph_cut_lambda=traffic["graph_cut_lambda"],
        gram_free=traffic["gram_free"], seed=seed)


def inputs(config: dict, seed: int):
    return data.gaussian_mixture(seed, config["classes"],
                                 config["rows_per_class"], config["width"])


def warm(base, x: np.ndarray, y: np.ndarray, traffic: dict) -> None:
    sizes = [int(n) for n in np.bincount(y)]
    k = max(1, int(round(traffic["subset_fraction"] * len(x))))
    buckets = list(zip(sizes, ref.class_budgets(sizes, k)))
    base.preprocessor().warmup(buckets, x.shape[1])


def build(base, x, y, prep_seed: int):
    from repro.selection import MiloSession

    return MiloSession(dataclasses.replace(base, prep_seed=prep_seed)
                       ).build_metadata(x, y)


def run(run):
    import jax

    cfg, tr = run.config, run.traffic
    x, y = inputs(cfg, run.seed)
    base = session_config(tr, data.subseed(run.seed, 0))
    warm(base, x, y, tr)
    prep_seeds = np.random.default_rng([run.seed, 1])
    artifacts = []
    with run.window() as w:
        while True:
            with jax.profiler.TraceAnnotation("bench.artifact"):
                md = build(base, x, y, int(prep_seeds.integers(0, 2**31 - 1)))
            artifacts.append(md)
            if w.elapsed() >= run.seconds:
                break

    reference = ref.reference(x, y, tr)
    worst: dict[str, float] = {}
    for md in artifacts:
        for name, v in ref.compare(md, reference, tr).items():
            worst[name] = max(worst.get(name, 0.0), v)
    limits = run.limits
    return {
        "attempted": len(artifacts), "failed": 0,
        "values": {"select_s": run.window_s / len(artifacts)},
        "record": {"artifacts": len(artifacts)},
        "checks": [(n, worst[n], limits[n]) for n in sorted(limits)],
    }
