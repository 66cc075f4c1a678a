"""Paper App. H.3: pre-processing cost and its amortization, plus selection
throughput microbenchmarks (the jit-compiled greedy engines).

The SGE-bank section is the PR-over-PR perf trajectory for the selection hot
path (recorded in ``BENCH_selection.json`` by ``benchmarks.run``):

  * ``sge_seq_full``   — the legacy path: one dispatch per run, O(n²) full
                         gain vector per step (``gains_at`` disabled).
  * ``sge_vmap_gather``— the fused path: whole bank in one XLA program,
                         O(n·s) candidate-gather gains per step.
  * ``sge_gram_free``  — the fused path over features only (no Gram matrix
                         anywhere): the route that scales past the O(n²)
                         memory wall (n=32768 Gram would be 4.3 GB fp32).

``BENCH_FAST=1`` keeps small-n cases only (CI smoke); the Pallas gram-free
kernel is always exercised once in interpret mode so kernel regressions show
up on every push, not only under pytest.
"""
from __future__ import annotations

import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row
from repro.core import (
    MiloPreprocessor,
    get_gram_free,
    gram_matrix,
    greedy,
    greedy_importance,
    lazy_greedy,
    sge,
    stochastic_greedy,
)
from repro.core.gram_free import make_gram_free_facility_location
from repro.core.greedy import stochastic_candidate_count
from repro.core.similarity import normalize_rows
from repro.core.submodular import facility_location, graph_cut
from repro.data.datasets import GaussianMixtureDataset


def _timeit(fn, reps: int = 3) -> float:
    fn()  # compile / warm
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def _features(n: int, d: int = 64, seed: int = 0) -> jnp.ndarray:
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))


def _bench_sge_bank(rows: list[str], verbose: bool, fast: bool) -> None:
    """Before/after for the tentpole: sequential full-gains vs vmapped
    candidate-gather vs gram-free, at n ∈ {2048, 8192, 32768}."""
    n_subsets = 2
    eps = 0.01
    sizes = (2048,) if fast else (2048, 8192, 32768)
    seq_full_max_n = 8192  # the legacy path's K + per-step O(n²) beyond this
                           # is exactly the wall this PR removes
    for n in sizes:
        z = _features(n)
        k = max(1, n // 20)
        s = stochastic_candidate_count(n, k, eps)
        meta = f"k={k} s={s} n_subsets={n_subsets}"
        timings: dict[str, float] = {}

        if n <= seq_full_max_n:
            K = gram_matrix(z)
            # the pre-PR path: gains_at disabled -> full O(n²) gain vector
            # per step, one dispatch per bank entry
            fn_full = dataclasses.replace(facility_location, gains_at=None)
            timings["seq_full"] = _timeit(
                lambda: jax.block_until_ready(
                    sge(fn_full, K, k, jax.random.PRNGKey(0),
                        n_subsets=n_subsets, eps=eps, vmapped=False)
                ),
                reps=1 if n > 2048 else 2,
            )
            rows.append(csv_row(f"preprocess/sge_seq_full_n{n}",
                                timings["seq_full"] * 1e6, meta))
            if verbose:
                print(rows[-1])

            timings["vmap_gather"] = _timeit(
                lambda: jax.block_until_ready(
                    sge(facility_location, K, k, jax.random.PRNGKey(0),
                        n_subsets=n_subsets, eps=eps, vmapped=True)
                ),
            )
            speedup = timings["seq_full"] / max(timings["vmap_gather"], 1e-9)
            rows.append(csv_row(f"preprocess/sge_vmap_gather_n{n}",
                                timings["vmap_gather"] * 1e6,
                                f"{meta} speedup_vs_seq_full={speedup:.1f}x"))
            if verbose:
                print(rows[-1])
            del K

        # gram-free: no (n, n) Gram anywhere — the only route at n=32768+.
        # Same set function (facility location) as the columns above, so the
        # comparison isolates gram-freedom, not a cheaper objective.
        zn = normalize_rows(z)
        fn_gf = make_gram_free_facility_location()
        timings["gram_free"] = _timeit(
            lambda: jax.block_until_ready(
                sge(fn_gf, zn, k, jax.random.PRNGKey(0),
                    n_subsets=n_subsets, eps=eps, vmapped=True)
            ),
        )
        gram_mb = n * n * 4 / 2**20
        feat_mb = z.size * 4 / 2**20
        rows.append(csv_row(
            f"preprocess/sge_gram_free_n{n}", timings["gram_free"] * 1e6,
            f"{meta} mem_mb={feat_mb:.1f} gram_would_be_mb={gram_mb:.0f}"))
        if verbose:
            print(rows[-1])


def _bench_lazy_importance(rows: list[str], verbose: bool, fast: bool) -> None:
    """Lazy gain reuse on the WRE full-greedy FL pass (ISSUE 3 tentpole).

    The eager engine contracts all n ground rows for every one of its n
    steps; the lazy engine's traced counter records what it actually
    contracted (budget rows on a lazy step, n on a fallback recompute), so
    ``eval_reduction`` is exact even at sizes where the eager pass is not
    worth running (n=8192 would be ~35 PFLOP-equivalent of row evals).
    """
    d = 32
    cases = ((512, 64, True),) if fast else (
        (1024, 128, True),      # eager A/B at a tractable size
        (8192, 256, False),     # acceptance row: counter-only reduction
    )
    for n, budget, run_eager in cases:
        zn = normalize_rows(_features(n, d=d))
        fn = make_gram_free_facility_location()
        res = None

        def one():
            nonlocal res
            res = lazy_greedy(fn, zn, n, budget=budget)
            jax.block_until_ready(res.rows_evaluated)

        t_lazy = _timeit(one, reps=1)
        rows_eval = np.asarray(res.rows_evaluated)
        eager_evals = n * n
        lazy_evals = n + int(rows_eval.sum())  # + init full evaluation
        reduction = eager_evals / lazy_evals
        full_steps = int((rows_eval == n).sum())
        meta = (f"budget={budget} eval_reduction={reduction:.1f}x "
                f"full_recomputes={full_steps}/{n}")
        if run_eager:
            t_eager = _timeit(
                lambda: greedy(fn, zn, n).gains.block_until_ready(), reps=1
            )
            rows.append(csv_row(f"preprocess/importance_fl_eager_n{n}",
                                t_eager * 1e6, f"d={d}"))
            if verbose:
                print(rows[-1])
            meta += f" speedup_vs_eager={t_eager / max(t_lazy, 1e-9):.1f}x"
        rows.append(csv_row(f"preprocess/importance_fl_lazy_n{n}",
                            t_lazy * 1e6, meta))
        if verbose:
            print(rows[-1])


def _bench_sharded(rows: list[str], verbose: bool, fast: bool) -> None:
    """Row-sharded selection vs the single-device path (only meaningful on a
    multi-device platform; on CPU force one with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  Forced host
    "devices" share the physical cores, so the value measured here is the
    memory split (n/ndev feature rows per device) and trajectory equality,
    not wall-clock speedup."""
    if jax.device_count() < 2:
        return
    from repro.core import (
        make_sharded_gram_free,
        sharded_greedy_importance,
        sharded_lazy_greedy,
        sharded_sge,
    )
    from repro.distributed.sharding import selection_mesh

    mesh = selection_mesh()
    ndev = jax.device_count()
    n = 512 if fast else 4096
    n -= n % ndev
    k = max(1, n // 20)
    zn = normalize_rows(_features(n))
    fn1 = make_gram_free_facility_location()
    fns = make_sharded_gram_free("facility_location", n_shards=ndev)
    key = jax.random.PRNGKey(0)

    bank1 = bank8 = None

    def run_single():
        nonlocal bank1
        bank1 = jax.block_until_ready(sge(fn1, zn, k, key, n_subsets=2))

    def run_sharded():
        nonlocal bank8
        bank8 = jax.block_until_ready(
            sharded_sge(fns, zn, k, key, n_subsets=2, mesh=mesh))

    t1 = _timeit(run_single, reps=1)
    t8 = _timeit(run_sharded, reps=1)
    same = bool(np.array_equal(np.asarray(bank1), np.asarray(bank8)))
    rows.append(csv_row(
        f"preprocess/sge_sharded_n{n}_dev{ndev}", t8 * 1e6,
        f"k={k} single_device_us={t1 * 1e6:.0f} trajectories_equal={same} "
        f"rows_per_device={n // ndev}"))
    if verbose:
        print(rows[-1])

    if fast:
        fnd1 = get_gram_free("disparity_min")
        fnd8 = make_sharded_gram_free("disparity_min", n_shards=ndev)
        t1 = _timeit(lambda: greedy_importance(fnd1, zn).block_until_ready(),
                     reps=1)
        t8 = _timeit(lambda: sharded_greedy_importance(
            fnd8, zn, mesh=mesh).block_until_ready(), reps=1)
        rows.append(csv_row(
            f"preprocess/importance_sharded_n{n}_dev{ndev}", t8 * 1e6,
            f"single_device_us={t1 * 1e6:.0f} rows_per_device={n // ndev}"))
        if verbose:
            print(rows[-1])

    # lazy + sharded composed (ISSUE 4 tentpole): the WRE full-greedy FL
    # pass with cached gains corrected over touched rows only, inside
    # shard_map.  The traced counter is the acceptance evidence — the eager
    # ring engine would contract all n ground rows on every one of its n
    # steps, so eval_reduction = n² / (n + Σ rows_evaluated) is exact even
    # where the eager pass is not worth timing.
    n_lz = 512 if fast else 8192
    n_lz -= n_lz % ndev
    budget = max(1, n_lz // 8)
    zl = normalize_rows(_features(n_lz, d=32))
    fl8 = make_sharded_gram_free("facility_location", n_shards=ndev)
    res = None

    def run_lazy_sharded():
        nonlocal res
        res = sharded_lazy_greedy(fl8, zl, n_lz, budget=budget, mesh=mesh)
        jax.block_until_ready(res.rows_evaluated)

    t_lz = _timeit(run_lazy_sharded, reps=1)
    rows_eval = np.asarray(res.rows_evaluated)
    reduction = (n_lz * n_lz) / (n_lz + int(rows_eval.sum()))
    full_steps = int((rows_eval == n_lz).sum())
    rows.append(csv_row(
        f"preprocess/importance_fl_lazy_sharded_n{n_lz}_dev{ndev}",
        t_lz * 1e6,
        f"budget={budget} eval_reduction={reduction:.1f}x "
        f"full_recomputes={full_steps}/{n_lz} rows_per_device={n_lz // ndev}"))
    if verbose:
        print(rows[-1])

    # Two-level gather budget (ISSUE 5 satellite): each lazy step gathers —
    # and psums across the mesh — only the smallest pow2 level covering the
    # rows that actually moved, instead of the full budget-sized block.
    # Trajectories are bit-identical; the payload counter (rows_evaluated
    # records the level gathered) is the psum-reduction evidence.
    res2 = None

    def run_lazy_two_level():
        nonlocal res2
        res2 = sharded_lazy_greedy(fl8, zl, n_lz, budget=budget, mesh=mesh,
                                   two_level=True)
        jax.block_until_ready(res2.rows_evaluated)

    t_lz2 = _timeit(run_lazy_two_level, reps=1)
    rows2 = np.asarray(res2.rows_evaluated)
    lazy1 = rows_eval[rows_eval < n_lz]
    lazy2 = rows2[rows2 < n_lz]
    payload_red = lazy1.sum() / max(lazy2.sum(), 1)
    identical = bool(np.array_equal(np.asarray(res.indices),
                                    np.asarray(res2.indices)))
    rows.append(csv_row(
        f"preprocess/importance_fl_lazy2_sharded_n{n_lz}_dev{ndev}",
        t_lz2 * 1e6,
        f"budget={budget} psum_payload_reduction={payload_red:.1f}x "
        f"mean_gather_rows={lazy2.mean():.1f} (single-level={budget}) "
        f"indices_identical={identical}"))
    if verbose:
        print(rows[-1])


def run(verbose: bool = True) -> list[str]:
    fast = os.environ.get("BENCH_FAST") == "1"
    rows = []
    # full preprocessing wall time vs dataset size (default path: bucketed,
    # vmapped bank, candidate-gather gains)
    for n in (1000,) if fast else (1000, 4000):
        ds = GaussianMixtureDataset(n=n, n_classes=10, dim=32, seed=0)
        pre = MiloPreprocessor(subset_fraction=0.1, n_sge_subsets=4, gram_block=1024)
        t0 = time.perf_counter()
        md = pre.preprocess(ds.features(), ds.y, jax.random.PRNGKey(0))
        dt = time.perf_counter() - t0
        rows.append(csv_row(f"preprocess/full_n{n}", dt * 1e6,
                            f"k={md.k} per_sample_us={dt/n*1e6:.1f}"))
        if verbose:
            print(rows[-1])

    # jit-compiled greedy engine throughput (whole-run-on-device; the
    # beyond-paper replacement for submodlib's per-element host loop)
    z = _features(2048)
    K = gram_matrix(z)
    for name, fn in (("facility_location", facility_location), ("graph_cut", graph_cut)):
        k = 205
        dt = _timeit(lambda: greedy(fn, K, k).indices.block_until_ready())
        rows.append(csv_row(f"preprocess/greedy_{name}_n2048_k205", dt * 1e6,
                            f"per_element_us={dt/k*1e6:.1f}"))
        if verbose:
            print(rows[-1])

    s = stochastic_candidate_count(2048, 205, 0.01)
    dt = _timeit(lambda: stochastic_greedy(
        facility_location, K, 205, jax.random.PRNGKey(1), s=s
    ).indices.block_until_ready())
    rows.append(csv_row("preprocess/stochastic_greedy_n2048_k205", dt * 1e6,
                        f"candidates_per_step={s}"))
    if verbose:
        print(rows[-1])
    del K

    _bench_sge_bank(rows, verbose, fast)
    _bench_lazy_importance(rows, verbose, fast)
    _bench_sharded(rows, verbose, fast)

    # Pallas gram-free FL kernel smoke (interpret mode off-TPU): exercises the
    # fused-similarity kernel on every benchmark run, including CI
    from repro.kernels import resolve_interpret
    from repro.kernels.fl_gains import ops as fl_ops

    interpret = resolve_interpret()
    zn = normalize_rows(_features(256, d=32))
    c = jnp.zeros((256,))
    dt = _timeit(lambda: jax.block_until_ready(
        fl_ops.fl_gains_gram_free(zn, zn[:128], c, block_i=128, block_j=128,
                                  interpret=interpret)
    ), reps=1)
    rows.append(csv_row("preprocess/fl_gains_gram_free_pallas_n256",
                        dt * 1e6, f"interpret={interpret} n_cand=128"))
    if verbose:
        print(rows[-1])
    return rows


if __name__ == "__main__":
    run()
