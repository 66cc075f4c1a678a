"""MILO orchestrator (paper Alg. 1): preprocessing + per-epoch subset serving.

``MiloPreprocessor.preprocess`` runs once per (dataset, k):
  1. class-wise partition of the feature matrix,
  2. per class: Gram matrix -> SGE with graph-cut (easy subsets bank),
  3. per class: full greedy with disparity-min -> importance -> Taylor-softmax
     probabilities (WRE),
  4. merge to global indices; persist as ``MiloMetadata``.

``MiloSelector`` consumes the metadata during training: given the epoch it
returns the subset indices dictated by the easy-to-hard curriculum.  Selection
cost during training is O(k) (a Gumbel top-k at WRE epochs; a table lookup at
SGE epochs) — the decoupling that gives the paper its 3-75x speedups.

New code should go through ``repro.selection`` — ``build_selector("milo",
metadata=..., ...)`` wraps this selector in the weighted ``SelectionPlan``
protocol, and ``MiloSession`` drives preprocess/train/tune end to end.  The
``indices_for_epoch`` entry point here is kept for backward compatibility.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.buckets import (
    BucketChunk,
    bucket_chunks,
    bucket_importance,
    bucket_kernels,
    bucket_sge,
    chunk_byte_limit,
    next_pow2,
    sge_key_chain,
)
from repro.core.greedy import (
    greedy,
    greedy_importance,
    refine as run_refine,
    sge as run_sge,
    stochastic_candidate_count,
)
from repro.core import gram_free as gram_free_mod, submodular
from repro.core.curriculum import CurriculumConfig
from repro.core.exploration import taylor_softmax, weighted_sample_without_replacement
from repro.core.metadata import MiloMetadata
from repro.core.partition import (
    Partition,
    PartitionStrategy,
    make_partition_strategy,
    merge_class_selections,
    partition_by_class,
    proportional_budgets,
)
from repro.core.similarity import gram_matrix_blocked, normalize_rows


def _normalize_probs(p: np.ndarray) -> np.ndarray:
    """Normalize to a distribution; degenerate mass (all-zero importance from
    singleton/degenerate classes, or NaN/inf from pathological features) falls
    back to uniform so WRE sampling stays well-defined."""
    p = np.where(np.isfinite(p), p, 0.0).astype(np.float32)
    p = np.maximum(p, 0.0)
    total = float(p.sum())
    if total <= 0.0:
        return np.full(p.shape, 1.0 / len(p), np.float32)
    return p / total


@dataclasses.dataclass
class MiloPreprocessor:
    """One-shot, model-agnostic pre-processing (paper §3.1-3.2)."""

    subset_fraction: float = 0.1
    n_sge_subsets: int = 8          # size of the easy-subset bank
    eps: float = 0.01               # stochastic-greedy epsilon (paper value)
    easy_fn: str = "graph_cut"      # SGE set function (paper: graph-cut)
    hard_fn: str = "disparity_min"  # WRE set function (paper: disparity-min)
    graph_cut_lambda: float = 0.4   # paper value
    classwise: bool = True
    metric: str = "cosine"
    gram_block: int = 2048
    use_pallas: bool = False        # route Gram tiles / FL gains through Pallas
    # Gram-free hot path: set functions contract features directly
    # (O(n·d + n) per-class memory) instead of materializing the (n², ) Gram.
    # Cosine metric only — the rescaled-cosine column is an O(n·d) matvec.
    gram_free: bool = False
    # Pad every per-class problem (ground-set size AND budget) to the next
    # power of two with exact masking, so the jitted greedy engines compile
    # once per bucket instead of once per distinct class size.
    bucket_classes: bool = True
    # Run the SGE bank as one vmapped XLA program (False = legacy per-run loop)
    sge_vmapped: bool = True
    # Shard the ground-set row axis of z across all local devices
    # (core.sharded): per-device memory drops to O(n·d / ndev + n) so one
    # class can exceed a single device.  Requires gram_free; classes whose
    # (padded) size does not divide the device count run the single-device
    # path — either way trajectories are identical to shard_selection=False.
    shard_selection: bool = False
    # Lazy gain reuse for the WRE full-greedy pass (facility-location hard
    # functions only): cache the gain vector and correct it over just the
    # rows whose cover the last pick moved, with a full recompute once the
    # touched fraction exceeds lazy_threshold.  Composes with
    # shard_selection: classes routed to the mesh run the same lazy engine
    # inside shard_map (sharded_greedy_importance(lazy_budget=...)), so the
    # largest classes get both the memory split AND the fewest-FLOPs path.
    # Near-ties below float32 rounding can resolve differently from the
    # eager pass (see greedy.lazy_greedy); importance is an equally valid
    # greedy order.
    lazy_gains: bool = False
    lazy_threshold: float = 0.125
    # Right-size each lazy gather to the smallest pow2 level covering the
    # touched rows instead of the full budget-sized block (bit-identical
    # trajectories; on the sharded path this shrinks the per-step psum
    # payload on calm steps at the cost of ~log2(budget) compiled variants).
    lazy_two_level: bool = False
    # Bucketed SGE draws its per-step candidate count s from the PADDED
    # problem geometry by default (one compile per bucket, documented
    # approximation).  True derives s from the class's true (n_c, k_c) —
    # the unpadded draw size — at no extra compile cost.
    exact_sge_candidates: bool = False
    # Input firewall policy run before any selection math (None = off):
    # "raise" refuses non-finite / zero-norm rows, "repair" fixes them
    # deterministically, "quarantine" excludes them from the ground set
    # and records the indices in provenance.  See repro.health.firewall.
    firewall: str | None = None
    # Level-0 ground-set decomposition (core.partition): "by_class" is the
    # paper's split and the provably-neutral default; "random_blocks" /
    # "balanced_blocks" bound per-partition memory so ground sets far past
    # one engine invocation's capacity still preprocess.
    partition: str = "by_class"
    partition_block: int = 4096     # block size for the block strategies
    partition_seed: int = 0         # random_blocks permutation seed
    # Level-1 refine: each partition contributes min(n_c, refine_factor*k_c)
    # SGE winners per bank slot and a greedy refine over the slot's union
    # (the easy_fn objective, lazy-routed like the WRE pass) cuts it back to
    # exactly k — the two-level scheme of Mirzasoleiman et al.  1 disables
    # the refine entirely: the flat path, bit-identical to pre-hierarchy
    # builds.
    refine_factor: int = 1

    def partition_strategy(self) -> PartitionStrategy:
        """The level-0 decomposition this preprocessor applies (see
        ``core.partition``); serving replays it to warm the exact per-
        partition geometries a future request will compile."""
        return make_partition_strategy(
            self.partition, block_size=self.partition_block,
            seed=self.partition_seed,
        )

    def artifact_identity(self, prep_seed: int | None = None) -> dict[str, Any]:
        """The stamped config keys that shape an artifact's bits, so must
        match before it is reused; ``prep_seed`` is the integer the draw's
        key was derived from.  Keys the stamp omits are omitted here too:
        ``lazy_threshold`` unless ``lazy_gains`` is on, ``firewall`` unless
        one is set, and the partition keys on the flat path (``by_class``,
        ``refine_factor`` 1).  ``core.metadata.check_identity`` compares an
        artifact against it."""
        ident: dict[str, Any] = dict(
            subset_fraction=self.subset_fraction,
            n_sge_subsets=self.n_sge_subsets,
            eps=self.eps,
            easy_fn=self.easy_fn,
            hard_fn=self.hard_fn,
            graph_cut_lambda=self.graph_cut_lambda,
            classwise=self.classwise,
            metric=self.metric,
            gram_free=self.gram_free,
            bucket_classes=self.bucket_classes,
            lazy_gains=self.lazy_gains,
            exact_sge_candidates=self.exact_sge_candidates,
        )
        if self.lazy_gains:
            ident["lazy_threshold"] = self.lazy_threshold
        if self.firewall is not None:
            ident["firewall"] = self.firewall
        strategy, rf = self.partition_strategy(), max(1, int(self.refine_factor))
        if strategy.name != "by_class" or rf > 1:
            ident.update(strategy.config(), refine_factor=rf)
        ident["prep_seed"] = prep_seed
        return ident

    def _sharded_set_fn(self, name: str, mesh) -> submodular.SetFunction:
        from repro.core import sharded as sharded_mod

        kwargs = {}
        if name == "graph_cut":
            kwargs["lam"] = self.graph_cut_lambda
        if name == "facility_location":
            kwargs["use_pallas"] = self.use_pallas
        return sharded_mod.make_sharded_gram_free(
            name, n_shards=mesh.shape[sharded_mod.AXIS], **kwargs
        )

    def _lazy_budget(self, n_run: int, fn: submodular.SetFunction) -> int | None:
        """Touched-rows budget for the WRE full-greedy pass, or None when
        lazy gains are off / the set function has no lazy hooks / the
        threshold would not save anything."""
        if not self.lazy_gains or fn.lazy is None:
            return None
        budget = max(1, int(n_run * self.lazy_threshold))
        return None if budget >= n_run else budget

    def _set_fn(self, name: str) -> submodular.SetFunction:
        if self.gram_free:
            if name == "graph_cut":
                return gram_free_mod.make_gram_free_graph_cut(self.graph_cut_lambda)
            if name == "facility_location":
                # the kernels compile on the TPU and are interpreted on the
                # CPU (repro.kernels.resolve_interpret)
                return gram_free_mod.make_gram_free_facility_location(
                    use_pallas=self.use_pallas)
            return gram_free_mod.get_gram_free(name)
        if name == "graph_cut":
            return submodular.make_graph_cut(self.graph_cut_lambda)
        return submodular.get(name)

    def _class_selection(
        self, subs: np.ndarray, imp: np.ndarray, n_c: int, k_c: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """One partition's ``(n_sge_subsets, k_c)`` local-index bank and
        ``(n_c,)`` importance vector, cut back from the engines' padded
        outputs (``(n_sge_subsets, k_run)`` and ``(n_pad,)``) on either
        route.  Kept as a method of its own because it is the one place
        both routes hand back a partition's importances: the benchmark's
        fault tests (``tests/bench/``) plant their faults here."""
        return subs[:, :k_c].astype(np.int64), imp[:n_c]

    def _partition_engines(
        self,
        feats_c: np.ndarray,
        k_c: int,
        k_sge: jax.Array,
        *,
        bucket: bool,
        mesh,
        easy: submodular.SetFunction,
        hard: submodular.SetFunction,
        easy_sh: submodular.SetFunction | None,
        hard_sh: submodular.SetFunction | None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """SGE bank + WRE importance for one partition, the per-partition
        route.

        ``feats_c`` is the partition's (n_c, d) feature slice, on the host
        or already on the device; returns the padded bank and importances
        on the host (see ``_class_selection``).  ``warmup`` replays this
        exact path on dummy features, so every engine/transform program it
        compiles is the one preprocess will hit.  The ``milo.*`` spans time
        the host: the engine spans cover the dispatch only, each
        ``milo.fetch`` the wait for a result.
        """
        n_c = len(feats_c)
        z = jnp.asarray(feats_c)
        with TraceAnnotation("milo.gram"):
            if self.gram_free:
                # the "kernel" threaded through the greedy engines is the
                # row-normalized feature matrix itself: O(n·d), no Gram
                A = normalize_rows(z.astype(jnp.float32))
            else:
                A = gram_matrix_blocked(
                    z, metric=self.metric, block=self.gram_block,
                    use_pallas=self.use_pallas,
                )
            valid = None
            k_run = k_c
            n_run = n_c
            if bucket:
                # Pad the problem (ground set AND budget) to the next
                # power of two: the jit cache then keys on O(log²)
                # distinct (bucket, k_run) pairs instead of every class
                # size.  Masking is exact — padded elements start
                # pre-selected and padded rows contribute nothing (zero
                # Gram rows / +inf FL cover) — so DETERMINISTIC runs
                # (full greedy -> WRE importance) match the unpadded run
                # bit-for-bit.  The STOCHASTIC SGE draws use the padded
                # candidate geometry (s and the per-step key split come
                # from n_pad/k_run), so for a fixed seed the bank differs
                # from an unbucketed run — a different but equally valid
                # stochastic-greedy sample (see ROADMAP perf follow-ups).
                n_pad = next_pow2(n_c)
                k_run = min(n_pad, next_pow2(k_c))
                if n_pad > n_c:
                    pad = ((0, n_pad - n_c), (0, 0)) if self.gram_free else (
                        (0, n_pad - n_c), (0, n_pad - n_c))
                    A = jnp.pad(A, pad)
                valid = jnp.arange(n_pad) < n_c
                n_run = n_pad
        # exact_sge_candidates: derive the stochastic-greedy draw
        # size from the class's true geometry instead of the padded
        # bucket's (identical when unbucketed)
        s_sge = (
            stochastic_candidate_count(n_c, k_c, self.eps)
            if self.exact_sge_candidates else None
        )
        # The sharded path needs the (padded) row count to divide the
        # mesh; pow2 buckets always do on a pow2 mesh, tiny/odd
        # classes fall back to the trajectory-identical local path.
        from repro.core import sharded as sharded_mod

        shard_ok = mesh is not None and n_run % mesh.size == 0
        with TraceAnnotation("milo.sge"):
            if shard_ok:
                subs = sharded_mod.sharded_sge(
                    easy_sh, A, k_run, k_sge, n_subsets=self.n_sge_subsets,
                    eps=self.eps, s=s_sge, mesh=mesh, valid=valid,
                )
            else:
                subs = run_sge(
                    easy, A, k_run, k_sge, n_subsets=self.n_sge_subsets,
                    eps=self.eps, vmapped=self.sge_vmapped, valid=valid,
                    s=s_sge,
                )
        with TraceAnnotation("milo.wre"):
            if shard_ok:
                # lazy + sharded compose: the mesh classes run the same
                # cached-gain engine inside shard_map instead of silently
                # falling back to eager ring gains
                imp_full = sharded_mod.sharded_greedy_importance(
                    hard_sh, A, mesh=mesh, valid=valid,
                    lazy_budget=self._lazy_budget(n_run, hard_sh),
                    lazy_two_level=self.lazy_two_level,
                )
            else:
                imp_full = greedy_importance(
                    hard, A, valid=valid,
                    lazy_budget=self._lazy_budget(n_run, hard),
                    lazy_two_level=self.lazy_two_level,
                )
        with TraceAnnotation("milo.fetch", bytes=subs.nbytes):
            subs = np.asarray(subs)
        with TraceAnnotation("milo.fetch", bytes=imp_full.nbytes):
            imp_full = np.asarray(imp_full, np.float32)
        return subs, imp_full

    def _refine_indices(
        self, feats_u: np.ndarray, k: int, mesh, easy, easy_sh
    ) -> np.ndarray:
        """Level-1 pass: exact greedy (easy_fn objective) over the union of
        level-0 winners, lazy-routed and mesh-dispatched exactly like the
        per-partition engines.  Returns local indices into ``feats_u``."""
        from repro.core import sharded as sharded_mod

        n_u = feats_u.shape[0]
        z = jnp.asarray(feats_u)
        if self.gram_free:
            A = normalize_rows(z.astype(jnp.float32))
        else:
            A = gram_matrix_blocked(
                z, metric=self.metric, block=self.gram_block,
                use_pallas=self.use_pallas,
            )
        shard_ok = mesh is not None and n_u % mesh.size == 0
        if shard_ok:
            res = sharded_mod.sharded_refine(
                easy_sh, A, k, mesh=mesh,
                lazy_budget=self._lazy_budget(n_u, easy_sh),
                lazy_two_level=self.lazy_two_level,
            )
        else:
            res = run_refine(
                easy, A, k, lazy_budget=self._lazy_budget(n_u, easy),
                two_level=self.lazy_two_level,
            )
        with TraceAnnotation("milo.fetch", bytes=res.indices.nbytes):
            return np.asarray(res.indices, np.int64)

    def _refine_bank(
        self,
        features: np.ndarray,
        parts: Sequence[Partition],
        per_class_sge: Sequence[np.ndarray],
        k: int,
        mesh,
        easy,
        easy_sh,
    ) -> np.ndarray:
        """Cut each oversampled bank slot back down to exactly k.

        Every slot's union has the same size (Σ min(n_c, rf·k_c) — the
        per-partition bank widths are slot-independent), so the refine
        program compiles once and replays across the bank.
        """
        slots = []
        for i in range(self.n_sge_subsets):
            union = merge_class_selections(
                parts, [s[i] for s in per_class_sge]
            )
            if len(union) <= k:
                slots.append(union)
                continue
            local = self._refine_indices(
                features[union], k, mesh, easy, easy_sh
            )
            slots.append(union[local])
        return np.stack(slots, axis=0)

    def _selection_mesh(self):
        """(mesh, easy_sh, hard_sh) when shard_selection routes to a real
        multi-device mesh; (None, None, None) otherwise."""
        if not self.shard_selection:
            return None, None, None
        if not self.gram_free:
            raise ValueError(
                "shard_selection=True requires gram_free=True: only the "
                "feature-matrix row axis is shardable (a materialized "
                "Gram couples both axes)"
            )
        from repro.core import sharded as sharded_mod
        from repro.distributed.sharding import selection_mesh

        sel_mesh = selection_mesh(axis=sharded_mod.AXIS)
        if sel_mesh.shape[sharded_mod.AXIS] <= 1:
            return None, None, None
        return (
            sel_mesh,
            self._sharded_set_fn(self.easy_fn, sel_mesh),
            self._sharded_set_fn(self.hard_fn, sel_mesh),
        )

    def _plan(
        self,
        geoms: Sequence[tuple[int, int]],
        d: int,
        *,
        bucket: bool,
        mesh,
        hard: submodular.SetFunction,
    ) -> tuple[list[BucketChunk], list[int]]:
        """Route the partitions that have a budget: ``(chunks, loop)``.

        ``geoms`` holds each partition's ``(n_c, k_sel)``.  The plain route
        (bucketed, rescaled cosine, XLA set functions, eager gains, off the
        ``sel`` mesh) runs as batched chunks (``core.buckets``); the rest
        keep the per-partition loop, whose positions ``loop`` lists.  The
        lazy engine stays off the batched route: under ``vmap`` its
        ``lax.cond`` fallback becomes a select that pays the full recompute
        on every step.  ``preprocess`` and ``warmup`` both route here, so
        warmup compiles the chunk programs preprocess will run.
        """
        plain = bucket and self.metric == "cosine" and not self.use_pallas
        batched, loop = [], []
        for pos, (n_c, k_sel) in enumerate(geoms):
            if k_sel <= 0:
                continue
            n_pad = next_pow2(n_c)
            on_mesh = mesh is not None and n_pad % mesh.size == 0
            if plain and not on_mesh and self._lazy_budget(n_pad, hard) is None:
                batched.append((pos, n_c, k_sel))
            else:
                loop.append(pos)
        chunks = bucket_chunks(
            batched, d=d, gram_free=self.gram_free,
            byte_limit=chunk_byte_limit(), eps=self.eps,
            exact_s=self.exact_sge_candidates,
        ) if batched else []
        return chunks, loop

    def _run_chunk(
        self,
        features: np.ndarray,
        chunk: BucketChunk,
        parts: Sequence[Partition],
        keys: jax.Array,
        easy: submodular.SetFunction,
        hard: submodular.SetFunction,
    ) -> tuple[np.ndarray, np.ndarray, list[tuple[range, np.ndarray]]]:
        """One chunk on the device, read back in one ``milo.fetch``.

        ``features`` is the host feature matrix and ``keys`` every
        partition's SGE key.  Only the chunk's own rows go to the device,
        so its footprint is bounded by its budget whatever the matrix's
        size.  Returns the ``[P, n_subsets, k_run]`` banks, the ``[P,
        n_pad]`` importances, and per true size ``n_c`` the chunk rows of
        that size with their ``[rows, n_c]`` probabilities.
        """
        own = np.sort(np.concatenate([parts[i].indices
                                      for i in chunk.members]))
        rows = np.zeros((len(chunk.members), chunk.n_pad), np.int32)
        for r, (i, n_c) in enumerate(zip(chunk.members, chunk.sizes)):
            rows[r, :n_c] = np.searchsorted(own, parts[i].indices)
        # a chunk that reads every row puts the matrix as it is, uncopied;
        # a chunk of a split group gathers its own rows on the host
        if len(own) == len(features):
            src = features
        else:
            with TraceAnnotation("milo.gather",
                                 bytes=len(own) * features.shape[1] * 4):
                src = features[own]
        with TraceAnnotation("milo.put", bytes=src.size * 4):
            x = jnp.asarray(src, jnp.float32)
        with TraceAnnotation("milo.gram"):
            kern, valid = bucket_kernels(x, rows, sizes=chunk.sizes,
                                         gram_free=self.gram_free,
                                         block=self.gram_block)
        # the engines are the ones this module names, as on the
        # per-partition route
        with TraceAnnotation("milo.sge"):
            banks = bucket_sge(
                run_sge, easy, kern, valid, keys,
                np.asarray(chunk.members, np.int32), k=chunk.k_run,
                s=chunk.s, n_subsets=self.n_sge_subsets,
                vmapped=self.sge_vmapped,
            )
        with TraceAnnotation("milo.wre"):
            imp = bucket_importance(greedy_importance, hard, kern, valid)
        with TraceAnnotation("milo.softmax"):
            # the eager Taylor-softmax over the rows of one true size: it
            # rounds each row as the per-partition route does, where a
            # masked or fused softmax over the padded bucket does not
            probs, lo = [], 0
            for n_c, run in itertools.groupby(chunk.sizes):
                hi = lo + len(list(run))
                probs.append((range(lo, hi), taylor_softmax(imp[lo:hi, :n_c])))
                lo = hi
        out = [banks, imp] + [p for _, p in probs]
        with TraceAnnotation("milo.fetch", bytes=sum(a.nbytes for a in out)):
            banks, imp, *p_host = jax.device_get(out)
        return banks, imp, [(sel, p) for (sel, _), p in zip(probs, p_host)]

    def warmup(
        self,
        buckets: Sequence[tuple[int, int]],
        d: int,
        *,
        key: jax.Array | None = None,
    ) -> int:
        """Pre-compile the engine programs for the given class geometries.

        ``buckets`` holds every class's true ``(n_c, k_c)`` in the order an
        upcoming ``preprocess`` will see them (e.g. ``[(5000, 500)] * 10``
        for a balanced 10-class dataset); ``d`` is the feature dimension
        (float32, the dtype preprocess casts to).  The classes are routed as
        preprocess routes them (``_plan``) and each distinct batched chunk,
        and each distinct per-partition geometry, replays its path —
        bucketing, masking, engines, Taylor-softmax — on dummy features, so
        the jitted programs are compiled before any real data arrives and
        the subsequent ``preprocess()`` triggers zero backend compiles.
        Returns the number of distinct programs run; outputs are discarded.
        """
        if key is None:
            key = jax.random.PRNGKey(0)
        rf = max(1, int(self.refine_factor))
        bucket_list = [(int(n_c), int(k_c)) for n_c, k_c in buckets]
        # the per-partition engines run at the oversampled bank width
        geoms = [(n_c, min(n_c, rf * k_c)) for n_c, k_c in bucket_list]
        # mirror preprocess: bucketing only deduplicates across >1 partition
        bucket = self.bucket_classes and len(geoms) > 1
        easy = self._set_fn(self.easy_fn)
        hard = self._set_fn(self.hard_fn)
        mesh, easy_sh, hard_sh = self._selection_mesh()
        chunks, loop = self._plan(geoms, d, bucket=bucket, mesh=mesh, hard=hard)
        keys = sge_key_chain(key, len(geoms))
        rng = np.random.default_rng(0)
        seen: set[tuple] = set()
        if chunks:
            # a stand-in ground set of the real shape, classes laid end to
            # end: a chunk's programs take its members' rows
            ends = np.cumsum([n_c for n_c, _ in geoms])
            parts = [Partition(i, np.arange(end - n_c, end))
                     for i, ((n_c, _), end) in enumerate(zip(geoms, ends))]
            x = np.zeros((int(ends[-1]), d), np.float32)
            for chunk in chunks:
                sig = (chunk.n_pad, chunk.k_run, chunk.s, chunk.sizes)
                if sig not in seen:
                    seen.add(sig)
                    self._run_chunk(x, chunk, parts, keys, easy, hard)
        key_list = list(keys) if loop else []
        for pos in loop:
            n_c, k_sel = geoms[pos]
            if (n_c, k_sel) in seen:
                continue
            seen.add((n_c, k_sel))
            dummy = rng.normal(size=(n_c, d)).astype(np.float32)
            _, imp = self._partition_engines(
                dummy, k_sel, key_list[pos], bucket=bucket, mesh=mesh,
                easy=easy, hard=hard, easy_sh=easy_sh, hard_sh=hard_sh,
            )
            imp = imp[:n_c]
            # preprocess follows every class selection with a within-class
            # Taylor-softmax on the (n_c,)-shaped importance — warm it too
            jax.block_until_ready(taylor_softmax(jnp.asarray(imp)))
        if rf > 1:
            # warm the level-1 refine program on the exact union geometry
            # preprocess will hit: Σ min(n_c, rf·k_c) winner rows cut to k
            n_union = sum(min(n_c, rf * k_c)
                          for n_c, k_c in bucket_list if k_c > 0)
            k_total = sum(k_c for n_c, k_c in bucket_list if k_c > 0)
            if 0 < k_total < n_union:
                dummy = rng.normal(size=(n_union, d)).astype(np.float32)
                self._refine_indices(dummy, k_total, mesh, easy, easy_sh)
        return len(seen)

    def preprocess(
        self,
        features: np.ndarray,
        labels: np.ndarray | None,
        key: jax.Array,
        *,
        encoder_id: str = "precomputed",
        prep_seed: int | None = None,
    ) -> MiloMetadata:
        """``prep_seed`` is provenance only: the integer the caller derived
        ``key`` from, recorded in the artifact config so reuse checks can
        tell two stochastic-greedy draws apart.

        With ``firewall`` set, the ground set is screened first
        (``repro.health.validate_features``) and the resulting
        ``DataHealthReport`` is stamped into the artifact config under
        ``data_health``.  Under the ``quarantine`` policy the flagged rows
        are excluded from selection entirely: ``k`` is computed over the
        surviving rows, quarantined rows get zero WRE probability and can
        never appear in an SGE subset, and their indices are recorded in
        provenance.
        """
        with TraceAnnotation("milo.preprocess") as span:
            features = np.asarray(features)
            report = None
            if self.firewall is not None:
                from repro.health.firewall import validate_features

                features, report = validate_features(
                    features, labels, policy=self.firewall,
                    subset_fraction=self.subset_fraction,
                    # overbudget detection mirrors the decomposition selection
                    # will actually use (classwise off -> single catch-all)
                    strategy=(self.partition_strategy() if self.classwise
                              else None),
                )
            quarantined = report.quarantined_rows if report is not None else []
            if quarantined:
                m = features.shape[0]
                labels_full = (
                    None if labels is None else np.asarray(labels, np.int64))
                keep = np.setdiff1d(
                    np.arange(m, dtype=np.int64),
                    np.asarray(quarantined, np.int64),
                )
                md = self._preprocess_clean(
                    features[keep],
                    None if labels_full is None else labels_full[keep],
                    key, encoder_id=encoder_id, prep_seed=prep_seed,
                    span=span,
                )
                md = self._lift_quarantined(md, keep, m, labels_full)
            else:
                md = self._preprocess_clean(
                    features, labels, key,
                    encoder_id=encoder_id, prep_seed=prep_seed, span=span,
                )
            if report is not None:
                md.config["data_health"] = report.to_dict()
            span.set_metadata(partitions=len(md.class_budgets))
        return md

    @staticmethod
    def _lift_quarantined(
        md: MiloMetadata,
        keep: np.ndarray,
        m: int,
        labels_full: np.ndarray | None,
    ) -> MiloMetadata:
        """Re-index an artifact built over ``features[keep]`` back to the
        full ground set: bank indices map through ``keep``, probabilities
        and importance scatter into zeros at the quarantined rows (which
        therefore can never be drawn)."""
        probs = np.zeros((m,), np.float32)
        probs[keep] = md.wre_probs
        imp = np.zeros((m,), np.float32)
        imp[keep] = md.wre_importance
        return MiloMetadata(
            sge_subsets=keep[md.sge_subsets],
            wre_probs=probs,
            wre_importance=imp,
            class_labels=(labels_full if labels_full is not None
                          else np.zeros((m,), np.int64)),
            class_budgets=md.class_budgets,
            config=md.config,
        )

    def _preprocess_clean(
        self,
        features: np.ndarray,
        labels: np.ndarray | None,
        key: jax.Array,
        *,
        encoder_id: str = "precomputed",
        prep_seed: int | None = None,
        span: TraceAnnotation | None = None,
    ) -> MiloMetadata:
        features = np.asarray(features)
        if self.gram_free and self.metric != "cosine":
            raise ValueError(
                f"gram_free preprocessing supports metric='cosine' only "
                f"(got {self.metric!r}); the gram-free set functions rebuild "
                "rescaled-cosine columns from features on the fly"
            )
        m = features.shape[0]
        k = max(1, int(round(self.subset_fraction * m)))
        strategy = self.partition_strategy()
        labels_arr = (np.zeros((m,), np.int64) if labels is None
                      else np.asarray(labels, np.int64))
        # label-free strategies (random_blocks) ignore the labels argument;
        # by_class without labels / classwise yields the single catch-all
        # partition — exactly the historical flat behaviour
        parts = strategy.partition(
            None if labels is None or not self.classwise else labels_arr, m
        )
        budgets = proportional_budgets(parts, k)
        rf = max(1, int(self.refine_factor))
        # oversampled per-partition bank widths (== budgets when rf == 1)
        sel_widths = [min(len(p.indices), rf * b)
                      for p, b in zip(parts, budgets)]

        easy = self._set_fn(self.easy_fn)
        hard = self._set_fn(self.hard_fn)
        # Bucketing exists to deduplicate compiles across many class shapes;
        # with a single partition there is exactly one shape, so padding
        # would only inflate the problem (up to 4x Gram memory, 2x steps).
        bucket = self.bucket_classes and len(parts) > 1
        mesh, easy_sh, hard_sh = self._selection_mesh()

        geoms = [(len(p.indices), w) for p, w in zip(parts, sel_widths)]
        chunks, loop = self._plan(geoms, features.shape[1], bucket=bucket,
                                  mesh=mesh, hard=hard)
        # every partition's SGE key, the chain of splits a loop over the
        # partitions would draw, in one call
        keys = sge_key_chain(key, len(parts))
        per_class_sge: list[np.ndarray] = [  # each (n_subsets, k_c) local idx
            np.zeros((self.n_sge_subsets, 0), np.int64) for _ in parts]
        wre_probs = np.zeros((m,), np.float32)
        wre_importance = np.zeros((m,), np.float32)
        for part, (n_c, k_sel) in zip(parts, geoms):
            if k_sel <= 0 < n_c:
                # no selection: zero importance, whose Taylor-softmax is
                # uniform within the partition
                p_local = np.full((n_c,), np.float32(1.0) / np.float32(n_c))
                wre_probs[part.indices] = p_local * (n_c / m)

        for chunk in chunks:
            with TraceAnnotation("milo.bucket", partitions=len(chunk.members),
                                 n_pad=chunk.n_pad, k_run=chunk.k_run):
                banks, imp, probs = self._run_chunk(features, chunk, parts,
                                                    keys, easy, hard)
                for r, (i, n_c) in enumerate(zip(chunk.members, chunk.sizes)):
                    per_class_sge[i], wre_importance[parts[i].indices] = (
                        self._class_selection(banks[r], imp[r], n_c,
                                              sel_widths[i]))
                for sel, p in probs:
                    for j, r in enumerate(sel):
                        idx = parts[chunk.members[r]].indices
                        wre_probs[idx] = p[j] * (len(idx) / m)

        key_list = list(keys) if loop else []
        for pos in loop:
            part, k_sel = parts[pos], sel_widths[pos]
            n_c = len(part.indices)
            with TraceAnnotation("milo.partition", n_c=n_c, k_c=k_sel):
                with TraceAnnotation("milo.put",
                                     bytes=n_c * features[0].nbytes):
                    feats_c = jnp.asarray(features[part.indices])
                subs, imp = self._class_selection(*self._partition_engines(
                    feats_c, k_sel, key_list[pos], bucket=bucket,
                    mesh=mesh, easy=easy, hard=hard,
                    easy_sh=easy_sh, hard_sh=hard_sh,
                ), n_c, k_sel)
                per_class_sge[pos] = subs
                with TraceAnnotation("milo.softmax"):
                    wre_importance[part.indices] = imp
                    # Within-class Taylor-softmax, weighted by class mass so
                    # the global vector is a proper distribution with
                    # stratified expectation.
                    p_dev = taylor_softmax(jnp.asarray(imp))
                    with TraceAnnotation("milo.fetch", bytes=p_dev.nbytes):
                        p_local = np.asarray(p_dev, np.float32)
                    wre_probs[part.indices] = p_local * (n_c / m)
        if span is not None:
            span.set_metadata(batched_partitions=sum(
                len(c.members) for c in chunks), chunks=len(chunks))

        with TraceAnnotation("milo.merge"):
            wre_probs = _normalize_probs(wre_probs)
            if rf > 1:
                # level-1: each slot's oversampled union refined down to k
                sge_subsets = self._refine_bank(
                    features, parts, per_class_sge, k, mesh, easy, easy_sh
                )
            else:
                sge_subsets = np.stack(
                    [
                        merge_class_selections(
                            parts, [s[i] for s in per_class_sge])
                        for i in range(self.n_sge_subsets)
                    ],
                    axis=0,
                )
        # the identity plus keys kept for provenance only: lazy_threshold
        # shapes the bits only under lazy gains, and sharded and two-level
        # runs select identically, so artifacts stay portable across them
        config = {
            "k": int(sge_subsets.shape[1]),
            "lazy_threshold": self.lazy_threshold,
            "lazy_two_level": self.lazy_two_level,
            "shard_selection": self.shard_selection,
            "encoder_id": encoder_id,
            **self.artifact_identity(prep_seed),
        }
        return MiloMetadata(
            sge_subsets=sge_subsets,
            wre_probs=wre_probs,
            wre_importance=wre_importance,
            class_labels=labels_arr,
            class_budgets=np.asarray(budgets, np.int64),
            config=config,
        )


@dataclasses.dataclass
class MiloSelector:
    """Per-epoch subset server driven by the curriculum (paper Alg. 1)."""

    metadata: MiloMetadata
    curriculum: CurriculumConfig
    seed: int = 0

    def __post_init__(self):
        self._cache_epoch: int = -1
        self._cache: np.ndarray | None = None

    @property
    def k(self) -> int:
        return self.metadata.k

    def indices_for_epoch(self, epoch: int) -> np.ndarray:
        """Subset (global indices) to train on at ``epoch``.

        Deterministic in (seed, epoch) so fault-tolerant restarts replay the
        identical data order (see distributed/fault_tolerance.py).
        """
        if epoch == self._cache_epoch and self._cache is not None:
            return self._cache
        cur = self.curriculum
        if cur.phase(epoch) == "sge":
            slot = (epoch // cur.R) % self.metadata.sge_subsets.shape[0]
            idx = self.metadata.sge_subsets[slot]
        else:
            # One fresh WRE draw per R-epoch window, keyed by (seed, window).
            window = (epoch - cur.sge_epochs) // cur.R
            key = jax.random.fold_in(jax.random.PRNGKey(self.seed), window)
            idx = np.asarray(
                weighted_sample_without_replacement(
                    key, jnp.asarray(self.metadata.wre_probs), self.k
                ),
                np.int64,
            )
        self._cache_epoch, self._cache = epoch, idx
        return idx


def _hier_kernel(
    feats: np.ndarray,
    n_pad: int,
    *,
    gram_free: bool,
    metric: str,
    gram_block: int,
    use_pallas: bool,
    pre_normalized: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(engine kernel, valid mask) for one partition, padded to ``n_pad``.

    Padding keeps every partition on ONE compiled greedy program (shapes
    (n_pad, ·) regardless of the true slice size); masking is exact, so the
    first n-valid picks equal the unpadded run's.  The mask is always
    materialized — an all-true mask is bit-equivalent to ``valid=None`` and
    keeps the jit input pytree static across equal- and under-sized
    partitions.
    """
    z = jnp.asarray(feats, jnp.float32)
    n = z.shape[0]
    if gram_free:
        A = z if pre_normalized else normalize_rows(z)
        if n_pad > n:
            A = jnp.pad(A, ((0, n_pad - n), (0, 0)))
    else:
        A = gram_matrix_blocked(z, metric=metric, block=gram_block,
                                use_pallas=use_pallas)
        if n_pad > n:
            A = jnp.pad(A, ((0, n_pad - n), (0, n_pad - n)))
    return A, jnp.arange(n_pad) < n


def _two_level_select(
    features: np.ndarray,
    k: int,
    parts: Sequence[Partition],
    budgets: Sequence[int],
    rf: int,
    fn: submodular.SetFunction,
    *,
    gram_free: bool,
    metric: str = "cosine",
    gram_block: int = 2048,
    use_pallas: bool = False,
    lazy_threshold: float | None = 0.125,
    pre_normalized: bool = False,
) -> tuple[np.ndarray, dict]:
    """Shared partition-then-refine driver (deterministic greedy both levels).

    Level 0: exact greedy inside every partition, oversampled to
    ``min(n_c, rf·k_c)`` winners; level 1: ``greedy.refine`` over the union
    of winners down to exactly ``k``.  Peak memory is O(n_max·d) gram-free
    (O(n_max²) with a materialized Gram) — the partition size, not the
    ground-set size.
    """
    kern = dict(gram_free=gram_free, metric=metric, gram_block=gram_block,
                use_pallas=use_pallas, pre_normalized=pre_normalized)
    active = [(p, b) for p, b in zip(parts, budgets)
              if b > 0 and len(p.indices) > 0]
    if not active:
        return np.zeros((0,), np.int64), {
            "n_partitions": len(parts), "union_size": 0,
            "peak_partition_rows": 0, "refine_factor": rf,
        }
    k_sels = [min(len(p.indices), rf * b) for p, b in active]
    n_max = max(len(p.indices) for p, _ in active)
    k_max = max(k_sels)
    winners = []
    for (p, _), k_sel in zip(active, k_sels):
        A, valid = _hier_kernel(features[p.indices], n_max, **kern)
        res = greedy(fn, A, k_max, valid=valid, n=n_max)
        # first k_sel picks of the padded run == the unpadded run's picks
        local = np.asarray(res.indices, np.int64)[:k_sel]
        winners.append(np.asarray(p.indices, np.int64)[local])
    union = np.concatenate(winners)
    if len(union) > k:
        n_u = len(union)
        A, valid = _hier_kernel(features[union], n_u, **kern)
        lazy_budget = None
        if lazy_threshold is not None and fn.lazy is not None:
            b = max(1, int(n_u * lazy_threshold))
            lazy_budget = b if b < n_u else None
        res = run_refine(fn, A, k, valid=valid, lazy_budget=lazy_budget)
        selected = union[np.asarray(res.indices, np.int64)]
    else:
        selected = union
    info = {
        "n_partitions": len(parts),
        "union_size": int(len(union)),
        "peak_partition_rows": int(n_max),
        "refine_factor": rf,
    }
    return selected, info


def hierarchical_select(
    features: np.ndarray,
    k: int,
    *,
    labels: np.ndarray | None = None,
    partition: str | PartitionStrategy = "random_blocks",
    block_size: int = 4096,
    seed: int = 0,
    refine_factor: int = 2,
    fn_name: str = "facility_location",
    gram_free: bool = True,
    metric: str = "cosine",
    gram_block: int = 2048,
    use_pallas: bool = False,
    graph_cut_lambda: float = 0.4,
    lazy_threshold: float | None = 0.125,
    return_info: bool = False,
):
    """One-shot hierarchical subset selection (partition → greedy → refine).

    The deterministic two-level scheme: a :class:`PartitionStrategy` splits
    the ground set, exact greedy picks ``refine_factor·k_c`` winners inside
    each partition (one compiled program for the whole sweep — partitions
    are padded to the largest), and a level-1 ``greedy.refine`` over the
    union returns exactly ``k`` global indices.  With FL and enough
    oversampling the objective stays within a few percent of the exact flat
    greedy (asserted ≥ 0.95× in tests) while peak memory tracks the
    *partition* size — ground sets of 2^20+ rows select on hardware where
    the flat pass cannot even hold its init.

    Returns the (k,) int64 global indices; with ``return_info=True`` also a
    dict of the run's geometry (partition count, union size, peak partition
    rows).
    """
    features = np.asarray(features)
    m = features.shape[0]
    k = max(0, min(int(k), m))
    if k == 0:
        empty = np.zeros((0,), np.int64)
        return (empty, {"n_partitions": 0, "union_size": 0,
                        "peak_partition_rows": 0,
                        "refine_factor": refine_factor}) if return_info else empty
    strategy = (partition if isinstance(partition, PartitionStrategy)
                else make_partition_strategy(partition, block_size=block_size,
                                             seed=seed))
    parts = strategy.partition(labels, m)
    budgets = proportional_budgets(parts, k)
    rf = max(1, int(refine_factor))
    pre = MiloPreprocessor(
        easy_fn=fn_name, gram_free=gram_free, metric=metric,
        gram_block=gram_block, use_pallas=use_pallas,
        graph_cut_lambda=graph_cut_lambda,
    )
    fn = pre._set_fn(fn_name)
    selected, info = _two_level_select(
        features, k, parts, budgets, rf, fn, gram_free=gram_free,
        metric=metric, gram_block=gram_block, use_pallas=use_pallas,
        lazy_threshold=lazy_threshold,
    )
    return (selected, info) if return_info else selected


def targeted_select(
    features: np.ndarray,
    queries: np.ndarray,
    k: int,
    *,
    labels: np.ndarray | None = None,
    partition: str | PartitionStrategy = "by_class",
    block_size: int = 4096,
    seed: int = 0,
    refine_factor: int = 4,
    return_info: bool = False,
):
    """Query-conditioned (SMI-style) targeted selection over partition winners.

    The auto-labeling / active-learning shape: ``queries`` holds a handful
    of exemplar embeddings of the slice you care about, and the objective is
    query facility location — f(S) = Σ_q max_{a∈S} sim(a, q) — so the subset
    *covers the queries*, not the ground set.  Both levels use the query
    objective: per-partition winners are the rows most relevant to the
    queries, and the level-1 refine trades them off globally.  Gram-free
    cosine only (the query gains are O(n·q) feature contractions).

    Returns the (k,) int64 global indices (plus the geometry dict with
    ``return_info=True``).
    """
    features = np.asarray(features)
    m = features.shape[0]
    k = max(0, min(int(k), m))
    if k == 0:
        empty = np.zeros((0,), np.int64)
        return (empty, {"n_partitions": 0, "union_size": 0,
                        "peak_partition_rows": 0,
                        "refine_factor": refine_factor}) if return_info else empty
    zn = np.asarray(normalize_rows(jnp.asarray(features, jnp.float32)))
    zq = np.asarray(normalize_rows(jnp.asarray(queries, jnp.float32)))
    fn = gram_free_mod.make_query_facility_location(zq)
    strategy = (partition if isinstance(partition, PartitionStrategy)
                else make_partition_strategy(partition, block_size=block_size,
                                             seed=seed))
    parts = strategy.partition(labels, m)
    budgets = proportional_budgets(parts, k)
    rf = max(1, int(refine_factor))
    selected, info = _two_level_select(
        zn, k, parts, budgets, rf, fn, gram_free=True, pre_normalized=True,
        lazy_threshold=None,
    )
    return (selected, info) if return_info else selected


def preprocess_with_encoder(
    encode_fn: Callable[[np.ndarray], np.ndarray],
    inputs: np.ndarray,
    labels: np.ndarray | None,
    key: jax.Array,
    *,
    batch_size: int = 256,
    encoder_id: str = "custom",
    **pre_kwargs,
) -> MiloMetadata:
    """Encode inputs in batches with a frozen encoder, then preprocess."""
    feats = []
    for lo in range(0, len(inputs), batch_size):
        feats.append(np.asarray(encode_fn(inputs[lo : lo + batch_size])))
    features = np.concatenate(feats, axis=0)
    pre = MiloPreprocessor(**pre_kwargs)
    return pre.preprocess(features, labels, key, encoder_id=encoder_id)
