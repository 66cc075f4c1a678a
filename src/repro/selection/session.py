"""``MiloSession`` — the one-call facade for the paper's workflow.

One config object drives the whole decoupled pipeline::

    session = MiloSession(MiloSessionConfig(subset_fraction=0.1,
                                            total_epochs=40,
                                            metadata_path="/tmp/milo.npz"))
    session.preprocess(features, labels)        # once per (dataset, k)
    r1 = session.train(features, labels, test_x=tx, test_y=ty)
    r2 = session.train(features, labels, test_x=tx, test_y=ty, seed=1)
    best = session.tune(features, labels, vx, vy, space={...})

``preprocess`` runs the model-agnostic stage (or loads a saved artifact whose
config hash matches — the "train multiple models at no additional cost"
claim); ``train`` wires a registry-built selector into ``Pipeline`` +
``Trainer`` with plan weights flowing into the loss; ``tune`` drives the
Hyperband tuner over the same machinery.  The downstream model here is the
CPU-scale MLP classifier used throughout the benchmarks (the paper's setting:
frozen-encoder features + an arbitrary downstream model).
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.metadata import MetadataMismatchError, MiloMetadata, is_preprocessed
from repro.distributed import multihost
from repro.core.milo import MiloPreprocessor
from repro.data import pipeline as pipeline_mod
from repro.models.classifier import accuracy, init_mlp, nesterov_update, weighted_nll
from repro.selection.base import Selector
from repro.selection.registry import build_selector, selector_entry
from repro.train.trainer import Trainer, TrainerConfig
from repro.tuning.tuner import (
    HyperbandResult,
    RandomSearch,
    TPESearch,
    hyperband,
    subset_objective,
)

def _float32_rows(features: np.ndarray) -> np.ndarray:
    """The matrix the fingerprint hashes: float32, C-ordered (a copy only
    where the input is not already both)."""
    return np.ascontiguousarray(np.asarray(features, np.float32))


def _digest(a: np.ndarray) -> str:
    """SHA-256 of a C-ordered array's buffer, read in place: the same
    digest as of ``a.tobytes()``, without that copy."""
    return hashlib.sha256(a).hexdigest()[:16]


def _data_fingerprint(features: np.ndarray) -> str:
    """Cheap content identity for a feature matrix (same config + same
    length is not enough to prove an artifact belongs to this data)."""
    a = _float32_rows(features)
    with jax.profiler.TraceAnnotation("milo.fingerprint", bytes=a.nbytes):
        return _digest(a)


def _timed_fingerprint(features: np.ndarray) -> tuple[str, int, int]:
    """``_data_fingerprint`` without its span, for a worker thread: the
    digest, the bytes hashed and the worker's nanoseconds (the conversion
    to float32, where one is needed, and the hash)."""
    t0 = time.perf_counter_ns()
    a = _float32_rows(features)
    return _digest(a), a.nbytes, time.perf_counter_ns() - t0


def _run_while_fingerprinting(features: np.ndarray, work) -> tuple[Any, str]:
    """``(work(), _data_fingerprint(features))``, the hash on a host thread
    while ``work`` runs on this one.

    ``hashlib`` releases the interpreter lock over a large buffer, so the
    hash takes another core while ``work`` dispatches the device's programs
    and waits on them.  ``milo.fingerprint`` opens on this thread around
    the wait for the digest: its idle is the part of the hash that ``work``
    did not hide, and ``hash_us`` is the worker's own time.  The worker
    opens no ``milo.*`` span.  Leaving the executor joins the worker, so no
    thread outlives the call, whichever side raises."""
    with ThreadPoolExecutor(max_workers=1) as pool:
        pending = pool.submit(_timed_fingerprint, features)
        out = work()
        with jax.profiler.TraceAnnotation("milo.fingerprint") as span:
            digest, nbytes, hash_ns = pending.result()
            span.set_metadata(bytes=nbytes, hash_us=hash_ns // 1000)
    return out, digest


#: config keys that must match when reusing a saved preprocessing artifact
_PREPROCESS_KEYS = (
    "subset_fraction", "n_sge_subsets", "eps", "easy_fn", "hard_fn",
    "graph_cut_lambda", "classwise", "metric",
)


@dataclasses.dataclass
class MiloSessionConfig:
    """Everything the session needs, in one object."""

    # selection strategy (a repro.selection registry name)
    selector: str = "milo"
    # preprocessing (MiloPreprocessor knobs)
    subset_fraction: float = 0.1
    n_sge_subsets: int = 8
    eps: float = 0.01
    easy_fn: str = "graph_cut"
    hard_fn: str = "disparity_min"
    graph_cut_lambda: float = 0.4
    classwise: bool = True
    metric: str = "cosine"
    gram_block: int = 2048
    use_pallas: bool = False
    # preprocessing hot-path knobs (see MiloPreprocessor): gram-free FL/set
    # functions (O(n·d) per-class memory), power-of-two class-size bucketing
    # (one compile per bucket), vmapped SGE bank (one XLA program per class)
    gram_free: bool = False
    bucket_classes: bool = True
    sge_vmapped: bool = True
    # multi-device row-sharded selection (requires gram_free; trajectories
    # identical to single-device, so artifacts stay portable across meshes)
    shard_selection: bool = False
    # lazy gain reuse for the WRE full-greedy pass + its full-recompute
    # threshold (fraction of touched rows); FL hard functions only.
    # Composes with shard_selection: mesh-routed classes run the cached-gain
    # engine inside shard_map (see core.sharded.sharded_lazy_greedy)
    lazy_gains: bool = False
    lazy_threshold: float = 0.125
    # right-size lazy gathers to pow2 levels (bit-identical; shrinks the
    # sharded psum payload on calm steps — see MiloPreprocessor)
    lazy_two_level: bool = False
    # bucketed SGE candidate counts from the true class geometry instead of
    # the padded bucket's (changes the stochastic draws; see MiloPreprocessor)
    exact_sge_candidates: bool = False
    # input firewall policy screening the ground set before preprocessing
    # (None = off): "raise" | "repair" | "quarantine" — see
    # repro.health.firewall.  Recorded in artifact provenance (data_health).
    firewall: str | None = None
    # hierarchical partition-then-refine selection (see core.partition /
    # MiloPreprocessor): level-0 decomposition strategy ("by_class" is the
    # paper's flat path), block size + permutation seed for the block
    # strategies, and the level-1 oversampling factor (1 = refine off).
    # Stamped into artifact provenance and enforced on reuse whenever the
    # hierarchical path is active.
    partition: str = "by_class"
    partition_block: int = 4096
    partition_seed: int = 0
    refine_factor: int = 1
    # degraded-mode selection: selector names to fall back to (in order)
    # when the primary hits degenerate math (e.g. ("adaptive_random",)).
    # Every hop is recorded in plan provenance — see repro.health.fallback.
    selector_fallback: tuple[str, ...] = ()
    # curriculum
    total_epochs: int = 40
    kappa: float = 1.0 / 6.0
    R: int = 1
    seed: int = 0
    # preprocessing draw seed; None = reuse `seed`.  Kept separate so a
    # session tuning downstream seeds can still share one artifact (the
    # artifact is model-agnostic by design)
    prep_seed: int | None = None
    # device-resident fused training (train.engine): gather batches on
    # device from resident feature/label buffers and fuse `superstep` train
    # steps into one scan dispatch with the state donated.  Falls back to
    # the step loop automatically for pipelines without a column store.
    fused_training: bool = False
    superstep: int = 32
    # downstream classifier training
    lr: float = 0.05
    hidden: int = 64
    # classifier head width; None derives it from the train ∪ eval labels
    # seen by each train() call (train labels alone under-size the head when
    # a class never made it into the training split, and out-of-range eval
    # labels gather clipped logits under jit — silently wrong metrics)
    n_classes: int | None = None
    sub_steps: int = 4
    batch_size: int = 0          # 0 = one full-subset batch per epoch
    eval_every_epochs: int = 1
    # artifact persistence (enables cross-session / cross-model reuse)
    metadata_path: str | None = None
    # -- multi-host execution (distributed.multihost) -----------------------
    # initialize jax.distributed at session construction from the
    # MILO_COORDINATOR / MILO_NUM_PROCESSES / MILO_PROCESS_ID env triplet
    # (idempotent; a no-op when the env does not describe a multi-process
    # job).  After initialization jax.devices() is global, so
    # shard_selection's `sel` mesh — and every collective in core.sharded —
    # spans all hosts with no further knobs; trajectories are bit-identical
    # to a single process exposing the same logical device count.
    multihost_init: bool = False
    # host-liveness beacons for train(): every step boundary writes this
    # host's heartbeat and checks its peers'; a peer stale past the timeout
    # raises HostLossError so the launcher can re-mesh and resume from the
    # last globally-valid checkpoint.  The directory must be shared across
    # the job's hosts.  None = liveness off (single-process default).
    heartbeat_dir: str | None = None
    heartbeat_timeout: float = 60.0

    def preprocessor(self) -> MiloPreprocessor:
        return MiloPreprocessor(
            subset_fraction=self.subset_fraction,
            n_sge_subsets=self.n_sge_subsets,
            eps=self.eps,
            easy_fn=self.easy_fn,
            hard_fn=self.hard_fn,
            graph_cut_lambda=self.graph_cut_lambda,
            classwise=self.classwise,
            metric=self.metric,
            gram_block=self.gram_block,
            use_pallas=self.use_pallas,
            gram_free=self.gram_free,
            bucket_classes=self.bucket_classes,
            sge_vmapped=self.sge_vmapped,
            shard_selection=self.shard_selection,
            lazy_gains=self.lazy_gains,
            lazy_threshold=self.lazy_threshold,
            lazy_two_level=self.lazy_two_level,
            exact_sge_candidates=self.exact_sge_candidates,
            firewall=self.firewall,
            partition=self.partition,
            partition_block=self.partition_block,
            partition_seed=self.partition_seed,
            refine_factor=self.refine_factor,
        )

    def resolved_prep_seed(self) -> int:
        return self.seed if self.prep_seed is None else self.prep_seed

    def expected_artifact_config(self) -> dict[str, Any]:
        """The stored-config keys a reusable artifact must agree on."""
        return {k: getattr(self, k) for k in _PREPROCESS_KEYS}


@dataclasses.dataclass
class TrainReport:
    final_acc: float
    best_acc: float
    train_time: float
    steps: int
    history: list[dict]


class _ClassifierState(NamedTuple):
    params: dict
    mom: dict
    step: jax.Array
    lr0: jax.Array          # () f32 — traced so lr sweeps don't recompile
    total_steps: jax.Array  # () f32


def _init_classifier(
    key, d_in: int, n_classes: int, hidden: int, lr0: float, total_steps: int
) -> _ClassifierState:
    params = init_mlp(key, d_in, n_classes, hidden)
    mom = jax.tree.map(jnp.zeros_like, params)
    return _ClassifierState(
        params, mom, jnp.zeros((), jnp.int32),
        jnp.asarray(lr0, jnp.float32), jnp.asarray(total_steps, jnp.float32),
    )


# One jitted step per sub_steps value, shared across every train()/tune()
# call: lr and horizon live in the (traced) state, so a Hyperband lr sweep
# reuses one compiled executable per batch shape instead of recompiling
# every trial.
_STEP_CACHE: dict[int, Any] = {}


def _classifier_step_fn(sub_steps: int):
    """Weighted-CE Nesterov-SGD step with cosine decay; consumes the plan
    weights the pipeline injects into ``batch["weights"]``."""
    fn = _STEP_CACHE.get(sub_steps)
    if fn is not None:
        return fn

    def train_step(state: _ClassifierState, batch: dict):
        x, y = batch["x"], batch["y"]
        w = batch.get("weights")
        if w is None:
            w = jnp.ones(x.shape[:1], jnp.float32)
        frac = state.step.astype(jnp.float32) / jnp.maximum(state.total_steps - 1.0, 1.0)
        lr = state.lr0 * 0.5 * (1.0 + jnp.cos(jnp.pi * jnp.minimum(frac, 1.0)))

        def one(carry, _):
            params, mom = carry
            l, g = jax.value_and_grad(weighted_nll)(params, x, y, w)
            params, mom = nesterov_update(params, mom, g, lr)
            return (params, mom), l

        (params, mom), losses = jax.lax.scan(
            one, (state.params, state.mom), None, length=sub_steps
        )
        new = _ClassifierState(params, mom, state.step + 1, state.lr0, state.total_steps)
        return new, {"loss": losses[-1]}

    fn = _STEP_CACHE[sub_steps] = jax.jit(train_step)
    return fn




class MiloSession:
    """Facade over preprocess → (many) train → tune."""

    def __init__(
        self,
        config: MiloSessionConfig | None = None,
        *,
        buffer_registry: Any | None = None,
        **overrides: Any,
    ):
        if config is None:
            config = MiloSessionConfig(**overrides)
        elif overrides:
            config = dataclasses.replace(config, **overrides)
        if config.multihost_init:
            multihost.initialize()
        self.config = config
        self.metadata: MiloMetadata | None = None
        self.loaded_from_artifact = False
        # optional repro.serve.BufferRegistry: when attached, train() places
        # its feature/label columns through it, so N sessions over the same
        # dataset share one device buffer per column (fused path only)
        self.buffer_registry = buffer_registry

    # -- stage 1: model-agnostic preprocessing ------------------------------

    def preprocess(
        self,
        features: np.ndarray,
        labels: np.ndarray | None = None,
        *,
        force: bool = False,
        encoder_id: str = "precomputed",
    ) -> MiloMetadata:
        """Run (or load) the one-shot preprocessing pass.

        If ``metadata_path`` names an existing artifact whose config matches
        this session's preprocessing settings, it is loaded instead of
        recomputed — the amortization the paper's speedups rest on.  Pass
        ``force=True`` to recompute regardless.
        """
        cfg = self.config
        if not force and cfg.metadata_path and is_preprocessed(cfg.metadata_path):
            md = self._load_artifact(encoder_id, _data_fingerprint(features))
            if md.m != len(features):
                raise MetadataMismatchError(
                    f"{cfg.metadata_path}: artifact was preprocessed over "
                    f"{md.m} samples but this dataset has {len(features)} — "
                    "same config, different data; pass force=True to rebuild"
                )
            self.metadata = md
            self.loaded_from_artifact = True
            return self.metadata
        md = self.build_metadata(features, labels, encoder_id=encoder_id)
        if cfg.metadata_path:
            md.save(cfg.metadata_path)
        self.metadata = md
        self.loaded_from_artifact = False
        return md

    def build_metadata(
        self,
        features: np.ndarray,
        labels: np.ndarray | None = None,
        *,
        encoder_id: str = "precomputed",
        fingerprint: str | None = None,
    ) -> MiloMetadata:
        """The pure compute unit behind ``preprocess``: run the model-agnostic
        pass and return the stamped artifact WITHOUT touching session state or
        the configured ``metadata_path``.

        This is what a serving layer (``repro.serve.ArtifactStore``) calls as
        its build function — the store owns persistence and caching, so the
        session must not also write files or mutate ``self.metadata`` here.
        The data fingerprint is always stamped (callers may pass a
        precomputed one to skip rehashing the feature matrix); without one,
        the matrix is hashed on a host thread while the pass runs.
        """
        cfg = self.config
        prep_seed = cfg.resolved_prep_seed()

        def work():
            return cfg.preprocessor().preprocess(
                features, labels, jax.random.PRNGKey(prep_seed),
                encoder_id=encoder_id, prep_seed=prep_seed,
            )

        with jax.profiler.TraceAnnotation("milo.build", m=len(features),
                                          prep_seed=prep_seed):
            if fingerprint is None:
                md, fingerprint = _run_while_fingerprinting(features, work)
            else:
                md = work()
            md.config["data_fingerprint"] = fingerprint
        return md

    def adopt_metadata(
        self, md: MiloMetadata, *, loaded: bool = True
    ) -> MiloMetadata:
        """Install an externally owned artifact (e.g. one the serving layer's
        store built or reloaded) as this session's preprocessing result, after
        the same config verification a ``metadata_path`` load applies."""
        expected = self.config.expected_artifact_config()
        bad = {
            k: (md.config.get(k), v)
            for k, v in expected.items()
            if k in md.config and md.config.get(k) != v
        }
        if bad:
            raise MetadataMismatchError(
                f"adopted artifact: config mismatch on {bad} (stored, expected)"
            )
        stored_seed = md.config.get("prep_seed")
        expected_seed = self.config.resolved_prep_seed()
        if stored_seed is not None and stored_seed != expected_seed:
            raise MetadataMismatchError(
                "adopted artifact: config mismatch on "
                f"{{'prep_seed': ({stored_seed}, {expected_seed})}} "
                "(stored, expected)"
            )
        self._check_partition_config(md, "adopted artifact")
        self.metadata = md
        self.loaded_from_artifact = loaded
        return md

    def _check_partition_config(self, md: MiloMetadata, where: str) -> None:
        """Hierarchical provenance guard shared by artifact load and adopt.

        Partition keys are stamped only when the hierarchical path is active
        (see ``MiloPreprocessor._preprocess_clean``), so absence means the
        flat path: legacy flat artifacts keep loading into flat sessions,
        while any partition/refine disagreement — including a hierarchical
        session reading a flat artifact, whose bank was built over a
        different decomposition — refuses."""
        cfg = self.config
        stored_part = md.config.get("partition", "by_class")
        stored_rf = int(md.config.get("refine_factor", 1))
        want_rf = max(1, int(cfg.refine_factor))
        bad: dict[str, tuple] = {}
        if stored_part != cfg.partition:
            bad["partition"] = (stored_part, cfg.partition)
        if stored_rf != want_rf:
            bad["refine_factor"] = (stored_rf, want_rf)
        # block/seed are stamped only by the strategies that depend on them
        for key, want in (("partition_block", cfg.partition_block),
                          ("partition_seed", cfg.partition_seed)):
            if key in md.config and int(md.config[key]) != int(want):
                bad[key] = (md.config[key], want)
        if bad:
            raise MetadataMismatchError(
                f"{where}: config mismatch on {bad} (stored, expected)"
            )

    def _load_artifact(
        self,
        encoder_id: str | None = None,
        data_fingerprint: str | None = None,
    ) -> MiloMetadata:
        """Load + verify the configured artifact.  The SGE bank is a
        stochastic-greedy draw, so a *recorded* preprocessing seed must match
        this session's; artifacts from other entry points (direct
        ``MiloPreprocessor``, pre-header formats) record no seed and are
        accepted on config alone.  When the caller knows which encoder
        produced its features, the artifact's recorded encoder must agree —
        subsets selected over one representation are meaningless for another."""
        cfg = self.config
        md = MiloMetadata.load(
            cfg.metadata_path, expected_config=cfg.expected_artifact_config()
        )
        stored_enc = md.config.get("encoder_id")
        if (encoder_id is not None and stored_enc is not None
                and stored_enc != encoder_id):
            raise MetadataMismatchError(
                f"{cfg.metadata_path}: config mismatch on "
                f"{{'encoder_id': ({stored_enc!r}, {encoder_id!r})}} "
                "(stored, expected)"
            )
        stored_fp = md.config.get("data_fingerprint")
        if (data_fingerprint is not None and stored_fp is not None
                and stored_fp != data_fingerprint):
            raise MetadataMismatchError(
                f"{cfg.metadata_path}: artifact was preprocessed over "
                "different data (feature fingerprint mismatch); pass "
                "force=True to rebuild"
            )
        # gram_free / bucket_classes / lazy_gains / exact_sge_candidates
        # change which selection trajectories the artifact holds, so a
        # recorded value must agree; artifacts from before these knobs
        # existed record neither and are accepted on the base config alone
        # (same tolerance as prep_seed below).  shard_selection is recorded
        # but deliberately NOT checked: sharded runs select identically to
        # single-device up to sub-ulp near-tie resolution (see core.sharded),
        # an accepted tolerance so artifacts stay portable across meshes —
        # including lazy+sharded runs, where the trajectory-affecting knobs
        # (lazy_gains, lazy_threshold) ARE checked and the mesh still is not.
        for knob in ("gram_free", "bucket_classes", "lazy_gains",
                     "exact_sge_candidates"):
            stored_knob = md.config.get(knob)
            expected_knob = getattr(cfg, knob)
            if stored_knob is not None and bool(stored_knob) != expected_knob:
                raise MetadataMismatchError(
                    f"{cfg.metadata_path}: config mismatch on "
                    f"{{{knob!r}: ({stored_knob}, {expected_knob})}} "
                    "(stored, expected)"
                )
        # with lazy gains active the recompute threshold shapes the drift
        # cadence (and thus near-tie resolution), so it must agree too
        stored_thr = md.config.get("lazy_threshold")
        if (cfg.lazy_gains and bool(md.config.get("lazy_gains"))
                and stored_thr is not None
                and float(stored_thr) != cfg.lazy_threshold):
            raise MetadataMismatchError(
                f"{cfg.metadata_path}: config mismatch on "
                f"{{'lazy_threshold': ({stored_thr}, {cfg.lazy_threshold})}} "
                "(stored, expected)"
            )
        stored_seed = md.config.get("prep_seed")
        expected_seed = cfg.resolved_prep_seed()
        if stored_seed is not None and stored_seed != expected_seed:
            raise MetadataMismatchError(
                f"{cfg.metadata_path}: config mismatch on "
                f"{{'prep_seed': ({stored_seed}, {expected_seed})}} "
                "(stored, expected) — set MiloSessionConfig.prep_seed="
                f"{stored_seed} to reuse this artifact with a different "
                "training seed"
            )
        # repair/quarantine rewrite the effective ground set, so an artifact
        # that RECORDS a firewall policy must agree with this session's;
        # pre-firewall artifacts record none and are accepted on the base
        # config (same legacy tolerance as the knobs above)
        stored_fw = md.config.get("firewall")
        if "firewall" in md.config and stored_fw != cfg.firewall:
            raise MetadataMismatchError(
                f"{cfg.metadata_path}: config mismatch on "
                f"{{'firewall': ({stored_fw!r}, {cfg.firewall!r})}} "
                "(stored, expected)"
            )
        # hierarchical decomposition provenance: the bank's indices are only
        # meaningful for the partition geometry + refine factor they were
        # selected under
        self._check_partition_config(md, str(cfg.metadata_path))
        return md

    def _require_metadata(
        self, n: int | None = None, features: np.ndarray | None = None
    ) -> MiloMetadata:
        if self.metadata is None:
            if self.config.metadata_path and is_preprocessed(self.config.metadata_path):
                self.metadata = self._load_artifact(
                    data_fingerprint=(
                        _data_fingerprint(features) if features is not None else None
                    ),
                )
                self.loaded_from_artifact = True
            else:
                raise MetadataMismatchError(
                    "no preprocessing artifact: call session.preprocess(...) first"
                )
        if n is not None and self.metadata.m != n:
            raise MetadataMismatchError(
                f"preprocessing artifact covers {self.metadata.m} samples but "
                f"this dataset has {n} — same config, different data"
            )
        return self.metadata

    # -- registry wiring ----------------------------------------------------

    def selector(
        self,
        name: str | None = None,
        *,
        n: int,
        epochs: int | None = None,
        seed: int | None = None,
        features: np.ndarray | None = None,
        **extra: Any,
    ) -> Selector:
        """Build this session's selector from the registry.

        ``milo``/``milo_fixed``/``full``/``random``/``adaptive_random`` are
        wired from session state; other strategies (el2n, craig_pb, ...) take
        their inputs (scores, grad_fn, ...) through ``extra``.

        With ``config.selector_fallback`` declared, the result is a
        ``repro.health.FallbackSelector`` walking ``(primary, *fallbacks)``:
        degenerate selection math degrades down the chain (with plan
        provenance recording every hop) instead of crashing the run.  The
        fallback tiers are wired from session state only (``extra`` kwargs
        apply to the primary).
        """
        cfg = self.config
        resolved = name or cfg.selector
        if not cfg.selector_fallback:
            return self._build_selector(
                resolved, n=n, epochs=epochs, seed=seed,
                features=features, **extra,
            )
        from repro.health.fallback import FallbackSelector

        def factory(nm: str, ex: dict):
            return lambda: self._build_selector(
                nm, n=n, epochs=epochs, seed=seed, features=features, **ex)

        chain = [(resolved, factory(resolved, dict(extra)))]
        chain += [(fb, factory(fb, {})) for fb in cfg.selector_fallback]
        return FallbackSelector(chain)

    def _build_selector(
        self,
        name: str | None = None,
        *,
        n: int,
        epochs: int | None = None,
        seed: int | None = None,
        features: np.ndarray | None = None,
        **extra: Any,
    ) -> Selector:
        cfg = self.config
        name = name or cfg.selector
        epochs = epochs if epochs is not None else cfg.total_epochs
        seed = seed if seed is not None else cfg.seed
        explicit_k = "k" in extra
        k = extra.pop("k", None)
        if k is None:
            k = (self.metadata.k if self.metadata is not None
                 else max(1, int(round(cfg.subset_fraction * n))))
        if name == "milo":
            md = self._require_metadata(n, features)
            if explicit_k and k != md.k:
                raise ValueError(
                    f"milo's subset size is fixed by the preprocessing "
                    f"artifact (k={md.k}); rebuild the artifact to change it"
                )
            return build_selector(
                "milo", metadata=md, total_epochs=epochs,
                kappa=cfg.kappa, R=cfg.R, seed=seed, **extra,
            )
        if name == "milo_fixed":
            if features is None:
                raise ValueError("milo_fixed needs `features`")
            return build_selector("milo_fixed", features=features, k=k, **extra)
        if name == "full":
            if explicit_k:
                raise ValueError("selector 'full' trains on the whole dataset; "
                                 "`k` is not applicable")
            return build_selector("full", n=n, **extra)
        if name == "random":
            return build_selector("random", n=n, k=k, seed=seed, **extra)
        if name == "adaptive_random":
            return build_selector(
                "adaptive_random", n=n, k=k, R=extra.pop("R", cfg.R), seed=seed, **extra
            )
        # other strategies (el2n, selfsup_prune, craig_pb, ...): forward the
        # session context for every field their config actually declares
        fields = {f.name for f in dataclasses.fields(selector_entry(name).config_cls)}
        kwargs = dict(extra)
        for key, val in (("k", k), ("n", n), ("seed", seed), ("features", features)):
            if key in fields and val is not None:
                kwargs.setdefault(key, val)
        return build_selector(name, **kwargs)

    def pipeline(
        self,
        make_batch,
        selector: Selector,
        batch_size: int,
        *,
        seed: int | None = None,
        prefetch: bool = True,
        arrays: dict | None = None,
        resident: dict | None = None,
    ) -> pipeline_mod.Pipeline:
        if resident is None and arrays is not None and self.buffer_registry is not None:
            resident = self.buffer_registry.get(arrays)
        return pipeline_mod.Pipeline(
            make_batch, selector, batch_size,
            seed=self.config.seed if seed is None else seed,
            prefetch=prefetch,
            arrays=arrays,
            resident=resident,
        )

    # -- stage 2: train any number of downstream models ---------------------

    def train(
        self,
        features: np.ndarray,
        labels: np.ndarray,
        *,
        test_x: np.ndarray,
        test_y: np.ndarray,
        selector: str | Selector | None = None,
        epochs: int | None = None,
        seed: int | None = None,
        lr: float | None = None,
        hidden: int | None = None,
        **selector_kwargs: Any,
    ) -> TrainReport:
        """Train one downstream classifier on registry-selected subsets."""
        cfg = self.config
        epochs = epochs if epochs is not None else cfg.total_epochs
        seed = seed if seed is not None else cfg.seed
        lr = lr if lr is not None else cfg.lr
        hidden = hidden if hidden is not None else cfg.hidden
        n = len(features)
        if isinstance(selector, Selector) or hasattr(selector, "plan"):
            if selector_kwargs:
                raise ValueError(
                    "selector is already a built instance; selector kwargs "
                    f"{sorted(selector_kwargs)} would be silently ignored — "
                    "pass a registry name to build from config"
                )
            sel = selector
        else:
            sel = self.selector(
                selector, n=n, epochs=epochs, seed=seed,
                features=features, **selector_kwargs,
            )

        feats = np.asarray(features, np.float32)
        labs = np.asarray(labels, np.int64)
        # size the head over every label the run will see: a test/val class
        # absent from the training split must still own a logit, or accuracy
        # gathers out-of-bounds (clipped under jit → silently wrong)
        max_label = int(max(labs.max(), np.asarray(test_y).max()))
        if cfg.n_classes is None:
            n_classes = max_label + 1
        elif cfg.n_classes <= max_label:
            raise ValueError(
                f"n_classes={cfg.n_classes} cannot cover label {max_label} "
                "present in the train/eval data — the override may only "
                "widen the head, never reintroduce clipped-logit metrics"
            )
        else:
            n_classes = cfg.n_classes

        def make_batch(idx: np.ndarray) -> dict:
            return {"x": feats[idx], "y": labs[idx]}

        # validate against THIS dataset: catches a loaded artifact whose
        # indices were selected over different data
        plan0 = sel.plan(0).validate(n)
        batch_size = cfg.batch_size or plan0.k
        if batch_size > plan0.k:
            raise ValueError(
                f"batch_size={batch_size} exceeds the selected subset size "
                f"k={plan0.k}; every epoch would yield zero batches"
            )
        # host batches here are cheap slices; prefetch=False keeps the epoch
        # iterator plain so the warm-up read below can't strand a worker.
        # The column store mirrors make_batch exactly, enabling the fused
        # device-resident path when cfg.fused_training asks for it.
        pipe = self.pipeline(
            make_batch, sel, batch_size, seed=seed, prefetch=False,
            arrays={"x": feats, "y": labs},
        )
        steps = max(1, pipe.steps_per_epoch()) * epochs
        train_step = _classifier_step_fn(cfg.sub_steps)

        def init_state():
            return _init_classifier(
                jax.random.PRNGKey(seed), feats.shape[1], n_classes,
                hidden, float(lr), steps,
            )

        state = init_state()
        tx, ty = jnp.asarray(test_x), jnp.asarray(test_y)

        def acc_fn(params):
            # module-level jit (shared with the benchmarks): one compiled
            # eval per test-set shape across all train()/tune() calls
            return accuracy(params, tx, ty)

        def eval_fn(st: _ClassifierState) -> dict:
            return {"acc": acc_fn(st.params)}

        trainer = Trainer(
            train_step, pipe,
            TrainerConfig(
                epochs=epochs, eval_every_epochs=cfg.eval_every_epochs,
                log_every_steps=1,
                heartbeat_dir=cfg.heartbeat_dir,
                heartbeat_timeout=cfg.heartbeat_timeout,
            ),
            eval_fn=eval_fn,
            fused=cfg.fused_training,
            superstep=cfg.superstep,
        )
        # warm the jit caches outside the timed region so selector comparisons
        # measure steady-state epochs, not compilation — including BOTH
        # curriculum phases (the first WRE draw compiles threefry/top_k);
        # skip for windowed selectors where a late plan() forces a wasted
        # re-selection
        if plan0.phase in ("sge", "wre"):
            _ = sel.plan(max(epochs - 1, 0))
        warm_batch = next(iter(pipe.epoch(0)))
        ws, _ = trainer.train_step(state, warm_batch)
        jax.block_until_ready(acc_fn(ws.params))
        # the fused path adds its own (segment-shaped) programs: compile them
        # on a throwaway state — donation invalidates ITS buffers, not ours
        if trainer.fused_active():
            trainer.warm_fused(init_state())
        # charge per-window/per-epoch selection to the timed region exactly
        # as benchmarks/common.py does — that cost is the paper's argument;
        # dropping BOTH caches keeps epoch 0's subset identical to the rest
        # of its R-window (one recompute inside fit, then memoized)
        getattr(sel, "reset_cache", lambda: None)()
        pipe.invalidate_plan_cache()

        t0 = time.perf_counter()
        state = trainer.fit(state, resume=False)
        train_time = time.perf_counter() - t0
        # always evaluate the FINAL state: history's last eval can be epochs
        # old when eval_every_epochs does not divide epochs
        final = float(acc_fn(state.params))
        accs = [float(h["acc"]) for h in trainer.history if "acc" in h] + [final]
        return TrainReport(
            final_acc=final,
            best_acc=max(accs),
            train_time=train_time,
            steps=int(state.step),
            history=trainer.history,
        )

    # -- stage 3: hyper-parameter tuning ------------------------------------

    def tune(
        self,
        features: np.ndarray,
        labels: np.ndarray,
        val_x: np.ndarray,
        val_y: np.ndarray,
        space: dict,
        *,
        selector: str | None = None,
        search: str = "tpe",
        max_budget: int = 9,
        eta: int = 3,
        seed: int | None = None,
        batched_objective: Any | None = None,
        should_stop: Any | None = None,
        checkpoint: str | None = None,
        **selector_kwargs: Any,
    ) -> HyperbandResult:
        """Hyperband over ``space`` with registry-selected subsets powering
        every configuration evaluation (paper §4's 20-75x tuning speedups).

        ``batched_objective(configs, budget) -> scores`` opts a rung into one
        batched evaluation of all its surviving configs (e.g. a trial scan
        vmapped over ``tuner.stack_configs`` leaves — possible whenever the
        space varies only traced leaves like ``lr``, not shapes like
        ``hidden``); trials fall back to the sequential per-config loop
        otherwise.  ``should_stop()`` is polled before every rung (see
        ``tuning.hyperband``) — the serving layer's cancellation/deadline
        hook; an early stop returns ``stopped=True``.  ``checkpoint`` names a
        JSON rung-state file making the sweep crash-safe: a killed sweep
        relaunched with the same arguments resumes at its rung boundary and
        reproduces the identical trial stream and ``best_config`` (see
        ``tuning.hyperband``)."""
        cfg = self.config
        seed = seed if seed is not None else cfg.seed
        tunable = {"lr", "hidden"}
        unknown = set(space) - tunable
        if unknown:
            raise ValueError(
                f"tune() searches over {sorted(tunable)}; unsupported space "
                f"keys {sorted(unknown)} would be sampled but never applied"
            )
        searches = {"tpe": TPESearch, "random": RandomSearch}
        if search not in searches:
            raise ValueError(
                f"unknown search {search!r}; available: {sorted(searches)}"
            )
        search_obj = searches[search](space, seed=seed)

        def train_fn(trial_cfg: dict, budget: int, sel) -> float:
            report = self.train(
                features, labels, test_x=val_x, test_y=val_y,
                selector=sel, epochs=max(2, budget), seed=seed,
                lr=trial_cfg.get("lr"), hidden=trial_cfg.get("hidden"),
            )
            return report.final_acc

        def selector_factory(budget: int):
            return self.selector(
                selector, n=len(features), epochs=max(2, budget), seed=seed,
                features=features, **selector_kwargs,
            )

        objective = subset_objective(train_fn, selector_factory)
        return hyperband(objective, search_obj, max_budget=max_budget, eta=eta,
                         batched_objective=batched_objective,
                         should_stop=should_stop, checkpoint=checkpoint)
