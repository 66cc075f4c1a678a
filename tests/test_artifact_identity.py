"""One rule for what an artifact is: ``MiloPreprocessor.artifact_identity``
names the settings that shape an artifact's bits, and every reuse path (a
``metadata_path`` load, ``MiloSession.adopt_metadata``, the server's store
key) checks that one list through ``core.metadata.check_identity``."""
import jax
import numpy as np
import pytest

from repro.core.metadata import MetadataMismatchError, MiloMetadata, check_identity
from repro.core.milo import MiloPreprocessor
from repro.selection import MiloSession, MiloSessionConfig
from repro.serve import MiloServer

N, D, CLASSES = 96, 8, 3

#: (key, settings both sides share, the reusing side's setting)
SETTINGS = [
    ("firewall", {}, {"firewall": "quarantine"}),
    ("refine_factor", {}, {"refine_factor": 2}),
    ("lazy_threshold", {"lazy_gains": True, "hard_fn": "facility_location"},
     {"lazy_threshold": 0.5}),
    ("exact_sge_candidates", {}, {"exact_sge_candidates": True}),
    ("gram_free", {}, {"gram_free": True}),
]
SETTING_IDS = [s[0] for s in SETTINGS]


def _dataset():
    rng = np.random.default_rng(0)
    labs = rng.integers(0, CLASSES, N).astype(np.int64)
    feats = (rng.normal(size=(N, D)) + 0.8 * labs[:, None]).astype(np.float32)
    return feats, labs


def _config(**kw) -> MiloSessionConfig:
    return MiloSessionConfig(**{"subset_fraction": 0.2, "n_sge_subsets": 2,
                                "total_epochs": 4, **kw})


@pytest.mark.parametrize("key,shared,other", SETTINGS, ids=SETTING_IDS)
def test_metadata_path_load_refuses_other_setting(tmp_path, key, shared, other):
    feats, labs = _dataset()
    path = str(tmp_path / "milo.npz")
    MiloSession(_config(metadata_path=path, **shared)).preprocess(feats, labs)
    with pytest.raises(MetadataMismatchError, match=key):
        MiloSession(_config(metadata_path=path, **shared, **other)
                    ).preprocess(feats, labs)


@pytest.mark.parametrize("key,shared,other", SETTINGS, ids=SETTING_IDS)
def test_adopt_metadata_refuses_other_setting(key, shared, other):
    feats, labs = _dataset()
    md = MiloSession(_config(**shared)).build_metadata(feats, labs)
    with pytest.raises(MetadataMismatchError, match=key):
        MiloSession(_config(**shared, **other)).adopt_metadata(md)


@pytest.mark.parametrize("key,shared,other", SETTINGS, ids=SETTING_IDS)
def test_server_tenant_override_gets_own_artifact(tmp_path, key, shared, other):
    """A tenant whose overrides change a setting of the artifact gets its own
    store key and its own build, never the other tenant's artifact or a
    mismatch error."""
    feats, labs = _dataset()
    with MiloServer(_config(**shared), store_root=str(tmp_path / "store"),
                    num_workers=1) as srv:
        first = srv.result(srv.submit("preprocess", features=feats,
                                      labels=labs, tenant="a"), timeout=120)
        second = srv.result(srv.submit("preprocess", features=feats,
                                       labels=labs, tenant="b",
                                       overrides=other), timeout=120)
        assert first["source"] == second["source"] == "built"
        assert first["artifact_key"] != second["artifact_key"]
        assert srv.store.builds == 2
        md = MiloMetadata.load(srv.store.path_for(second["artifact_key"]))
    assert md.config[key] == other[key]


@pytest.mark.parametrize("path", ["load", "adopt"])
def test_artifact_without_legacy_keys_still_reused(tmp_path, path):
    """An artifact saved before the later settings existed records none of
    them; it still loads into (and is adopted by) a default session."""
    feats, labs = _dataset()
    art = str(tmp_path / "legacy.npz")
    md = MiloSession(_config()).build_metadata(feats, labs)
    for key in ("gram_free", "bucket_classes", "lazy_gains",
                "exact_sge_candidates", "lazy_threshold"):
        del md.config[key]
    md.save(art)
    if path == "load":
        session = MiloSession(_config(metadata_path=art))
        session.preprocess(feats, labs)
        assert session.loaded_from_artifact
    else:
        md = MiloMetadata.load(art)
        assert MiloSession(_config()).adopt_metadata(md) is md


@pytest.mark.parametrize("kw", [
    {},
    {"firewall": "repair"},
    {"partition": "random_blocks", "partition_block": 32, "refine_factor": 2},
    {"partition": "balanced_blocks", "partition_block": 16},
    {"lazy_gains": True, "hard_fn": "facility_location"},
], ids=["flat", "firewall", "random_blocks", "balanced_blocks", "lazy"])
def test_stamp_holds_the_identity(kw):
    """Every identity key is stamped with its value, so a partial-dict check
    against the identity (the store's disk load) accepts the artifact."""
    feats, labs = _dataset()
    pre = MiloPreprocessor(subset_fraction=0.2, n_sge_subsets=2, **kw)
    md = pre.preprocess(feats, labs, jax.random.PRNGKey(3), prep_seed=3)
    ident = pre.artifact_identity(3)
    assert {k: md.config[k] for k in ident} == ident
    check_identity(md.config, ident, "stamp")


BASE = {"subset_fraction": 0.2, "eps": 0.01, "gram_free": False,
        "lazy_gains": False, "prep_seed": 0}


@pytest.mark.parametrize("stored,expected,bad", [
    # a key the artifact predates is accepted
    ({"subset_fraction": 0.2, "eps": 0.01, "prep_seed": 0}, BASE, {}),
    # a direct MiloPreprocessor build records no seed
    ({**BASE, "prep_seed": None}, BASE, {}),
    # an unstamped key is compared as what its absence means, both ways
    (BASE, {**BASE, "firewall": "quarantine"},
     {"firewall": (None, "quarantine")}),
    ({**BASE, "firewall": "repair"}, BASE, {"firewall": ("repair", None)}),
    (BASE, {**BASE, "partition": "by_class", "refine_factor": 2},
     {"refine_factor": (1, 2)}),
    ({**BASE, "partition": "random_blocks", "partition_block": 64,
      "partition_seed": 0, "refine_factor": 1}, BASE,
     {"partition": ("random_blocks", "by_class")}),
    # a setting every artifact records may not be missing
    ({"eps": 0.01, "prep_seed": 0}, BASE, {"subset_fraction": (None, 0.2)}),
    # every differing key is named in one error
    ({**BASE, "eps": 0.1, "gram_free": True, "prep_seed": 1}, BASE,
     {"eps": (0.1, 0.01), "gram_free": (True, False), "prep_seed": (1, 0)}),
], ids=["legacy", "direct_build", "firewall_absent", "firewall_extra",
        "refine_absent", "partition_extra", "base_missing", "many"])
def test_check_identity_rules(stored, expected, bad):
    if not bad:
        check_identity(stored, expected, "art")
        return
    with pytest.raises(MetadataMismatchError) as exc:
        check_identity(stored, expected, "art")
    assert str(exc.value) == f"art: config mismatch on {bad} (stored, expected)"
