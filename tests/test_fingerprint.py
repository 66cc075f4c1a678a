"""The data fingerprint: hashed in place, and overlapped with the pass.

``_data_fingerprint`` and ``serve.buffers.array_fingerprint`` hash an
array's buffer without a ``tobytes()`` copy; their digests must stay those
of the copying expressions, written out literally here, since stored
artifacts and server keys carry them.  ``MiloSession.build_metadata``
without a ``fingerprint`` hashes on a host thread while the preprocessing
pass runs, and joins it before it returns, whichever side raises.
"""
from __future__ import annotations

import hashlib
import threading
import time

import numpy as np
import pytest

import repro.selection.session as session_mod
from repro.core.milo import MiloPreprocessor
from repro.selection import MiloSession, MiloSessionConfig
from repro.serve.buffers import array_fingerprint

CLASSES, ROWS, WIDTH = 4, 64, 32
SLEEP_S = 0.3


def _matrix():
    return np.random.default_rng(0).normal(size=(48, 16)).astype(np.float32)


CASES = {
    "float32_c": lambda: _matrix(),
    "fortran": lambda: np.asfortranarray(_matrix()),
    "strided": lambda: _matrix()[::3, 1::2],
    "float64": lambda: _matrix().astype(np.float64),
    "list_of_lists": lambda: _matrix()[:5].tolist(),
    "zero_rows": lambda: np.zeros((0, 16), np.float32),
}


def _old_data_fingerprint(x):
    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(x, np.float32)).tobytes()
    ).hexdigest()[:16]


def _old_array_fingerprint(x):
    a = np.ascontiguousarray(x)
    h = hashlib.sha256()
    h.update(str(a.dtype).encode())
    h.update(str(a.shape).encode())
    h.update(a.tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("new, old", [
    (session_mod._data_fingerprint, _old_data_fingerprint),
    (array_fingerprint, _old_array_fingerprint),
], ids=["data_fingerprint", "array_fingerprint"])
def test_in_place_digest_equals_the_copying_one(new, old, case):
    x = CASES[case]()
    assert new(x) == old(x)


def _session() -> MiloSession:
    return MiloSession(MiloSessionConfig(subset_fraction=0.1, n_sge_subsets=2,
                                         prep_seed=7))


def _data():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(CLASSES * ROWS, WIDTH)).astype(np.float32)
    return x, np.repeat(np.arange(CLASSES), ROWS)


@pytest.fixture
def slow_hash(monkeypatch):
    """``_digest`` patched to stamp when each call starts, then sleep."""
    starts: list[float] = []
    digest = session_mod._digest

    def slow(a):
        starts.append(time.perf_counter())
        time.sleep(SLEEP_S)
        return digest(a)

    monkeypatch.setattr(session_mod, "_digest", slow)
    return starts


def _stub_preprocess(monkeypatch, fn):
    class _Artifact:
        def __init__(self):
            self.config = {}

    def preprocess(self, *args, **kwargs):
        fn()
        return _Artifact()

    monkeypatch.setattr(MiloPreprocessor, "preprocess", preprocess)


def test_build_hashes_while_the_pass_runs(slow_hash, monkeypatch):
    x, y = _data()
    returned: list[float] = []
    preprocess = MiloPreprocessor.preprocess

    def timed(self, *args, **kwargs):
        out = preprocess(self, *args, **kwargs)
        returned.append(time.perf_counter())
        return out

    monkeypatch.setattr(MiloPreprocessor, "preprocess", timed)
    md = _session().build_metadata(x, y)
    assert len(slow_hash) == 1 and len(returned) == 1
    assert slow_hash[0] < returned[0]
    assert md.config["data_fingerprint"] == session_mod._data_fingerprint(x)


def test_a_failing_hash_raises_from_build(monkeypatch):
    def broken(a):
        raise RuntimeError("hash failed")

    monkeypatch.setattr(session_mod, "_digest", broken)
    _stub_preprocess(monkeypatch, lambda: None)
    x, y = _data()
    with pytest.raises(RuntimeError, match="hash failed"):
        _session().build_metadata(x, y)


def test_a_failing_pass_joins_the_hash(slow_hash, monkeypatch):
    """The pass raises while the hash still sleeps: the worker is joined
    before the pass's exception leaves ``build_metadata``."""
    def fail():
        time.sleep(SLEEP_S / 10)
        raise ValueError("pass failed")

    _stub_preprocess(monkeypatch, fail)
    x, y = _data()
    before = threading.active_count()
    with pytest.raises(ValueError, match="pass failed"):
        _session().build_metadata(x, y)
    assert len(slow_hash) == 1
    assert threading.active_count() == before


def test_a_given_fingerprint_starts_no_hash(slow_hash, monkeypatch):
    threads: list[int] = []
    _stub_preprocess(monkeypatch,
                     lambda: threads.append(threading.active_count()))
    x, y = _data()
    before = threading.active_count()
    md = _session().build_metadata(x, y, fingerprint="given")
    assert slow_hash == []
    assert threads == [before]
    assert md.config["data_fingerprint"] == "given"
