"""The selection path's ``milo.*`` profiler spans, read back from a real trace.

A tiny ``MiloSession.build_metadata`` (4 classes x 64 rows x 32 wide) runs
under ``jax.profiler`` on the CPU, inside a ``bench.window`` span as the
benchmark opens it.  The ``.xplane.pb`` is read by the benchmark's own
reader (``bench/spans.py``), which the CPU's trace serves for the host side
only, and the spans are checked for their nesting, their counts and their
byte arguments.  The four classes share one pow2 bucket, so they run as one
batched chunk (``milo.bucket``); a smaller chunk budget splits them into two
chunks, each gathering its own rows (``milo.gather``); a lazy configuration
shows the per-partition route's spans (``milo.partition``).
"""
from __future__ import annotations

from pathlib import Path

import jax
import numpy as np
import pytest

import repro.core.milo as milo
from bench import spans, trace
from bench.manifest import ROOT, Manifest
from repro.core.buckets import chunk_bytes
from repro.selection import MiloSession, MiloSessionConfig

CLASSES, ROWS, WIDTH = 4, 64, 32
PREP_SEED = 7
LOOP = ("milo.build", "milo.preprocess")
BUCKET = LOOP + ("milo.bucket",)
PART = LOOP + ("milo.partition",)
N_PAD = 64


def _session() -> MiloSession:
    return MiloSession(MiloSessionConfig(subset_fraction=0.1, n_sge_subsets=2,
                                         prep_seed=PREP_SEED))


def _data():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(CLASSES * ROWS, WIDTH)).astype(np.float32)
    return x, np.repeat(np.arange(CLASSES), ROWS)


def _trace(session, trace_dir):
    """The artifact built under the profiler, what ``spans.load`` reads of
    its trace, and each ``milo.*`` event's chain of enclosing span names."""
    x, y = _data()
    session.build_metadata(x, y)  # compile outside the trace
    jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            md = session.build_metadata(x, y)
    finally:
        jax.profiler.stop_trace()
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    ops, bench_spans, events = spans.load(path)
    return md, ops, bench_spans, events, spans.chains(events)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return _trace(_session(), tmp_path_factory.mktemp("trace"))


@pytest.fixture(scope="module")
def traced_loop(tmp_path_factory):
    """The same build on the lazy gram-free route, which keeps the
    per-partition loop."""
    session = MiloSession(MiloSessionConfig(
        subset_fraction=0.1, n_sge_subsets=2, prep_seed=PREP_SEED,
        gram_free=True, lazy_gains=True, hard_fn="facility_location"))
    return _trace(session, tmp_path_factory.mktemp("trace_loop"))


@pytest.fixture(scope="module")
def traced_split(tmp_path_factory):
    """The same build with a chunk budget of two partitions: two chunks,
    each reading half the rows."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(milo, "chunk_byte_limit",
                   lambda: 2 * chunk_bytes(N_PAD, WIDTH, False))
        return _trace(_session(), tmp_path_factory.mktemp("trace_split"))


def _with_chain(traced, chain):
    _, _, _, events, chains = traced
    return [ev for ev, c in zip(events, chains) if c == chain]


def _inside(traced, outer):
    """The events nested in ``outer`` on its thread, with their chains, in
    time order."""
    _, _, _, events, chains = traced
    a, b, thread = outer[0], outer[1], outer[4]
    return sorted(((ev, c) for ev, c in zip(events, chains)
                   if ev is not outer and ev[4] == thread
                   and a <= ev[0] and ev[1] <= b), key=lambda p: p[0][0])


def test_one_build_holds_the_fingerprint_and_the_preprocessor(traced):
    chains = traced[4]
    (build,) = _with_chain(traced, ("milo.build",))
    assert build[3] == {"m": CLASSES * ROWS, "prep_seed": PREP_SEED}
    (_fp,) = _with_chain(traced, ("milo.build", "milo.fingerprint"))
    (prep,) = _with_chain(traced, LOOP)
    # every class ran batched
    assert prep[3] == {"partitions": CLASSES, "batched_partitions": CLASSES,
                       "chunks": 1}
    assert all(c[0] == "milo.build" for c in chains)


def _k_run(md) -> int:
    return 1 << (int(max(md.class_budgets)) - 1).bit_length()


def test_one_partition_span_per_class(traced):
    """The classes share one bucket: one ``milo.bucket`` span holds them
    all, and none of the per-partition route's ``milo.partition``."""
    md = traced[0]
    assert len({1 << (int(b) - 1).bit_length() for b in md.class_budgets}) == 1
    (bucket,) = _with_chain(traced, BUCKET)
    assert bucket[3] == {"partitions": CLASSES, "n_pad": N_PAD,
                         "k_run": _k_run(md)}
    assert _with_chain(traced, PART) == []
    assert len(_with_chain(traced, LOOP + ("milo.merge",))) == 1


def test_three_fetches_nested_in_each_partition(traced):
    """One blocking read per chunk, nested in its ``milo.bucket`` after
    the put, the three dispatches and the Taylor-softmax."""
    chains = traced[4]
    assert sum(c[-1] == "milo.fetch" for c in chains) == 1
    (bucket,) = _with_chain(traced, BUCKET)
    inner = _inside(traced, bucket)
    assert [c for _, c in inner if c[-1] == "milo.fetch"] == [
        BUCKET + ("milo.fetch",)]
    children = [c[-1] for _, c in inner if len(c) == len(BUCKET) + 1]
    assert children == ["milo.put", "milo.gram", "milo.sge", "milo.wre",
                        "milo.softmax", "milo.fetch"]


def test_byte_arguments_are_the_arrays_nbytes(traced):
    md = traced[0]
    x, _ = _data()
    (fp,) = _with_chain(traced, ("milo.build", "milo.fingerprint"))
    assert fp[3]["bytes"] == x.nbytes
    # the whole feature matrix goes to the device once
    (put,) = _with_chain(traced, BUCKET + ("milo.put",))
    assert put[3]["bytes"] == x.nbytes
    # one read of the chunk's SGE banks (int32, padded to the bucket's
    # budget), its importances (float32, padded to the bucket) and the
    # classes' probabilities (float32)
    (fetch,) = _with_chain(traced, BUCKET + ("milo.fetch",))
    banks = CLASSES * md.config["n_sge_subsets"] * _k_run(md) * 4
    assert fetch[3]["bytes"] == banks + CLASSES * N_PAD * 4 + x.shape[0] * 4


@pytest.mark.parametrize("name", ["traced", "traced_loop", "traced_split"])
def test_the_fingerprint_wait_stays_on_the_calling_thread(name, request):
    """The hash runs on a worker thread, but ``milo.fingerprint`` (the
    wait for it) nests in ``milo.build`` on the caller's thread, with the
    worker's time as ``hash_us``; the worker opens no ``milo.*`` span."""
    t = request.getfixturevalue(name)
    x, _ = _data()
    (build,) = _with_chain(t, ("milo.build",))
    (fp,) = _with_chain(t, ("milo.build", "milo.fingerprint"))
    assert fp[4] == build[4]
    assert fp[3]["bytes"] == x.nbytes
    assert isinstance(fp[3]["hash_us"], int) and fp[3]["hash_us"] >= 0
    assert all(ev[4] == build[4] for ev in t[3])


def test_per_partition_route_keeps_its_spans(traced_loop):
    """The lazy route: a ``milo.partition`` per class, each with its put,
    dispatches and three reads, and no ``milo.bucket``."""
    md = traced_loop[0]
    x, _ = _data()
    (prep,) = _with_chain(traced_loop, LOOP)
    assert prep[3] == {"partitions": CLASSES, "batched_partitions": 0,
                       "chunks": 0}
    assert _with_chain(traced_loop, BUCKET) == []
    parts = _with_chain(traced_loop, PART)
    assert [p[3]["k_c"] for p in parts] == [int(b) for b in md.class_budgets]
    assert all(p[3]["n_c"] == ROWS for p in parts)
    for part in parts:
        inner = _inside(traced_loop, part)
        children = [c[-1] for _, c in inner if len(c) == len(PART) + 1]
        assert children == ["milo.put", "milo.gram", "milo.sge", "milo.wre",
                            "milo.fetch", "milo.fetch", "milo.softmax"]
    puts = _with_chain(traced_loop, PART + ("milo.put",))
    assert [p[3]["bytes"] for p in puts] == [ROWS * x[0].nbytes] * CLASSES


def test_load_reads_the_spans_the_program_writes(traced):
    """What the benchmark's reduction reads of the trace: no device plane
    on the CPU, the window span, and the build with its fetch count."""
    _, ops, bench_spans, events, _ = traced
    assert ops == {}
    assert [n for _, _, n in bench_spans] == ["bench.window"]
    (lo, hi, _), = bench_spans
    assert spans.builds(events, lo, hi) == [
        {"m": CLASSES * ROWS, "prep_seed": PREP_SEED, "fetches": 1}]


def test_tracing_leaves_the_artifact_unchanged(traced):
    md = traced[0]
    x, y = _data()
    plain = _session().build_metadata(x, y)
    np.testing.assert_array_equal(plain.sge_subsets, md.sge_subsets)
    np.testing.assert_array_equal(plain.wre_probs, md.wre_probs)
    assert plain.config == md.config


def test_a_split_group_gathers_once_per_chunk(traced_split):
    """Each chunk of a split group gathers its own rows on the host, before
    its put: ``bytes`` is the rows gathered times the width times 4."""
    (prep,) = _with_chain(traced_split, LOOP)
    assert prep[3] == {"partitions": CLASSES, "batched_partitions": CLASSES,
                       "chunks": 2}
    buckets = _with_chain(traced_split, BUCKET)
    assert [b[3]["partitions"] for b in buckets] == [2, 2]
    for bucket in buckets:
        children = [c[-1] for _, c in _inside(traced_split, bucket)
                    if len(c) == len(BUCKET) + 1]
        assert children == ["milo.gather", "milo.put", "milo.gram",
                            "milo.sge", "milo.wre", "milo.softmax",
                            "milo.fetch"]
    gathers = _with_chain(traced_split, BUCKET + ("milo.gather",))
    assert [g[3]["bytes"] for g in gathers] == [2 * ROWS * WIDTH * 4] * 2
    puts = _with_chain(traced_split, BUCKET + ("milo.put",))
    assert [p[3]["bytes"] for p in puts] == [2 * ROWS * WIDTH * 4] * 2


def test_no_gather_where_one_chunk_reads_every_row(traced, traced_loop):
    for t in (traced, traced_loop):
        assert all(c[-1] != "milo.gather" for c in t[4])


def _gather_share(t, monkeypatch, tmp_path):
    """``idle_in_gather.select`` on the CPU trace ``t``, its host events as
    recorded and, since the CPU's trace has no device plane, one device
    operation over the first tenth of the window."""
    _, _, bench_spans, events, _ = t
    (lo, hi, _), = bench_spans
    named = {"/device:TPU:0": [(lo, lo + (hi - lo) // 10, "op")]}
    ops = {p: [(a, b) for a, b, _ in evs] for p, evs in named.items()}
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    monkeypatch.setattr(spans, "trace_file", lambda: path)
    monkeypatch.setattr(spans, "load", lambda p: (ops, bench_spans, events))
    monkeypatch.setattr(spans, "_cache", {})
    record = {"trace": trace.reduce(named, bench_spans)}
    return Manifest(ROOT).metric_reader("idle_in_gather.select").read(record)


def test_gather_reader_reads_a_share_of_the_window(traced_split, monkeypatch,
                                                  tmp_path):
    share = _gather_share(traced_split, monkeypatch, tmp_path)
    assert share is not None and 0 < share <= 100


def test_gather_reader_is_none_without_a_gather(traced, monkeypatch,
                                               tmp_path):
    assert _gather_share(traced, monkeypatch, tmp_path) is None
