"""The reduction of the program's ``milo.*`` spans (``bench/spans.py``) and
its three metric readers: on synthetic trace events, whose times are whole
nanoseconds so that the idle split can be checked exactly, and on a trace
recorded on a TPU v5 lite.
"""
from __future__ import annotations

import gzip
from pathlib import Path

import pytest

from bench import spans, trace
from bench.manifest import ROOT, Manifest

M = Manifest(ROOT)
READERS = ["idle_in_session.select", "idle_in_host_loop.select",
           "syncs_per_artifact.select"]

BENCH = [(0, 1000, "bench.window"), (100, 500, "bench.artifact"),
         (900, 1100, "bench.artifact")]
T = ("/host:CPU", 0)
MILO = [
    # a build wholly inside the window: two partitions, then the fingerprint
    (100, 500, "milo.build", {"m": 10, "prep_seed": 1}, T),
    (100, 400, "milo.preprocess", {"partitions": 2}, T),
    (110, 250, "milo.partition", {"n_c": 5, "k_c": 1}, T),
    (110, 130, "milo.put", {"bytes": 64}, T),
    (150, 200, "milo.fetch", {"bytes": 8}, T),
    (200, 240, "milo.fetch", {"bytes": 32}, T),
    (260, 390, "milo.partition", {"n_c": 5, "k_c": 1}, T),
    (260, 270, "milo.put", {"bytes": 64}, T),
    (300, 380, "milo.fetch", {"bytes": 32}, T),
    (390, 400, "milo.merge", {}, T),
    (410, 490, "milo.fingerprint", {"bytes": 40}, T),
    # a build cut by the window's end
    (900, 1100, "milo.build", {"m": 10, "prep_seed": 2}, T),
    (920, 1100, "milo.preprocess", {}, T),
    (950, 1000, "milo.fetch", {"bytes": 8}, T),
]
NAMED_OPS = {
    "/device:TPU:0": [(0, 100, "a"), (130, 150, "b"), (240, 300, "c"),
                      (500, 550, "d"), (600, 900, "e")],
    "/device:TPU:1": [(0, 1000, "all")],
}
OPS = {p: [(a, b) for a, b, _ in evs] for p, evs in NAMED_OPS.items()}
BUSY_NS = 100 + 20 + 60 + 50 + 300 + 1000


def _reduce(events=MILO):
    return spans.reduce(OPS, BENCH, list(events))


def test_idle_split_sums_to_the_idle_time_exactly():
    red = _reduce()
    assert sum(red["idle_ns"].values()) == 2 * 1000 - BUSY_NS
    assert red["devices"] == 2


def test_innermost_span_takes_the_idle():
    idle = _reduce()["idle_ns"]
    loop = "milo.build/milo.preprocess"
    assert idle[f"{loop}/milo.partition/milo.put"] == 20       # 110-130
    assert idle[f"{loop}/milo.partition/milo.fetch"] == 50 + 40 + 80
    assert idle[f"{loop}/milo.fetch"] == 50                    # 950-1000
    assert idle[f"{loop}/milo.partition"] == 10                # 380-390
    assert idle[f"{loop}/milo.merge"] == 10
    assert idle[loop] == 10 + 30                               # 100-110, 920-950
    assert idle["milo.build/milo.fingerprint"] == 80
    assert idle["milo.build"] == 10 + 10 + 20                  # 400-410, 490-500, 900-920


def test_idle_outside_any_milo_span_is_untraced():
    idle = _reduce()["idle_ns"]
    assert idle[spans.UNTRACED] == 50                          # 550-600
    assert spans.layer(spans.UNTRACED) == spans.UNTRACED
    assert spans.layer("milo.build/milo.fingerprint") == spans.SESSION
    assert spans.layer("milo.build/milo.preprocess/milo.merge") == spans.HOST_LOOP


def test_only_complete_builds_are_counted():
    builds = _reduce()["builds"]
    assert builds == [{"m": 10, "prep_seed": 1, "fetches": 3}]


def test_without_program_spans_the_reduction_is_unchanged():
    base = trace.reduce(NAMED_OPS, BENCH)
    red = _reduce(events=[])
    for key in ("busy_s", "window_s", "devices"):
        assert red[key] == base[key]
    assert red["idle_ns"] == {spans.UNTRACED: 2 * 1000 - BUSY_NS}
    assert red["builds"] == []


@pytest.fixture
def synthetic_trace(tmp_path, monkeypatch):
    """Point the readers at a trace file whose events are ``MILO``."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    events = list(MILO)
    monkeypatch.setattr(spans, "trace_file", lambda: path)
    monkeypatch.setattr(spans, "load", lambda p: (OPS, BENCH, events))
    monkeypatch.setattr(spans, "_cache", {})
    return events


def test_readers_on_a_trace_with_spans(synthetic_trace):
    record = {"trace": trace.reduce(NAMED_OPS, BENCH)}
    read = {n: M.metric_reader(n).read(record) for n in READERS}
    window_ns = 2 * 1000
    session = 80 + 10 + 10 + 20
    assert read["idle_in_session.select"] == pytest.approx(100 * session / window_ns)
    loop = 2 * 1000 - BUSY_NS - session - 50
    assert read["idle_in_host_loop.select"] == pytest.approx(100 * loop / window_ns)
    assert read["syncs_per_artifact.select"] == 3.0


@pytest.mark.parametrize("reader", READERS)
def test_reader_returns_none_without_a_build(synthetic_trace, reader):
    synthetic_trace[:] = [ev for ev in synthetic_trace if ev[2] != "milo.build"]
    record = {"trace": trace.reduce(NAMED_OPS, BENCH)}
    assert M.metric_reader(reader).read(record) is None


@pytest.mark.parametrize("reader", READERS)
def test_reader_returns_none_for_an_untraced_run(synthetic_trace, reader):
    assert M.metric_reader(reader).read({"trace": None}) is None


@pytest.mark.parametrize("reader", READERS)
def test_reader_raises_for_another_trace(synthetic_trace, reader):
    other = trace.reduce(NAMED_OPS, [(0, 999, "bench.window")])
    with pytest.raises(ValueError, match="not this run's trace"):
        M.metric_reader(reader).read({"trace": other})


@pytest.mark.parametrize("reader", READERS)
def test_reader_raises_without_a_trace_file(monkeypatch, reader):
    """A traced run whose trace is not where the harness keeps it (as with
    ``--trace-dir``) fails loudly instead of reading as a program without
    spans."""
    monkeypatch.setattr(spans, "trace_file", lambda: None)
    record = {"trace": trace.reduce(NAMED_OPS, BENCH)}
    with pytest.raises(FileNotFoundError):
        M.metric_reader(reader).read(record)


# Two builds of 4 classes x 64 rows x 32 wide (n_sge_subsets 2, prep_seed 7
# then 8) inside bench.window and bench.artifact spans, traced on one TPU v5
# lite by jax.profiler and gzipped.
RECORDED = Path(__file__).parent / "fixtures" / "sel-tiny.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("recorded") / "sel-tiny.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    return path


def test_recorded_trace_busy_share(recorded):
    base = trace.reduce_file(recorded)
    assert (base["busy_s"], base["window_s"], base["devices"]) == (
        0.003973918, 0.080495348, 1)
    red = spans.reduce(*spans.load(recorded))
    assert (red["busy_s"], red["window_s"], red["devices"]) == (
        base["busy_s"], base["window_s"], base["devices"])


def test_recorded_trace_span_tree(recorded):
    ops, bench_spans, events = spans.load(recorded)
    red = spans.reduce(ops, bench_spans, events)
    assert red["builds"] == [
        {"m": 256, "prep_seed": seed, "fetches": 12} for seed in (7, 8)]
    loop = "milo.build/milo.preprocess/milo.partition"
    keys = ["/".join(c) for c in spans.chains(events)]
    assert keys.count(loop) == 2 * 4
    assert keys.count(f"{loop}/milo.put") == 2 * 4
    assert set(red["idle_ns"]) == {
        spans.UNTRACED, "milo.build", "milo.build/milo.fingerprint",
        "milo.build/milo.preprocess", "milo.build/milo.preprocess/milo.merge",
        loop, f"{loop}/milo.softmax", f"{loop}/milo.softmax/milo.fetch",
        *(f"{loop}/milo.{s}" for s in ("put", "gram", "sge", "wre", "fetch"))}


def test_recorded_trace_idle_split_sums_to_the_idle_time(recorded):
    red = spans.reduce(*spans.load(recorded))
    idle = red["idle_ns"]
    assert sum(idle.values()) == (red["window_s"] - red["busy_s"]) * 1e9
    assert idle[spans.UNTRACED] < 0.01 * red["window_s"] * 1e9


def test_readers_on_the_recorded_trace(recorded, monkeypatch):
    monkeypatch.setattr(spans, "trace_file", lambda: recorded)
    monkeypatch.setattr(spans, "_cache", {})
    record = {"trace": trace.reduce_file(recorded)}
    read = {n: M.metric_reader(n).read(record) for n in READERS}
    idle = M.metric_reader("device_idle_share.select").read(record)
    untraced = spans.for_run(record)["idle_ns"][spans.UNTRACED]
    assert read["idle_in_session.select"] + read["idle_in_host_loop.select"] \
        + 100 * untraced / 0.080495348e9 == pytest.approx(idle)
    assert read["idle_in_session.select"] == pytest.approx(
        100 * (2140955 + 87401) / 80495348)
    assert read["syncs_per_artifact.select"] == 12.0
