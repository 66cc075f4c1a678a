"""The selection path's ``milo.*`` profiler spans, read back from a real trace.

A tiny ``MiloSession.build_metadata`` (4 classes x 64 rows x 32 wide) runs
under ``jax.profiler`` on the CPU, inside a ``bench.window`` span as the
benchmark opens it.  The ``.xplane.pb`` is read by the benchmark's own
reader (``bench/spans.py``), which the CPU's trace serves for the host side
only, and the spans are checked for their nesting, their counts and their
byte arguments.
"""
from __future__ import annotations

from pathlib import Path

import jax
import numpy as np
import pytest

from bench import spans
from repro.selection import MiloSession, MiloSessionConfig

CLASSES, ROWS, WIDTH = 4, 64, 32
PREP_SEED = 7
LOOP = ("milo.build", "milo.preprocess")
PART = LOOP + ("milo.partition",)


def _session() -> MiloSession:
    return MiloSession(MiloSessionConfig(subset_fraction=0.1, n_sge_subsets=2,
                                         prep_seed=PREP_SEED))


def _data():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(CLASSES * ROWS, WIDTH)).astype(np.float32)
    return x, np.repeat(np.arange(CLASSES), ROWS)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The artifact built under the profiler, what ``spans.load`` reads of
    its trace, and each ``milo.*`` event's chain of enclosing span names."""
    x, y = _data()
    session = _session()
    session.build_metadata(x, y)  # compile outside the trace
    trace_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            md = session.build_metadata(x, y)
    finally:
        jax.profiler.stop_trace()
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    ops, bench_spans, events = spans.load(path)
    return md, ops, bench_spans, events, spans.chains(events)


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
    assert prep[3] == {"partitions": CLASSES}
    assert all(c[0] == "milo.build" for c in chains)


def test_one_partition_span_per_class(traced):
    md = traced[0]
    parts = _with_chain(traced, PART)
    assert len(parts) == CLASSES
    assert [p[3]["k_c"] for p in parts] == [int(b) for b in md.class_budgets]
    assert all(p[3]["n_c"] == ROWS for p in parts)
    assert len(_with_chain(traced, LOOP + ("milo.merge",))) == 1


def test_three_fetches_nested_in_each_partition(traced):
    chains = traced[4]
    assert sum(c[-1] == "milo.fetch" for c in chains) == 3 * CLASSES
    for part in _with_chain(traced, PART):
        inner = _inside(traced, part)
        fetches = [c for _, c in inner if c[-1] == "milo.fetch"]
        assert sorted(fetches) == [PART + ("milo.fetch",)] * 2 + [
            PART + ("milo.softmax", "milo.fetch")]
        children = [c[-1] for _, c in inner if len(c) == len(PART) + 1]
        assert children == ["milo.put", "milo.gram", "milo.sge", "milo.wre",
                            "milo.fetch", "milo.fetch", "milo.softmax"]


def test_byte_arguments_are_the_arrays_nbytes(traced):
    md = traced[0]
    x, _ = _data()
    (fp,) = _with_chain(traced, ("milo.build", "milo.fingerprint"))
    assert fp[3]["bytes"] == x.nbytes
    puts = _with_chain(traced, PART + ("milo.put",))
    assert [p[3]["bytes"] for p in puts] == [ROWS * x[0].nbytes] * CLASSES
    for part in _with_chain(traced, PART):
        sge, imp, probs = [ev for ev, c in _inside(traced, part)
                           if c[-1] == "milo.fetch"]
        n_c, k_c = part[3]["n_c"], part[3]["k_c"]
        n_run = 1 << (n_c - 1).bit_length()
        k_run = 1 << (k_c - 1).bit_length()
        # the SGE bank (int32) and the importances (float32) padded to the
        # class's pow2 bucket, then the class's probabilities (float32)
        assert sge[3]["bytes"] == md.config["n_sge_subsets"] * k_run * 4
        assert imp[3]["bytes"] == n_run * 4
        assert probs[3]["bytes"] == n_c * 4


def test_load_reads_the_spans_the_program_writes(traced):
    """What the benchmark's reduction reads of the trace: no device plane
    on the CPU, the window span, and the build with its fetch count."""
    _, ops, bench_spans, events, _ = traced
    assert ops == {}
    assert [n for _, _, n in bench_spans] == ["bench.window"]
    (lo, hi, _), = bench_spans
    assert spans.builds(events, lo, hi) == [
        {"m": CLASSES * ROWS, "prep_seed": PREP_SEED,
         "fetches": 3 * CLASSES}]


def test_tracing_leaves_the_artifact_unchanged(traced):
    md = traced[0]
    x, y = _data()
    plain = _session().build_metadata(x, y)
    np.testing.assert_array_equal(plain.sge_subsets, md.sge_subsets)
    np.testing.assert_array_equal(plain.wre_probs, md.wre_probs)
    assert plain.config == md.config
