"""Benchmark master: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and merges every measured row
into a tracked JSON trajectory file so the perf trajectory is
machine-readable across PRs, not just printed: ``bench_training``'s rows
land in ``BENCH_training.json`` (the training/tuning hot-path trajectory),
everything else in ``BENCH_selection.json``.  Override the paths with
``BENCH_TRAINING_JSON`` / ``BENCH_JSON``; ``BENCH_JSON=0`` disables ALL
writes.  Set ``BENCH_FAST=1`` to run a reduced subset (CI smoke); pass
module names as argv to run a subset, e.g.
``python -m benchmarks.run preprocess kernels``.

  bench_set_functions  — Fig. 4 (set-function composition)
  bench_exploration    — Fig. 5 (SGE vs WRE vs curriculum)
  bench_training       — Fig. 6 / Tab. 5,7 (MILO vs baselines, speedup/deg)
  bench_tuning         — Fig. 7 / Tab. 9,10 (hparam tuning + Kendall-tau)
  bench_ablations      — Tab. 1,2,13,14 (hardness, kappa, R)
  bench_preprocess     — App. H.3 (preprocess cost, greedy/SGE throughput)
  bench_kernels        — kernel microbenches
  bench_serving        — warm MiloServer vs N cold sessions (concurrent tuning)
  bench_hierarchical   — partition→refine selection at flat-infeasible n
  bench_multihost      — two-process selection vs single-process (bit-identity)
"""
from __future__ import annotations

import datetime
import json
import os
import sys
import time

DEFAULT_JSON_PATH = "BENCH_selection.json"
DEFAULT_TRAINING_JSON_PATH = "BENCH_training.json"


def parse_row(row: str) -> tuple[str, dict] | None:
    """``name,us_per_call,derived`` -> (name, record); None for non-rows."""
    if row.startswith("#"):
        return None
    parts = row.split(",", 2)
    if len(parts) != 3:
        return None
    name, us, derived = parts
    try:
        return name, {"us_per_call": float(us), "derived": derived}
    except ValueError:
        return None


def write_json(rows: list[str], path: str, *, fmt: str = "bench-selection") -> None:
    """Merge measured rows into the JSON trajectory file keyed by benchmark
    name, so partial runs (module subsets, BENCH_FAST) refresh their own
    entries without clobbering the rest.  Each record carries backend/fast
    metadata so a CPU smoke row is never mistaken for a TPU trajectory
    point."""
    try:
        import jax

        backend = jax.default_backend()
        device_count = jax.device_count()
    except Exception:  # benchmarks ran, so this is near-impossible; be safe
        backend = "unknown"
        device_count = 0
    if device_count > 1:
        # only rows emitted by the sharded benches get the axis stamp below
        from repro.core.sharded import AXIS as shard_axis
    else:
        shard_axis = None
    fast = os.environ.get("BENCH_FAST") == "1"
    doc: dict = {"format": fmt, "version": 1, "benchmarks": {}}
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
            if isinstance(prev.get("benchmarks"), dict):
                doc["benchmarks"] = prev["benchmarks"]
        except (json.JSONDecodeError, OSError):
            pass  # unreadable trajectory file: start fresh rather than crash
    stamp = datetime.datetime.now(datetime.timezone.utc).isoformat(timespec="seconds")
    for row in rows:
        parsed = parse_row(row)
        if parsed is None:
            continue
        name, rec = parsed
        rec["measured_at"] = stamp
        rec["backend"] = backend
        rec["device_count"] = device_count
        if shard_axis is not None and "sharded" in name:
            rec["shard_axis"] = shard_axis
        if fast:
            rec["bench_fast"] = True
        doc["benchmarks"][name] = rec
    doc["updated"] = stamp
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def main(argv: list[str] | None = None) -> None:
    from benchmarks import (
        bench_ablations,
        bench_exploration,
        bench_hierarchical,
        bench_kernels,
        bench_multihost,
        bench_preprocess,
        bench_serving,
        bench_set_functions,
        bench_training,
        bench_tuning,
    )

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    argv = sys.argv[1:] if argv is None else argv
    fast = os.environ.get("BENCH_FAST") == "1"
    # third field: which tracked trajectory file the module's rows merge into
    modules = [
        ("set_functions", bench_set_functions, "selection"),
        ("exploration", bench_exploration, "selection"),
        ("training", bench_training, "training"),
        ("serving", bench_serving, "training"),
        ("tuning", bench_tuning, "selection"),
        ("ablations", bench_ablations, "selection"),
        ("preprocess", bench_preprocess, "selection"),
        ("kernels", bench_kernels, "selection"),
        ("hierarchical", bench_hierarchical, "selection"),
        ("multihost", bench_multihost, "selection"),
    ]
    if argv:
        known = {name for name, _, _ in modules}
        unknown = [a for a in argv if a not in known]
        if unknown:
            raise SystemExit(f"unknown benchmark modules {unknown}; available: {sorted(known)}")
        modules = [m for m in modules if m[0] in argv]
    elif fast:
        modules = [m for m in modules if m[0] in ("preprocess", "kernels")]

    print("name,us_per_call,derived")
    t0 = time.time()
    failures = 0
    rows_by_target: dict[str, list[str]] = {"selection": [], "training": []}
    for name, mod, target in modules:
        t1 = time.time()
        try:
            rows = mod.run(verbose=False)
            rows_by_target[target].extend(rows)
            for r in rows:
                print(r, flush=True)
            print(f"# {name} done in {time.time()-t1:.1f}s", flush=True)
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
    json_path = os.environ.get("BENCH_JSON", DEFAULT_JSON_PATH)
    training_path = os.environ.get("BENCH_TRAINING_JSON",
                                   DEFAULT_TRAINING_JSON_PATH)
    if json_path != "0":
        if rows_by_target["selection"]:
            write_json(rows_by_target["selection"], json_path)
            print(f"# wrote {json_path}")
        if rows_by_target["training"] and training_path != "0":
            write_json(rows_by_target["training"], training_path,
                       fmt="bench-training")
            print(f"# wrote {training_path}")
    print(f"# total {time.time()-t0:.1f}s, failures={failures}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
