"""BENCHMARK.json against its contract, and the harness finding files by name.

Everything here runs on the CPU and touches no TPU library.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import data, trace
from bench.device import UnknownDeviceError, peaks
from bench.manifest import NAME_RE, ROOT, UNIT_RE, Manifest

M = Manifest(ROOT)
B = M.data
E2E = {m["name"]: m for m in B["end_to_end"]}
CELLS = [w["name"] for w in B["workloads"]]


def test_top_level_keys_and_limits():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= B["run_seconds"] <= 51 and isinstance(B["run_seconds"], int)
    assert 1 <= len(B["paths"]) <= 16
    for p in B["paths"]:
        assert (ROOT / p).is_dir() and ".." not in p and not p.startswith("/")
    assert len(B["command"]) <= 32
    assert len(json.dumps(B).encode()) <= 64 * 1024
    total = 2 + 14 * 24
    assert total * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_text_fields():
    names = ([c["name"] for c in B["configs"]] + CELLS
             + [m["name"] for m in B["end_to_end"] + B["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME_RE.match(n), n
    for w in B["workloads"]:
        assert NAME_RE.match(w["config"]) and NAME_RE.match(w["traffic"])
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT_RE.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for text in ([w["why"] for w in B["workloads"]]
                 + [c["source"] for c in B["configs"]]
                 + [c["why"] for c in B["configs"]]
                 + [m["layer"] for m in B["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_entry_keys():
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_names_existing_files(cell):
    w = M.workload(cell)
    entry = M.config_entry(w["config"])
    assert (ROOT / entry["file"]).is_file()
    config = M.config(w["config"])
    for key in entry["reduced"]:
        assert NAME_RE.match(key) and key in config
    traffic = M.traffic(w["traffic"])
    assert M.driver_path(traffic).is_file()
    assert M.limits(cell)
    reported = [m["name"] for m in M.end_to_end_for(cell)]
    assert "setup_s" in reported and len(reported) >= 2
    assert M.per_layer_for(cell)


def test_every_config_is_used():
    used = {w["config"] for w in B["workloads"]}
    assert used == {c["name"] for c in B["configs"]}
    files = [c["file"] for c in B["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("metric", [m["name"] for m in B["per_layer"]])
def test_per_layer_metric_moves_a_reported_metric(metric):
    m = next(x for x in B["per_layer"] if x["name"] == metric)
    assert M.metric_path(metric).is_file()
    moved = E2E[m["moves"]]
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS
        assert cell in moved.get("workloads", CELLS)


def test_layer_names_are_those_of_perf_md():
    perf = (ROOT / "PERF.md").read_text()
    for m in B["per_layer"]:
        assert f"| {m['layer']} |" in perf, m["layer"]


def test_unknown_device_kind_raises():
    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(UnknownDeviceError):
        peaks("TPU v99")


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_subseed_takes_seeds_past_32_bits():
    big = 2**33 + 12345
    s = data.subseed(big, 1)
    assert 0 <= s < 2**31 and s == data.subseed(big, 1) != data.subseed(big, 2)


def test_trace_reduction_on_a_synthetic_trace():
    ms = 1_000_000
    spans = [(0, 100 * ms, "bench.window"), (10 * ms, 30 * ms, "bench.step"),
             (40 * ms, 70 * ms, "bench.batch"), (45 * ms, 50 * ms, "bench.plan")]
    ops = {"/device:TPU:0": [(10 * ms, 30 * ms, "fusion"), (20 * ms, 35 * ms, "dot"),
                             (80 * ms, 90 * ms, "fusion"), (150 * ms, 160 * ms, "late")]}
    red = trace.reduce(ops, spans)
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.035)  # 10-35 and 80-90
    assert red["device_ops"] == [["fusion", pytest.approx(0.03)],
                                 ["dot", pytest.approx(0.015)]]
    assert red["idle_gaps"][0] == ["bench.batch", pytest.approx(0.045)]
    assert red["idle_gaps"][1] == ["no bench span", pytest.approx(0.01)]
    assert [g[1] for g in red["idle_gaps"]] == sorted(
        [g[1] for g in red["idle_gaps"]], reverse=True)


def test_an_added_cell_is_found_with_no_edit(tmp_path):
    """A new configuration, traffic mix, limits and BENCHMARK.json entry,
    as files only, run end to end on the CPU."""
    import jax

    from bench.run import run_cell

    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = {"name": "tiny-sel", "classes": 3, "rows_per_class": 40, "width": 16}
    (tmp_path / "bench/configs/tiny-sel.json").write_text(json.dumps(config))
    traffic = dict(M.traffic("paper-default"), n_sge_subsets=2)
    (tmp_path / "bench/traffic/tiny-mix.json").write_text(json.dumps(traffic))
    (tmp_path / "bench/limits/sel-tiny.json").write_text(json.dumps(
        {"limits": {"imp_gap": 1e-3, "bank_bad": 0}}))
    manifest = json.loads(json.dumps(B))
    manifest["configs"].append({"name": "tiny-sel", "source": "test",
                                "file": "bench/configs/tiny-sel.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "sel-tiny", "config": "tiny-sel",
                                  "traffic": "tiny-mix", "chips": 1, "why": "test"})
    manifest["end_to_end"][0]["workloads"].append("sel-tiny")
    (tmp_path / "bench/metrics/artifacts_built.tiny.py").write_text(
        "def read(run):\n    return float(run['artifacts'])\n")
    manifest["per_layer"].append({
        "name": "artifacts_built.tiny", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "device", "moves": "select_s",
        "workloads": ["sel-tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    m = Manifest(tmp_path)
    assert [x["name"] for x in m.per_layer_for("sel-tiny")] == ["artifacts_built.tiny"]
    assert m.metric_reader("artifacts_built.tiny").read({"artifacts": 4}) == 4.0
    cell = m.workload("sel-tiny")
    result = run_cell(m, cell, seed=3, seconds=0.2, trace=False,
                      devices=jax.devices(), t_start=time.perf_counter())
    assert result["correct"] is True
    assert set(result["metrics"]) == {"select_s", "setup_s"}
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == {"imp_gap", "bank_bad"}
