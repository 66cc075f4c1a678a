"""``BENCHMARK.json`` and the files it names, found by name under a root.

The root is the checkout (the directory that holds ``BENCHMARK.json``).  A
cell names a configuration and a traffic mix; the traffic file names its
driver; a per-layer metric is read by ``bench/metrics/<name>.py``.  Nothing
here lists the cells, mixes or metrics that exist: adding one is adding
files.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType

ROOT = Path(__file__).resolve().parents[1]

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _load_module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    """One ``BENCHMARK.json`` and the files under ``root`` that it names."""

    def __init__(self, root: Path = ROOT, data: dict | None = None):
        self.root = Path(root)
        self.data = data if data is not None else json.loads(
            (self.root / "BENCHMARK.json").read_text())

    # -- entries of BENCHMARK.json -------------------------------------------

    def workload(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config_entry(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def end_to_end_for(self, cell: str) -> list[dict]:
        return [m for m in self.data["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer_for(self, cell: str) -> list[dict]:
        return [m for m in self.data["per_layer"]
                if cell in m.get("workloads", [cell])]

    # -- files found by name ---------------------------------------------------

    def config(self, name: str) -> dict:
        return json.loads((self.root / self.config_entry(name)["file"]).read_text())

    def traffic_path(self, name: str) -> Path:
        return self.root / "bench" / "traffic" / f"{name}.json"

    def traffic(self, name: str) -> dict:
        return json.loads(self.traffic_path(name).read_text())

    def driver_path(self, traffic: dict) -> Path:
        return self.root / "bench" / "drivers" / f"{traffic['driver']}.py"

    def driver(self, traffic: dict) -> ModuleType:
        return _load_module(self.driver_path(traffic),
                            f"bench_driver_{traffic['driver']}")

    def limits_path(self, cell: str) -> Path:
        return self.root / "bench" / "limits" / f"{cell}.json"

    def limits(self, cell: str) -> dict:
        """The limit of each number the cell's check compares."""
        return json.loads(self.limits_path(cell).read_text())["limits"]

    def metric_path(self, name: str) -> Path:
        return self.root / "bench" / "metrics" / f"{name}.py"

    def metric_reader(self, name: str) -> ModuleType:
        return _load_module(self.metric_path(name),
                            "bench_metric_" + name.replace(".", "_").replace("-", "_"))
