"""BENCHMARK.json and the data files it names, resolved by name.

Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own; this module only FINDS them:

    configs/<config>.json        traffic/<traffic>.json
    queries/<query>.py           generators/<generator>.py
    end_to_end/<metric>.py       layer_metrics/<metric>.json
    readers/<reader>.py

so a later PR adds a cell, a mix or a metric by adding files and entries,
never by editing a file that is there. ``bench_dir`` is injectable so a
test can resolve a second, tiny benchmark from a temporary directory.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Any, Optional

__all__ = ["Spec", "Cell", "load_spec", "load_module", "BENCH_DIR",
           "REPO_ROOT"]

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def _read_json(path: str) -> Any:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(bench_dir: str, kind: str, name: str):
    """Import ``<bench_dir>/<kind>/<name>.py`` by path (not by package
    name, so a test's temporary directory works the same way). A
    directory that holds only data files finds the code in this one."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.isfile(path):
        path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Cell:
    """One entry of ``workloads`` with its files loaded."""
    name: str
    chips: int
    why: str
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list[dict] = field(default_factory=list)
    per_layer: list[dict] = field(default_factory=list)


class Spec:
    def __init__(self, benchmark: dict, bench_dir: str):
        self.benchmark = benchmark
        self.bench_dir = bench_dir
        self.run_seconds = int(benchmark["run_seconds"])

    # -- lookups -----------------------------------------------------------
    def workload_names(self) -> list[str]:
        return [w["name"] for w in self.benchmark["workloads"]]

    def _config_entry(self, name: str) -> dict:
        for c in self.benchmark["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"workload names configuration {name!r}, which "
                       "BENCHMARK.json does not list")

    def config_path(self, name: str) -> str:
        # BENCHMARK.json gives the file relative to the repo root; inside
        # an injected bench_dir only its basename is meaningful
        return os.path.join(self.bench_dir, "configs",
                            os.path.basename(self._config_entry(name)["file"]))

    def traffic_path(self, name: str) -> str:
        return os.path.join(self.bench_dir, "traffic", f"{name}.json")

    def layer_metric_path(self, name: str) -> str:
        return os.path.join(self.bench_dir, "layer_metrics", f"{name}.json")

    def _applies(self, metric: dict, cell_name: str) -> bool:
        cells = metric.get("workloads")
        return cells is None or cell_name in cells

    def cell(self, name: str) -> Cell:
        entry = next((w for w in self.benchmark["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise KeyError(f"unknown workload {name!r}; BENCHMARK.json has "
                           f"{self.workload_names()}")
        e2e = [m for m in self.benchmark["end_to_end"]
               if self._applies(m, name)]
        e2e_names = {m["name"] for m in e2e}
        layer = [m for m in self.benchmark["per_layer"]
                 if self._applies(m, name) and m["moves"] in e2e_names]
        return Cell(name=name, chips=int(entry["chips"]), why=entry["why"],
                    config_name=entry["config"],
                    traffic_name=entry["traffic"],
                    config=_read_json(self.config_path(entry["config"])),
                    traffic=_read_json(self.traffic_path(entry["traffic"])),
                    end_to_end=e2e, per_layer=layer)

    def layer_metric(self, name: str) -> dict:
        return _read_json(self.layer_metric_path(name))

    def module(self, kind: str, name: str):
        return load_module(self.bench_dir, kind, name)


def load_spec(benchmark_json: Optional[str] = None,
              bench_dir: Optional[str] = None) -> Spec:
    path = benchmark_json or os.path.join(REPO_ROOT, "BENCHMARK.json")
    return Spec(_read_json(path), bench_dir or BENCH_DIR)
