"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A configuration is ``bench/configs/<config>.json`` (its sizes, its topology
and the name of its driver and plain reference), a traffic mix is
``bench/traffic/<traffic>.json`` (parameters only), a driver is
``bench/drivers/<driver>.py`` and a metric is ``bench/metrics/<name>.py``.
A later cell adds files and entries; nothing here names a cell.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
from dataclasses import dataclass
from types import ModuleType
from typing import Dict, List

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path) -> ModuleType:
    """Import a file by path (metric files carry dots in their names)."""
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    name = "bench_file_" + "".join(c if c.isalnum() else "_"
                                   for c in str(path.resolve()))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


@dataclass
class Cell:
    """One entry of ``workloads`` with its files resolved."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: pathlib.Path = BENCH_DIR

    def driver(self) -> ModuleType:
        return load_module(self.bench_dir / "drivers"
                           / f"{self.config['driver']}.py")

    def reference(self) -> ModuleType:
        return load_module(self.bench_dir / "configs"
                           / f"{self.config['reference']}.py")

    def metric_readers(self, trace: bool) -> Dict[str, ModuleType]:
        specs = self.per_layer if trace else self.end_to_end
        return {m["name"]: load_module(self.bench_dir / "metrics"
                                       / f"{m['name']}.py") for m in specs}

    def metric_spec(self, name: str) -> dict:
        for m in self.end_to_end + self.per_layer:
            if m["name"] == name:
                return m
        raise KeyError(name)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        known = [w["name"] for w in bench["workloads"]]
        raise KeyError(f"unknown workload {workload!r}; known: {known}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    bench_dir = root / "bench"
    config = load_json(root / conf["file"])
    traffic = load_json(bench_dir / "traffic" / f"{entry['traffic']}.json")
    return Cell(
        name=workload, chips=int(entry["chips"]), config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        bench_dir=bench_dir)
