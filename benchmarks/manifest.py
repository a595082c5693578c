"""Finds everything a cell needs by the names in BENCHMARK.json.

A later PR adds a configuration, a cell, a traffic mix or a per-layer
metric as new files plus one entry in BENCHMARK.json; nothing here lists
them. The files, all under benchmarks/:

    configs/<config>.json     sizes as run, precision, optimizer
    workloads/<cell>.json     runner, engine or step settings, limits of
                              `correct`, optional mesh axes
    traffic/<traffic>.json    parameters of one traffic mix; names the
                              generator module traffic/<generator>.py
    metrics/<metric>.json     reader module readers/<reader>.py + parameters
"""
from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(kind: str, name: str) -> dict:
    path = os.path.join(HERE, kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def benchmark_json(path: str | None = None) -> dict:
    """BENCHMARK.json, or for the tests another manifest of its shape."""
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class Cell:
    """One entry of `workloads`, with the files its names point at."""

    def __init__(self, name: str, bench: dict | None = None,
                 entry: dict | None = None):
        bench = bench if bench is not None else benchmark_json()
        self.bench = bench
        if entry is None:
            found = [w for w in bench["workloads"] if w["name"] == name]
            if not found:
                raise SystemExit(
                    f"no workload {name!r} in BENCHMARK.json; it has "
                    f"{[w['name'] for w in bench['workloads']]}")
            entry = found[0]
        self.name = name
        self.entry = entry
        self.chips = int(entry["chips"])
        self.config_name = entry["config"]
        self.config = _load("configs", entry["config"])
        self.traffic = _load("traffic", entry["traffic"])
        self.settings = _load("workloads", name)

    def runner(self):
        return importlib.import_module(
            f"benchmarks.runners.{self.settings['runner']}")

    def generator(self):
        return importlib.import_module(
            f"benchmarks.traffic.{self.traffic['generator']}")

    def _reports(self, metric: dict, e2e_names) -> bool:
        if "workloads" in metric:
            return self.name in metric["workloads"]
        moved = metric.get("moves")
        return moved is None or moved in e2e_names

    def end_to_end(self) -> list[dict]:
        return [m for m in self.bench["end_to_end"]
                if "workloads" not in m or self.name in m["workloads"]]

    def per_layer(self) -> list[dict]:
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if self._reports(m, e2e)]


def metric_reader(name: str):
    """(read function, the metric's own file) for one per-layer metric."""
    spec = _load("metrics", name)
    mod = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    return mod.read, spec
