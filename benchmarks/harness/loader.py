"""Finds a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own under ``benchmarks/``; a later
PR adds files and entries and edits nothing here.
"""

from __future__ import annotations

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmarks")


def _read(path):
    with open(path) as f:
        return json.load(f)


def manifest():
    return _read(os.path.join(ROOT, "BENCHMARK.json"))


class Cell:
    """One workload of the manifest with its configuration, its traffic
    mix and the metrics it reports."""

    def __init__(self, name: str, man=None):
        man = man or manifest()
        rows = [w for w in man["workloads"] if w["name"] == name]
        if not rows:
            raise SystemExit(f"unknown workload {name!r}; have "
                             f"{[w['name'] for w in man['workloads']]}")
        self.name, self.manifest, self.row = name, man, rows[0]
        self.chips = int(self.row["chips"])
        self.config = _read(os.path.join(
            BENCH_DIR, "configs", self.row["config"] + ".json"))
        self.traffic = _read(os.path.join(
            BENCH_DIR, "traffic", self.row["traffic"] + ".json"))
        self.adapter = importlib.import_module(
            "benchmarks.adapters." + self.config["family"])

    def _applies(self, metric):
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self):
        return [m for m in self.manifest["end_to_end"] if self._applies(m)]

    def per_layer(self):
        return [m for m in self.manifest["per_layer"] if self._applies(m)]


def metric_spec(name: str):
    """The metric's own file: the reader's name and its arguments (unit,
    layer, ``moves`` and cells are the manifest's alone)."""
    return _read(os.path.join(BENCH_DIR, "metrics", name + ".json"))


def reader(name: str):
    return importlib.import_module("benchmarks.readers." + name).read
