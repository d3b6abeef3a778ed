"""Finds everything a cell needs by its name in ``BENCHMARK.json``.

A later change adds a configuration, a traffic mix, a cell, a driver or a
per-layer metric by adding files under these names and entries in
``BENCHMARK.json``; nothing here lists them.
"""
from __future__ import annotations

import importlib.util
import json
import os


def load_module(path: str, name: str):
    """Import a file by path (so metric readers and drivers need no
    package entry and may sit in any benchmark root)."""
    spec = importlib.util.spec_from_file_location(
        "bench_dyn_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Bench:
    """``BENCHMARK.json`` at ``root`` and the files it names."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.spec = _json(os.path.join(self.root, "BENCHMARK.json"))
        self.dir = os.path.join(self.root, "bench")

    def _entry(self, key: str, name: str) -> dict:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {key} entry named {name!r} in BENCHMARK.json")

    def workload(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        entry = self._entry("configs", name)
        out = _json(os.path.join(self.root, entry["file"]))
        out.setdefault("name", name)
        return out

    def traffic(self, name: str) -> dict:
        return _json(os.path.join(self.dir, "traffic", name + ".json"))

    def cell(self, name: str) -> dict:
        """Engine sizes and correctness limits of one cell."""
        return _json(os.path.join(self.dir, "cells", name + ".json"))

    def driver(self, name: str):
        return load_module(os.path.join(self.dir, "drivers", name + ".py"),
                           "driver_" + name)

    def metrics_for(self, workload: str, kind: str) -> list:
        """The metric entries a cell reports: ``end_to_end`` or
        ``per_layer``, restricted by each entry's ``workloads`` key."""
        return [m for m in self.spec[kind]
                if workload in m.get("workloads", [workload])]

    def reader(self, metric: str):
        return load_module(os.path.join(self.dir, "metrics", metric + ".py"),
                           "metric_" + metric)
