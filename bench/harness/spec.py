"""Discovery by name: everything a cell needs is a file found from the names
in ``BENCHMARK.json``.

* ``configs/<config>.json`` holds the configuration as it is run; its
  ``builder`` names ``builders/<builder>.py``, which makes the weights from
  the seed (``build``) and gives the plain reference (``reference``);
* ``traffic/<traffic>.json`` holds a traffic mix: the name of its generator
  (``traffic/<generator>.py``) and the generator's parameters;
* ``workloads/<cell>.json`` holds what is fixed per cell: the arrivals
  (an offered rate, or a number of clients), the pre-roll, the traced slice and the correctness limits;
* ``metrics/<metric>.py`` holds one reader per per-layer metric.

A later change adds a cell, a configuration, a traffic mix or a metric by
adding such files and ``BENCHMARK.json`` entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by path (file names carry dots and dashes)."""
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Benchmark:
    """``BENCHMARK.json`` plus lookups of the files its names point at."""

    def __init__(self, root: str = ROOT, bench_dir: str = BENCH_DIR):
        self.root = root
        self.dir = bench_dir
        self.spec = _load_json(os.path.join(root, "BENCHMARK.json"))

    def workload(self, name: str) -> Dict[str, Any]:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config_entry(self, name: str) -> Dict[str, Any]:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def builder(self, conf: Dict[str, Any]):
        """The builder module a configuration file names."""
        name = conf["builder"]
        return load_module(os.path.join(self.dir, "builders", name + ".py"),
                           "bench_builder_" + _ident(name))

    def config_file(self, name: str) -> Dict[str, Any]:
        return _load_json(os.path.join(self.root,
                                       self.config_entry(name)["file"]))

    def traffic(self, name: str) -> Dict[str, Any]:
        return _load_json(os.path.join(self.dir, "traffic", name + ".json"))

    def generator(self, kind: str):
        return load_module(os.path.join(self.dir, "traffic", kind + ".py"),
                           "bench_traffic_" + _ident(kind))

    def cell(self, name: str) -> Dict[str, Any]:
        return _load_json(os.path.join(self.dir, "workloads", name + ".json"))

    def end_to_end(self, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.spec["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> List[Dict[str, Any]]:
        """Per-layer metrics this cell reports: those that list it, and those
        without a ``workloads`` key whose end-to-end metric it reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        out = []
        for m in self.spec["per_layer"]:
            if "workloads" in m:
                if cell in m["workloads"]:
                    out.append(m)
            elif m["moves"] in e2e:
                out.append(m)
        return out

    def metric_reader(self, name: str):
        return load_module(os.path.join(self.dir, "metrics", name + ".py"),
                           "bench_metric_" + _ident(name))


def _ident(name: str) -> str:
    return "".join(c if c.isalnum() else "_" for c in name)
