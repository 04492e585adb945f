"""Find a cell's files by the names ``BENCHMARK.json`` gives.

A cell names a configuration (``bench/configs/<config>.json``) and a traffic
mix (``bench/traffic/<traffic>.json``); a per-layer metric is
``bench/metrics/<name>.json`` naming a reader ``bench/readers/<reader>.py``;
a traffic file names the ``kind`` whose driver is ``bench/kinds/<kind>.py``
and a configuration the ``architecture`` whose adapter is
``bench/archs/<architecture>.py``, which names its plain reference
(``REFERENCE``, a module under ``bench/reference/``), each layer's leaves and
the walk a forward pass makes over the layers (``bench/archs/gpt2.py`` has
the adapter's interface). Nothing here, in the kinds or in the comparison
lists names or assumes that layers are alike or visited once: a later PR adds
files and one ``workloads`` entry, for a new architecture too (PERF.md
section 4 lists the files). What is not files yet: the training reference
follows a walk that visits every layer once (``reference/train_steps.py``).
"""
from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def module_name(name: str) -> str:
    """File stem for a kind, reader or architecture name (``-`` -> ``_``)."""
    return name.replace("-", "_").replace(".", "_")


class Cell:
    """One entry of ``workloads`` with its files loaded."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "bench")
        self.benchmark = _load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.benchmark["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                             f"{sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.benchmark["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = _load_json(os.path.join(root, self.config_entry["file"]))
        self.traffic = _load_json(os.path.join(
            self.bench_dir, "traffic", self.entry["traffic"] + ".json"))

    def _for_cell(self, metric) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self):
        return [m for m in self.benchmark["end_to_end"] if self._for_cell(m)]

    def per_layer(self):
        return [m for m in self.benchmark["per_layer"] if self._for_cell(m)]

    def kind(self):
        return importlib.import_module(
            "bench.kinds." + module_name(self.traffic["kind"]))

    def arch(self):
        return importlib.import_module(
            "bench.archs." + module_name(self.config["architecture"]))

    def metric_file(self, metric_name: str) -> dict:
        return _load_json(os.path.join(self.bench_dir, "metrics",
                                       metric_name + ".json"))

    def reader(self, metric_name: str):
        """The ``read(ctx, name)`` of the reader the metric's file names."""
        return importlib.import_module("bench.readers." + module_name(
            self.metric_file(metric_name)["reader"])).read


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip, by the exact ``device_kind``.
    An unknown device is an error, never a default."""
    table = _load_json(os.path.join(BENCH_DIR, "lib", "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"bench/lib/peaks.json has {sorted(table)}")
    return table[device_kind]
