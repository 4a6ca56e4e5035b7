"""Finds the benchmark's parts by name: ``BENCHMARK.json`` at the checkout
root, ``configs/<config>.json``, ``traffic/<traffic>.json``,
``metrics/<metric>.py`` and ``peaks.json`` beside this file.

A later cell, traffic mix or metric is a new file and a new entry; nothing
here changes for it.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _file(root: str, kind: str, name: str, ext: str) -> str:
    if not NAME.match(name):
        raise ValueError(f"{kind} name {name!r} is not a benchmark name")
    path = os.path.join(root, kind, name + ext)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    return path


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Catalog:
    """The benchmark's files: configurations and traffic under ``root``
    (this directory by default; the tests point it at small files of their
    own), metric readers and peaks beside this file."""

    def __init__(self, root: str = HERE,
                 benchmark: str = os.path.join(CHECKOUT, "BENCHMARK.json")):
        self.root = root
        self.benchmark_path = benchmark

    def benchmark(self) -> dict:
        return load_json(self.benchmark_path)

    def config(self, name: str) -> dict:
        return load_json(_file(self.root, "configs", name, ".json"))

    def traffic(self, name: str) -> dict:
        return load_json(_file(self.root, "traffic", name, ".json"))

    def metric(self, name: str):
        """The reader module of a per-layer metric: ``read(record)`` gives
        its number, or None where the run had nothing to read."""
        path = _file(HERE, "metrics", name, ".py")
        spec = importlib.util.spec_from_file_location(
            "chipbench_metric_" + re.sub(r"\W", "_", name), path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def peaks(self, device_kind: str) -> dict:
        table = load_json(os.path.join(HERE, "peaks.json"))
        if device_kind not in table["devices"]:
            raise KeyError(f"no peaks for device kind {device_kind!r}; "
                           f"peaks.json has {sorted(table['devices'])}")
        return table["devices"][device_kind]

    def cell(self, workload: str) -> dict:
        """The cell's entry, its configuration and traffic, and the
        end-to-end and per-layer metric entries that it reports."""
        bench = self.benchmark()
        cells = [w for w in bench["workloads"] if w["name"] == workload]
        if not cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        cell = cells[0]

        def reports(metric):
            return workload in metric.get("workloads", [workload])

        end_to_end = [m for m in bench["end_to_end"] if reports(m)]
        reported = {m["name"] for m in end_to_end}
        per_layer = [m for m in bench["per_layer"]
                     if reports(m) and m["moves"] in reported]
        return {"cell": cell, "config": self.config(cell["config"]),
                "traffic": self.traffic(cell["traffic"]),
                "end_to_end": end_to_end, "per_layer": per_layer}
