"""``BENCHMARK.json`` and the files it names: a cell's configuration, its
traffic mix, and each per-layer metric's reader. Everything is found by
name, so a later cell, configuration or metric is new files and new entries."""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"{what} {name!r} is not in BENCHMARK.json "
                   f"(has: {', '.join(e['name'] for e in entries)})")


class Cell:
    """One entry of ``workloads`` with its configuration file, its traffic
    file and the modules those name."""

    def __init__(self, name: str):
        bench = benchmark()
        self.bench = bench
        self.spec = by_name(bench["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.spec["chips"])
        cfg = by_name(bench["configs"], self.spec["config"], "config")
        self.config = load_json(ROOT, cfg["file"])
        self.traffic = load_json(HERE, "traffic",
                                 self.spec["traffic"] + ".json")
        self.entry = importlib.import_module(
            "perfbench.entries." + self.config["entry"])
        self.reference = importlib.import_module(
            "perfbench.reference." + self.config["reference"])
        self.limits = self.config["correct"]["limits"]

    def reports(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"] if self.reports(m)]

    def per_layer(self) -> list:
        return [m for m in self.bench["per_layer"] if self.reports(m)]


def reader_of(metric_name: str):
    """``(read, args)`` of a per-layer metric: ``metrics/<name>.json`` names
    a module of ``readers/`` and the arguments it is called with."""
    spec = load_json(HERE, "metrics", metric_name + ".json")
    mod = importlib.import_module("perfbench.readers." + spec["reader"])
    return mod.read, spec.get("args", {})


def peaks(device_kind: str) -> dict:
    table = load_json(HERE, "peaks.json")["device_kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"perfbench/peaks.json (has: {', '.join(table)})")
    return table[device_kind]
