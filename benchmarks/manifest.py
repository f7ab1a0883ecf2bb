"""Reads ``BENCHMARK.json`` and finds, by name, the files a cell is made of:
its configuration, its traffic mix, the entry and the loop that the traffic
file names, and the reader of each per-layer metric.  No name of a cell, a
configuration or a metric appears in the harness's code.
"""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load(path: "str | None" = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    def __init__(self, manifest: dict, workload: str):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if workload not in cells:
            raise KeyError(
                f"no workload {workload!r} in BENCHMARK.json "
                f"(have {sorted(cells)})"
            )
        self.manifest = manifest
        self.name = workload
        self.spec = cells[workload]
        self.chips = int(self.spec["chips"])
        cfg = {c["name"]: c for c in manifest["configs"]}[self.spec["config"]]
        self.config_name = cfg["name"]
        self.config = _json(os.path.join(ROOT, cfg["file"]))
        self.traffic_name = self.spec["traffic"]
        self.traffic = _json(
            os.path.join(HERE, "traffic", self.traffic_name + ".json")
        )
        self.entry = importlib.import_module(
            "benchmarks.entries." + self.traffic["entry"]
        )
        self.loop = importlib.import_module(
            "benchmarks.loops." + self.traffic["loop"]
        )

    def _reported(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> "list[dict]":
        return [m for m in self.manifest["end_to_end"] if self._reported(m)]

    def per_layer(self) -> "list[dict]":
        return [m for m in self.manifest["per_layer"] if self._reported(m)]


def reader(folder: str, name: str):
    """``benchmarks/<folder>/<name>.py`` (``layers`` or ``end_to_end``);
    dots and dashes of a metric's name become underscores in the file's."""
    return importlib.import_module(
        f"benchmarks.{folder}." + name.replace(".", "_").replace("-", "_")
    )
