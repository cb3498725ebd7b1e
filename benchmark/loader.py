"""Finds every part of a cell by name: nothing here knows a cell, a
configuration, a mix or a metric by name. ``BENCHMARK.json`` names them;
each lives in a file of its own under ``benchmark/``:

* ``configs/<configuration>.json``   sizes, and ``"pipeline"``: the builder
* ``pipelines/<pipeline>.py``        ``build(ctx)``: the system under test
* ``traffic/<mix>.json``             parameters, and ``"generator"``
* ``generators/<generator>.py``      ``setup / window / collect / check``
* ``limits/<cell>.json``             the limit of each number ``correct`` compares
* ``layer_metrics/<metric>.py``      ``read(ctx) -> float | None``
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchmarkError(Exception):
    """The benchmark cannot run, or a run did not hold together."""


def _json(path: str) -> dict:
    if not os.path.isfile(path):
        raise BenchmarkError(f"no such file: {os.path.relpath(path, ROOT)}")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def module(kind: str, name: str, here: str = HERE):
    """``<here>/<kind>/<name>.py`` as a module (metric names may hold dots)."""
    path = os.path.join(here, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchmarkError(f"no such file: {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    def __init__(self, spec: dict, name: str, here: str = HERE):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise BenchmarkError(
                f"no workload {name!r} in BENCHMARK.json (has: {sorted(cells)})"
            )
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        config_entry = {c["name"]: c for c in spec["configs"]}[self.entry["config"]]
        self.config = _json(os.path.join(os.path.dirname(here), config_entry["file"]))
        self.traffic = _json(os.path.join(here, "traffic", self.entry["traffic"] + ".json"))
        self.limits = _json(os.path.join(here, "limits", name + ".json"))
        self.pipeline = module("pipelines", self.config["pipeline"], here)
        self.generator = module("generators", self.traffic["generator"], here)
        self.end_to_end = [m for m in spec["end_to_end"] if self._reports(m, spec)]
        self.per_layer = [m for m in spec["per_layer"] if self._reports(m, spec)]
        self._here = here

    def _reports(self, metric: dict, spec: dict) -> bool:
        if "workloads" in metric:
            return self.name in metric["workloads"]
        moves = metric.get("moves")
        if moves is None:
            return True
        moved = {m["name"]: m for m in spec["end_to_end"]}[moves]
        return self.name in moved.get("workloads", [self.name])

    def reader(self, metric_name: str):
        return module("layer_metrics", metric_name, self._here).read


def load(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))
