"""Finding a cell's files by name.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix;
each is a JSON file of its own, found by name:

- ``configs/<config>.json``   (the ``file`` the configuration names),
- ``traffic/<traffic>.json``,
- ``limits/<cell>.json``      (the limits of its correctness check),
- ``metrics/<metric>.py``     (one reader per per-layer metric).

So adding a configuration, a traffic mix or a per-layer metric is adding
files and ``BENCHMARK.json`` entries; no harness code names any of them.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))


def find_root(start: str = HERE) -> str:
    """The checkout root: the nearest directory above that holds
    ``BENCHMARK.json``."""
    d = start
    while True:
        if os.path.exists(os.path.join(d, "BENCHMARK.json")):
            return d
        parent = os.path.dirname(d)
        if parent == d:
            raise FileNotFoundError("no BENCHMARK.json above " + start)
        d = parent


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One workload of BENCHMARK.json with its configuration, traffic
    mix, limits and per-layer metric entries loaded."""

    def __init__(self, root: str, name: str, bench: dict = None,
                 base: str = HERE):
        self.root = root
        self.base = base
        self.bench = bench or _load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"unknown workload {name!r}; known: "
                           f"{sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = _load_json(os.path.join(root,
                                              self.config_entry["file"]))
        self.traffic = _load_json(os.path.join(
            base, "traffic", self.entry["traffic"] + ".json"))
        self.limits = _load_json(os.path.join(base, "limits", name + ".json"))
        self.chips = int(self.entry["chips"])

    def _reports(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self) -> List[dict]:
        return [m for m in self.bench["end_to_end"] if self._reports(m)]

    def per_layer(self) -> List[dict]:
        return [m for m in self.bench["per_layer"] if self._reports(m)]


def metric_reader(name: str, base: str = HERE) -> Callable:
    """``metrics/<name>.py``'s ``read(ctx)``: returns the metric's value,
    or None where the trace holds nothing to read."""
    path = os.path.join(base, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chip_bench_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(cell: Cell, ctx) -> Dict[str, dict]:
    """Every per-layer metric of the cell whose reader finds something."""
    out = {}
    for m in cell.per_layer():
        v = metric_reader(m["name"], cell.base)(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def missing_metrics(cell: Cell, metrics: Dict[str, dict]) -> List[str]:
    """The cell's per-layer metrics that ``metrics`` lacks: a reader that
    found nothing, because the program it reads was renamed or fused."""
    return [m["name"] for m in cell.per_layer() if m["name"] not in metrics]
