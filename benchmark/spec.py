"""Finds a cell's files by the names in ``BENCHMARK.json``.

    <root>/workloads/<cell>.json             the traffic mix, as parameters
    the ``file`` of the cell's configuration      the configuration, as run
    benchmark/end_to_end/<metric>.json       an end-to-end metric's reader
    benchmark/layer_metrics/<metric>.json    a per-layer metric's reader

``root`` is the ``benchmark/`` directory (``selftest/tiny`` holds tiny
cells with a ``BENCHMARK.json`` of their own, for CPU rehearsals).  A later PR adds a file and an entry in
``BENCHMARK.json``; nothing here names a cell, a configuration or a
metric.
"""

from __future__ import annotations

import importlib
import json
import os
import pkgutil

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_benchmark_json(root: str) -> str:
    """``<root>/BENCHMARK.json`` where a root has one of its own (the
    tiny cells), else the repo's."""
    for path in (os.path.join(root, "BENCHMARK.json"),
                 os.path.join(os.path.dirname(root), "BENCHMARK.json")):
        if os.path.exists(path):
            return path
    raise SystemExit(f"no BENCHMARK.json at or above {root}")


class Spec:
    """One cell: its workload, its configuration and the metrics that
    ``BENCHMARK.json`` lists for it."""

    def __init__(self, root: str, workload: str):
        self.root = root
        self.bench = _load(find_benchmark_json(root))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; "
                             f"BENCHMARK.json has {sorted(cells)}")
        self.cell = cells[workload]
        self.name = workload
        self.chips = int(self.cell["chips"])
        self.workload = _load(os.path.join(root, "workloads",
                                           f"{workload}.json"))
        configs = {c["name"]: c for c in self.bench["configs"]}
        entry = configs[self.cell["config"]]
        self.config = _load(os.path.join(CHECKOUT, entry["file"]))
        for key in ("config", "chips"):
            if self.workload[key] != self.cell[key]:
                raise SystemExit(
                    f"{workload}: {key} differs between BENCHMARK.json "
                    "and the workload file")

    def _applies(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def metrics(self, kind: str) -> list:
        """[(entry of BENCHMARK.json, the metric's own file)] for this
        cell; ``kind`` is ``end_to_end`` or ``per_layer``."""
        folder = "end_to_end" if kind == "end_to_end" else "layer_metrics"
        out = []
        for m in self.bench[kind]:
            if self._applies(m):
                out.append((m, _load(os.path.join(
                    HERE, folder, f"{m['name']}.json"))))
        return out


def load_registry(package: str, attr: str) -> dict:
    """Merge the ``attr`` dict of every module of ``benchmark.<package>``:
    source kinds, drivers and reducers are looked up by name, and a new
    file in the directory extends the table."""
    table: dict = {}
    pkg = importlib.import_module(f"benchmark.{package}")
    for info in sorted(pkgutil.iter_modules(pkg.__path__),
                       key=lambda i: i.name):
        mod = importlib.import_module(f"benchmark.{package}.{info.name}")
        for key, value in getattr(mod, attr, {}).items():
            if key in table:
                raise SystemExit(f"{package}: {key!r} is defined twice")
            table[key] = value
    return table
