"""Finds everything by the names in BENCHMARK.json.

* a configuration ``<name>``: ``bench/configs/<name>.json`` (the file the
  ``configs`` entry names);
* a traffic mix ``<name>``: ``bench/traffic/<name>.json``;
* a cell ``<name>``: ``bench/cells/<name>.json``, what belongs to the cell
  and not to its mix (the offered rate), laid over the mix's parameters;
* a per-layer metric ``<name>``: ``bench/metrics/<name>.py``, a reader
  with ``UNIT``, ``LAYER``, ``MOVES`` and ``read(record)``.

A later change adds a configuration, a mix or a metric by adding its file
and its entry, without editing any file that is already here.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from types import ModuleType
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    config: Dict                # the configuration file's contents
    traffic_name: str
    traffic: Dict               # the mix's parameters with the cell's
    chips: int
    end_to_end: List[Dict]      # the metric entries this cell reports
    per_layer: List[Dict]


def load_benchmark(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    return Cell(
        name=name, config_name=w["config"], config=config,
        traffic_name=w["traffic"],
        traffic=dict(traffic(w["traffic"]), **_data("cells", name)),
        chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def _data(kind: str, name: str) -> Dict:
    with open(os.path.join(BENCH_DIR, kind, f"{name}.json")) as f:
        return json.load(f)


def traffic(name: str) -> Dict:
    """A mix's parameters alone; a cell adds its rate (``cell().traffic``)."""
    return _data("traffic", name)


def reader(name: str) -> ModuleType:
    """The per-layer metric's reader module, loaded from its file (a name
    may hold dots, so it is loaded by path, not imported by name)."""
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
